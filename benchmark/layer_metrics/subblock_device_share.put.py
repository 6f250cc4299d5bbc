"""Codec route: blocks of objects under one block that the device encoded over all such blocks handed to the batch former, over the window (scheduler.stats: subblock_device_blocks / subblock_blocks). None where the program has no such counters, or saw no such object."""


def read(win):
    v0, v1 = win["c0"]["verbs"]["encode"], win["c1"]["verbs"]["encode"]
    if win["verb"] != "encode" or "subblock_blocks" not in v1:
        return None
    blocks = v1["subblock_blocks"] - v0["subblock_blocks"]
    if not blocks:
        return None
    return 100.0 * (v1["subblock_device_blocks"]
                    - v0["subblock_device_blocks"]) / blocks
