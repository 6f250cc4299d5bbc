"""Batch former: blocks of objects under one block per device launch at an S rung, over the window (scheduler.stats: subblock_device_blocks / subblock_launches). None where the program has no such counters, or launched none."""


def read(win):
    v0, v1 = win["c0"]["verbs"]["encode"], win["c1"]["verbs"]["encode"]
    if win["verb"] != "encode" or "subblock_launches" not in v1:
        return None
    launches = v1["subblock_launches"] - v0["subblock_launches"]
    if not launches:
        return None
    return (v1["subblock_device_blocks"]
            - v0["subblock_device_blocks"]) / launches
