"""Fused step: the ragged encode step's share of its roofline — least bytes of the traced stretch's blocks with every block priced at ITS OWN shard length (benchlib/workbytes_ragged.py: the window's share of short blocks and their mean shard length applied to the stretch's blocks) / HBM peak / device busy time (the union of all device ops, so it reads the same work whatever implements it). None untraced, and where the program does not count short blocks."""

from benchlib import workbytes_ragged


def read(win):
    tr = win.get("trace")
    v0, v1 = win["c0"]["verbs"]["encode"], win["c1"]["verbs"]["encode"]
    if not tr or win["verb"] != "encode" or tr["busy_s"] <= 0 \
            or "short_shard_bytes" not in v1:
        return None
    blocks = tr["blocks"].get("encode", 0)
    if not blocks:
        return None
    g = win["geometry"]
    least = workbytes_ragged.stretch_bytes(
        blocks, v1["blocks"] - v0["blocks"],
        v1["short_blocks"] - v0["short_blocks"],
        v1["short_shard_bytes"] - v0["short_shard_bytes"],
        g["k"], g["m"], g["block_size"])
    if not least:
        return None
    return 100.0 * least / win["peak"]["hbm_bytes_per_s"] / tr["busy_s"]
