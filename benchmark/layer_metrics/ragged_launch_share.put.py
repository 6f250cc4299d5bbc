"""Batch former: encode launches on the device that carried a short block (the ragged program) over all encode launches on the device, over the window (scheduler.stats: ragged_batches / batches). None where the program has no such counter."""


def read(win):
    v0, v1 = win["c0"]["verbs"]["encode"], win["c1"]["verbs"]["encode"]
    if win["verb"] != "encode" or "ragged_batches" not in v1:
        return None
    batches = v1["batches"] - v0["batches"]
    if not batches:
        return None
    return 100.0 * (v1["ragged_batches"] - v0["ragged_batches"]) / batches
