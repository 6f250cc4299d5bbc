"""Object engine: groups of blocks submitted to the batch former over the PUTs that ended in the window (scheduler.stats: groups; the clients' records) — a 10 MiB object is one group when its short last block rides the group of its whole blocks, two when it goes alone. None where the program has no such counter."""


def read(win):
    v0, v1 = win["c0"]["verbs"]["encode"], win["c1"]["verbs"]["encode"]
    if win["verb"] != "encode" or "groups" not in v1:
        return None
    puts = sum(1 for r in win["records"] if r[4])
    return (v1["groups"] - v0["groups"]) / puts if puts else None
