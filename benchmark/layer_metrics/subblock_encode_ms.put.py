"""Object engine: mean ms of pipeline.encode (the stream's wait on its encode) a PUT, over the PUTs that ended in the window whose body is under one block (the span's attribute subblock=1). None untraced, and where the program marks no such span."""

from benchlib import spanview


def read(win):
    a = win.get("anchors") or {}
    spans = win.get("spans")
    if win.get("op") != "PUT" or spans is None or "t0" not in a \
            or "t1" not in a:
        return None
    kids = spanview.children_of(spans)
    per_put = []
    for root in spanview.roots(spans, spanview.ROOT_OF_OP["PUT"],
                               a["t0"], a["t1"]):
        durs = [sp["t1_ns"] - sp["t0_ns"]
                for sp in spanview.subtree(root, kids)
                if sp["name"] == "pipeline.encode"
                and (sp.get("attrs") or {}).get("subblock") == 1]
        if durs:
            per_put.append(sum(durs))
    return spanview.mean_ms(per_put)
