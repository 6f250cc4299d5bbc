"""The program's span trees, read beside the device trace.

Input is the flat span list the program's window recorder returns
(`SPANS.record_end()["spans"]`): dicts with `name`, `trace_id`,
`span_id`, `parent_id`, `t0_ns`, `t1_ns` (both `time.perf_counter_ns()`), `tid`,
`cpu_ns` and `attrs` where the program took them. Everything here is
arithmetic on plain lists — nothing imports the program or JAX.

Three things are computed:

* self time: a span's duration minus the union of its children's
  intervals. A STREAMED child (`busy_ns` in its attrs and no `calls`:
  the program's `traced_iter`) is current only while its producer runs;
  the rest of its interval is handed back to the parent, the consumer.
  An ACCUMULATED span (`busy_ns` and `calls`: one span for a boundary
  crossed per chunk) has its busy time as its self time.
* the clock map: the harness reads `perf_counter_ns` immediately before
  and after it opens and closes the `TraceAnnotation` that marks the
  traced stretch, and the profiler stamps the annotation's two ends on
  the trace's clock — two anchors, each giving an offset; they must
  agree to 1 ms or the map is refused.
* idle attribution: every instant of the stretch at which no device
  operation runs is in exactly one of three host states, `launch` (a
  dispatch is inside `sched.transfer`/`h2d`/`compute`/`fetch`), else
  `former` (a group is inside `sched.queue`), else `upstream` (no group
  exists: the streams are in handlers, engine or drives).
"""

from __future__ import annotations

LAUNCH_SPANS = ("sched.transfer", "sched.h2d", "sched.compute",
                "sched.fetch")
FORMER_SPAN = "sched.queue"
FORMER_PARTS = ("sched.collect", "sched.slot")
ANCHOR_TOLERANCE_NS = 1_000_000

Interval = tuple[float, float]


# -- interval arithmetic ----------------------------------------------------

def merge(ivs: list[Interval]) -> list[Interval]:
    """Sorted, disjoint union of `ivs` (empty intervals dropped)."""
    out: list[list[float]] = []
    for s, e in sorted(iv for iv in ivs if iv[1] > iv[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(ivs: list[Interval]) -> float:
    return sum(e - s for s, e in ivs)


def intersect(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """a ∩ b; both merged."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """a minus b; both merged."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def clip(ivs: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs
            if min(e, hi) > max(s, lo)]


# -- trees ------------------------------------------------------------------

def children_of(spans: list[dict]) -> dict[str, list[dict]]:
    kids: dict[str, list[dict]] = {}
    for sp in spans:
        if sp.get("parent_id"):
            kids.setdefault(sp["parent_id"], []).append(sp)
    return kids


def roots(spans: list[dict], name: str = "", lo_ns: float | None = None,
          hi_ns: float | None = None) -> list[dict]:
    """Spans without a parent (requests), optionally of one name and
    ENDED within [lo_ns, hi_ns] — as the clients' records are kept."""
    return [sp for sp in spans if not sp.get("parent_id")
            and (not name or sp["name"] == name)
            and (lo_ns is None or sp["t1_ns"] >= lo_ns)
            and (hi_ns is None or sp["t1_ns"] <= hi_ns)]


def _busy(sp: dict) -> float | None:
    return (sp.get("attrs") or {}).get("busy_ns")


def _streamed(sp: dict) -> bool:
    a = sp.get("attrs") or {}
    return "busy_ns" in a and "calls" not in a


def self_ns(sp: dict, kids: list[dict]) -> float:
    """Duration minus the union of the children's intervals (cut to the
    span's own), with the idle part of streamed children handed back."""
    lo, hi = sp["t0_ns"], sp["t1_ns"]
    if "calls" in (sp.get("attrs") or {}):
        return float(_busy(sp))
    covered = total(merge(clip([(c["t0_ns"], c["t1_ns"]) for c in kids],
                               lo, hi)))
    back = sum(max((c["t1_ns"] - c["t0_ns"]) - _busy(c), 0)
               for c in kids if _streamed(c))
    own = _busy(sp) if _streamed(sp) else hi - lo
    return max(own - max(covered - back, 0.0), 0.0)


def subtree(sp: dict, kids: dict[str, list[dict]]) -> list[dict]:
    out, todo = [], [sp]
    while todo:
        cur = todo.pop()
        out.append(cur)
        todo.extend(kids.get(cur["span_id"], ()))
    return out


def mean_ms(values: list[float]) -> float | None:
    return sum(values) / len(values) / 1e6 if values else None


def per_request(spans: list[dict], root_name: str, span_name: str,
                what: str, lo_ns: float | None = None,
                hi_ns: float | None = None) -> list[float]:
    """For each request `root_name` that ended in the window, the sum
    over its spans called `span_name` (the root itself when equal) of
    `what`: "self", "dur" or "busy" — nanoseconds, one value a
    request (requests without such a span give none)."""
    kids = children_of(spans)
    out = []
    for root in roots(spans, root_name, lo_ns, hi_ns):
        vals = [_measure(sp, kids, what)
                for sp in subtree(root, kids) if sp["name"] == span_name]
        if vals:
            out.append(sum(vals))
    return out


def per_span(spans: list[dict], root_name: str, span_name: str,
             what: str, lo_ns: float | None = None,
             hi_ns: float | None = None) -> list[float]:
    """The same, one value a SPAN (a group, a launch)."""
    kids = children_of(spans)
    return [_measure(sp, kids, what)
            for root in roots(spans, root_name, lo_ns, hi_ns)
            for sp in subtree(root, kids) if sp["name"] == span_name]


def _measure(sp: dict, kids: dict[str, list[dict]], what: str) -> float:
    if what == "self":
        return self_ns(sp, kids.get(sp["span_id"], []))
    if what == "busy":
        return float(_busy(sp) or 0)
    return float(sp["t1_ns"] - sp["t0_ns"])


def coverage(root: dict, kids: dict[str, list[dict]]) -> float:
    """Share of a request's duration that its tree accounts for: self
    times down the request's own chain (root, `s3.*`, `engine.*`) plus
    the union of every other span's interval, cut to the root. Under
    1.0 when spans that should fill the request lie elsewhere on the
    clock; `outside_ns` says by how much."""
    lo, hi = root["t0_ns"], root["t1_ns"]
    if hi <= lo:
        return 0.0
    own, named = 0.0, []
    for sp in subtree(root, kids):
        if sp is root or sp["name"].startswith(("s3.", "engine.")):
            own += self_ns(sp, kids.get(sp["span_id"], []))
        else:
            named.append((sp["t0_ns"], sp["t1_ns"]))
    return min((own + total(merge(clip(named, lo, hi)))) / (hi - lo), 1.0)


def outside_ns(root: dict, kids: dict[str, list[dict]]) -> float:
    """Nanoseconds by which the spans of a tree lie outside their
    parents — 0 when every stamp is on the one clock."""
    return sum(max(sp["t0_ns"] - ch["t0_ns"], 0)
               + max(ch["t1_ns"] - sp["t1_ns"], 0)
               for sp in subtree(root, kids)
               for ch in kids.get(sp["span_id"], ()))


# -- the clock map ----------------------------------------------------------

def clock_offset(anchors: dict, mark: Interval) -> float:
    """-> trace_ns − perf_counter_ns. `anchors` = {"enter": [before,
    after], "exit": [before, after]}: perf_counter_ns around the
    annotation's `__enter__` and `__exit__`; `mark` = its (start, end)
    on the trace's clock. Raises when the two anchors disagree by more
    than 1 ms: the clocks drift or the annotation is not this one."""
    at_enter = mark[0] - sum(anchors["enter"]) / 2
    at_exit = mark[1] - sum(anchors["exit"]) / 2
    if abs(at_enter - at_exit) > ANCHOR_TOLERANCE_NS:
        raise ValueError(
            f"the two clock anchors disagree by "
            f"{abs(at_enter - at_exit) / 1e6:.3f} ms (> 1 ms)")
    return (at_enter + at_exit) / 2


def on_trace_clock(spans: list[dict], offset: float) -> list[dict]:
    return [dict(sp, t0_ns=sp["t0_ns"] + offset, t1_ns=sp["t1_ns"] + offset)
            for sp in spans]


# -- idle attribution -------------------------------------------------------

def _named(spans: list[dict], names: tuple[str, ...], lo: float,
           hi: float) -> list[Interval]:
    return merge(clip([(sp["t0_ns"], sp["t1_ns"]) for sp in spans
                       if sp["name"] in names], lo, hi))


def idle_states(spans: list[dict], busy: list[Interval],
                window: Interval) -> dict[str, list[Interval]]:
    """The stretch's idle time split three ways. `spans` on the trace's
    clock, `busy` the merged device-busy intervals, `window` the mark.
    The three lists are disjoint and their union is window − busy."""
    lo, hi = window
    idle = subtract([(lo, hi)], merge(clip(busy, lo, hi)))
    launch = intersect(idle, _named(spans, LAUNCH_SPANS, lo, hi))
    rest = subtract(idle, launch)
    former = intersect(rest, _named(spans, (FORMER_SPAN,), lo, hi))
    return {"launch": launch, "former": former,
            "upstream": subtract(rest, former)}


def idle_shares(states: dict[str, list[Interval]]) -> dict[str, float]:
    """Percent of the idle time in each state (they sum to 100)."""
    whole = sum(total(iv) for iv in states.values())
    return {k: 100.0 * total(iv) / whole if whole else 0.0
            for k, iv in states.items()}


def _heaviest(spans: list[dict], names: tuple[str, ...] | None,
              part: list[Interval]) -> tuple[str, float]:
    """The span name with most thread-seconds inside `part` — among
    `names`, or among leaf spans when None."""
    if names is None:
        parents = {sp.get("parent_id") for sp in spans}
        pool = [sp for sp in spans if sp["span_id"] not in parents
                and not sp["name"].startswith("sched.")]
    else:
        pool = [sp for sp in spans if sp["name"] in names]
    by_name: dict[str, float] = {}
    for sp in pool:
        ns = total(intersect(part, [(sp["t0_ns"], sp["t1_ns"])]))
        if ns:
            by_name[sp["name"]] = by_name.get(sp["name"], 0.0) + ns
    if not by_name:
        return "", 0.0
    best = max(by_name, key=by_name.get)
    return best, by_name[best]


def name_gaps(merged: list[tuple], window: Interval, spans: list[dict],
              n: int = 10) -> list[list]:
    """`tracered.idle_gaps` again — the same gaps, the same seconds, in
    the same order — with each name BEGINNING with the host states that
    cover the gap, largest first: `former.slot 71% | upstream:
    put.read_stream 20% | after put_step before put_step`. `merged` is
    `tracered.busy_union`'s list (start, end, first op, last op);
    `spans` are on the trace's clock."""
    gaps = []
    prev_end, prev_op = window[0], "window start"
    for start, end, first, last in merged:
        if start > prev_end:
            gaps.append((prev_end, start, f"after {prev_op} before {first}"))
        prev_end, prev_op = max(prev_end, end), last
    if window[1] > prev_end:
        gaps.append((prev_end, window[1],
                     f"after {prev_op} before window end"))
    gaps = sorted(gaps, key=lambda g: -(g[1] - g[0]))[:n]
    out = []
    for lo, hi, ops in gaps:
        near = [sp for sp in spans if sp["t1_ns"] > lo and sp["t0_ns"] < hi]
        states = idle_states(near, [], (lo, hi))
        parts = []
        for state, ivs in sorted(states.items(),
                                 key=lambda kv: -total(kv[1])):
            share = 100.0 * total(ivs) / (hi - lo)
            if share < 1.0:
                continue
            if state == "former":
                who = _heaviest(near, FORMER_PARTS, ivs)[0]
                label = "former." + (who.split(".")[-1] or "queue")
            elif state == "launch":
                who = _heaviest(near, LAUNCH_SPANS, ivs)[0]
                label = "launch." + (who.split(".")[-1] or "?")
            else:
                label = "upstream:" + (_heaviest(near, None, ivs)[0]
                                       or "no span")
            parts.append(f"{label} {share:.0f}%")
        out.append([" | ".join(parts + [ops]), (hi - lo) / 1e9])
    return out


# -- launches against the device's own record --------------------------------

def launches_inside(spans: list[dict], modules: list[Interval],
                    tolerance_ns: float = ANCHOR_TOLERANCE_NS) -> dict:
    """How many device program runs (`XLA Modules` events, trace clock)
    lie inside SOME `sched.compute` span (mapped to that clock), to
    within the tolerance — and the worst excursion of any. The spans of
    one launch are attached to every member's tree, so duplicates are
    folded first."""
    computes = sorted({(sp["t0_ns"], sp["t1_ns"]) for sp in spans
                       if sp["name"] == "sched.compute"})
    inside, worst = 0, 0.0
    for s, e in modules:
        best = min((max(cs - s, e - ce, 0.0) for cs, ce in computes),
                   default=float("inf"))
        inside += best <= tolerance_ns
        worst = max(worst, best)
    return {"launches": len(modules), "inside": inside,
            "worst_outside_ms": worst / 1e6,
            "share": 100.0 * inside / len(modules) if modules else None}


# -- device time by kernel ----------------------------------------------------

# what an operation of the fused steps IS, from its own name on the
# trace's "XLA Ops" line: the Pallas GF(2^8) matmul is `gf_matmul` (the
# name the program gives its pallas_call), and the only loops in these
# steps are the bitrot hash's (HighwayHash's packet scan, SHA-256's
# block scan) — a `while` event spans its whole body. Everything else
# the device does in a step (layout copies, pads, concatenates, the
# digests' unpacking) is `pack`. The raw `.xplane.pb` carries no
# name-scope line (a trace viewer derives one from the HLO's metadata),
# so the program's `jax.named_scope`s are not read here.
KERNEL_OF_OP = (("gf_matmul", "gf"), ("while", "hash"))


def kernel_times(events: list[tuple], window: Interval) -> dict[str, float]:
    """Device seconds of the stretch by kernel — `hash`, `gf`, and
    `pack` for the rest — from one device plane's events `(line, op,
    start_ns, duration_ns)` as `tracered.load_trace` gives them. The
    three are disjoint (an instant under both a loop and a matmul goes
    to the matmul) and sum to the plane's busy union."""
    lo, hi = window
    ops = [(op, s, s + d) for line, op, s, d in events]
    busy = merge(clip([(s, e) for _op, s, e in ops], lo, hi))
    out, taken = {}, []
    for prefix, kernel in KERNEL_OF_OP:
        mine = merge(clip([(s, e) for op, s, e in ops
                           if op.startswith(prefix)], lo, hi))
        mine = subtract(mine, taken)
        out[kernel] = total(mine) / 1e9
        taken = merge(taken + mine)
    out["pack"] = total(subtract(busy, taken)) / 1e9
    return out


# -- the dispatch histogram's stage sums --------------------------------------

def stage_share_pct(win: dict, verb: str, stage: str) -> float | None:
    """Seconds the program observed under `minio_tpu_device_dispatch_
    seconds{verb,stage}` between the window's marks, as a percent of
    the window — for a stage whose SUM matters (the collector's blocked
    time), where `readers.stage_mean_ms` gives a per-observation mean.
    None when nothing was observed (a program without the stage)."""
    key = f"{verb}.{stage}"
    n0, s0 = win["c0"]["stages"].get(key, [0, 0.0])
    n1, s1 = win["c1"]["stages"].get(key, [0, 0.0])
    return 100.0 * (s1 - s0) / win["window_s"] if n1 > n0 else None
