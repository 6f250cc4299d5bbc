"""Self-checks of `benchlib/spanview.py` on synthetic span lists and
busy intervals: self time, the clock map from two anchors (and its
refusal), the three idle states as an exact partition — with a control
— and the gap names against `tracered.idle_gaps`' own seconds."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import spanview, tracered  # noqa: E402

MS = 1_000_000


def sp(name, t0, t1, ident, parent="", **attrs):
    d = {"name": name, "trace_id": "t", "span_id": ident,
         "parent_id": parent,
         "t0_ns": t0 * MS, "t1_ns": t1 * MS, "tid": 1}
    if attrs:
        d["attrs"] = attrs
    return d


# -- intervals ---------------------------------------------------------------

def test_interval_arithmetic():
    a = spanview.merge([(5, 7), (0, 2), (1, 3), (9, 9)])
    assert a == [(0, 3), (5, 7)]
    b = [(2, 6), (6.5, 10)]
    assert spanview.intersect(a, b) == [(2, 3), (5, 6), (6.5, 7)]
    assert spanview.subtract(a, b) == [(0, 2), (6, 6.5)]
    assert spanview.total(spanview.intersect(a, b)) \
        + spanview.total(spanview.subtract(a, b)) == spanview.total(a)
    assert spanview.subtract([(0, 10)], []) == [(0, 10)]
    assert spanview.clip([(0, 4), (8, 12)], 2, 9) == [(2, 4), (8, 9)]


# -- self time ---------------------------------------------------------------

def test_self_time_is_duration_minus_the_union_of_children():
    spans = [sp("PutObject", 0, 100, "r"),
             sp("s3.auth", 1, 3, "a", "r"),
             sp("engine.put_object", 5, 95, "e", "r"),
             # two stage threads overlap: the union counts once
             sp("pipeline.encode", 10, 60, "p1", "e"),
             sp("pipeline.shard_write", 40, 90, "p2", "e"),
             # one span for a boundary crossed per chunk: busy is its own
             sp("s3.body_hash", 6, 80, "h", "r", busy_ns=30 * MS, calls=16)]
    kids = spanview.children_of(spans)
    by = {s["span_id"]: s for s in spans}
    assert spanview.self_ns(by["e"], kids["e"]) == 10 * MS      # 90 - [10,90]
    # children cover [1,3] + [5,95] (body_hash inside): 100 - 92
    assert spanview.self_ns(by["r"], kids["r"]) == 8 * MS
    assert spanview.self_ns(by["h"], []) == 30 * MS
    assert spanview.per_request(spans, "PutObject", "PutObject",
                                "self") == [8 * MS]
    assert spanview.per_span(spans, "PutObject", "pipeline.encode",
                             "dur") == [50 * MS]
    assert spanview.per_request(spans, "PutObject", "s3.body_hash",
                                "busy") == [30 * MS]
    assert spanview.coverage(by["r"], kids) == 1.0
    # requests are kept by when they ENDED, as the clients' records are
    assert spanview.roots(spans, "PutObject", 0, 99 * MS) == []
    assert spanview.mean_ms([2 * MS, 4 * MS]) == 3.0
    assert spanview.mean_ms([]) is None


def test_a_streamed_child_hands_its_idle_time_back_to_the_consumer():
    """GET: `engine.get_object` is a traced_iter under `s3.respond` —
    current only while the engine produces a chunk; the writer's time
    between chunks is the endpoint's, not the engine's."""
    spans = [sp("GetObject", 0, 100, "r"),
             sp("s3.respond", 10, 100, "w", "r"),
             sp("engine.get_object", 10, 98, "e", "w", busy_ns=40 * MS),
             sp("get.read_shards", 12, 30, "g", "e"),
             sp("get.join", 50, 55, "j", "e")]
    kids = spanview.children_of(spans)
    by = {s["span_id"]: s for s in spans}
    assert spanview.self_ns(by["e"], kids["e"]) == (40 - 18 - 5) * MS
    # the writer: 90 of its own, the engine held 40 of them
    assert spanview.self_ns(by["w"], kids["w"]) == 50 * MS
    assert spanview.self_ns(by["r"], kids["r"]) == 10 * MS
    assert spanview.coverage(by["r"], kids) == 1.0
    assert spanview.outside_ns(by["r"], kids) == 0
    # a child stamped on another clock lies outside its parent: counted
    bad = spans[:2] + [sp("pipeline.x", 95, 180, "x", "w")]
    assert spanview.outside_ns(bad[0], spanview.children_of(bad)) == 80 * MS


# -- the clock map -----------------------------------------------------------

def test_clock_map_from_two_anchors_and_its_refusal():
    off = 7_000_000_123
    anchors = {"enter": [1000 * MS, 1000 * MS + 4000],
               "exit": [3000 * MS, 3000 * MS + 2000]}
    mark = (1000 * MS + 2000 + off, 3000 * MS + 1000 + off)
    assert spanview.clock_offset(anchors, mark) == off
    moved = spanview.on_trace_clock([sp("a", 1, 2, "a")], off)
    assert moved[0]["t0_ns"] == MS + off and moved[0]["t1_ns"] == 2 * MS + off
    # 0.9 ms apart: accepted, the mean of the two
    near = (mark[0], mark[1] + 0.9 * MS)
    assert spanview.clock_offset(anchors, near) == off + 0.45 * MS
    # over 1 ms: the clocks drift, or the annotation is another
    with pytest.raises(ValueError, match="disagree"):
        spanview.clock_offset(anchors, (mark[0], mark[1] + 1.2 * MS))


# -- idle attribution --------------------------------------------------------

def stretch():
    """A 100 ms stretch: the device runs [20,30] and [70,75]."""
    busy = [(20 * MS, 30 * MS), (70 * MS, 75 * MS)]
    spans = [
        sp("PutObject", 0, 100, "r"),
        sp("put.read_stream", 0, 12, "rs", "r", busy_ns=11 * MS, calls=8),
        sp("sched.dispatch", 10, 34, "d", "r"),
        sp("sched.queue", 10, 16, "q", "d"),
        sp("sched.collect", 10, 11, "qc", "q"),
        sp("sched.slot", 11, 16, "qs", "q"),
        sp("sched.transfer", 16, 18, "t", "d"),
        sp("sched.h2d", 18, 19, "h", "d"),
        sp("sched.compute", 19, 31, "c", "d"),
        sp("sched.fetch", 31, 34, "f", "d"),
        sp("pipeline.shard_write", 34, 60, "w", "r"),
        sp("disk.shard_write", 35, 58, "w1", "w"),
        sp("disk.shard_write", 35, 59, "w2", "w")]
    return spans, busy, (0.0, 100.0 * MS)


def test_idle_states_partition_the_idle_time_exactly():
    spans, busy, window = stretch()
    states = spanview.idle_states(spans, busy, window)
    idle = spanview.subtract([window], spanview.merge(busy))
    assert spanview.total(idle) == 85 * MS
    # disjoint, and together exactly the idle time
    parts = list(states.values())
    for i in range(3):
        for j in range(i + 1, 3):
            assert spanview.intersect(parts[i], parts[j]) == []
    assert spanview.merge([iv for p in parts for iv in p]) == idle
    assert sum(spanview.total(p) for p in parts) == 85 * MS
    # launch: [16,20] + [30,34]; former: [10,16]; upstream: the rest
    assert spanview.total(states["launch"]) == 8 * MS
    assert spanview.total(states["former"]) == 6 * MS
    assert spanview.total(states["upstream"]) == 71 * MS
    shares = spanview.idle_shares(states)
    assert shares["former"] == pytest.approx(100 * 6 / 85)
    assert sum(shares.values()) == pytest.approx(100.0)


def test_control_a_queue_span_outside_every_gap_reads_zero_former():
    spans, busy, window = stretch()
    for s in spans:
        if s["name"] in ("sched.queue", "sched.collect", "sched.slot"):
            # the group waited while the device was busy, not in a gap
            s["t0_ns"], s["t1_ns"] = 21 * MS, 29 * MS
    states = spanview.idle_states(spans, busy, window)
    assert spanview.idle_shares(states)["former"] == 0.0
    assert spanview.total(states["upstream"]) == 77 * MS
    # and with no spans at all, every idle instant is upstream
    only = spanview.idle_shares(spanview.idle_states([], busy, window))
    assert only == {"launch": 0.0, "former": 0.0, "upstream": 100.0}


def test_gap_names_begin_with_the_host_state_and_keep_their_seconds():
    spans, busy, window = stretch()
    events = [("XLA Ops", "put_step", s, e - s) for s, e in busy]
    merged = tracered.busy_union(events)[1]
    plain = tracered.idle_gaps(merged, window)
    named = spanview.name_gaps(merged, window, spans)
    # the same gaps, the same seconds, in the same order
    assert [g[1] for g in named] == [g[1] for g in plain]
    for got, was in zip(named, plain):
        assert got[0].endswith(was[0])
        assert got[0].split(" ")[0].split(":")[0].split(".")[0] in (
            "upstream", "former", "launch")
    # [30,70]: 4 ms of fetch, then 36 with only the drives at work
    assert named[0][0].startswith(
        "upstream:disk.shard_write 90% | launch.fetch 10% | after put_step")
    # [0,20]: read_stream, then the slot wait, then the launch's host side
    assert named[2][0].startswith(
        "upstream:put.read_stream 50% | former.slot 30% | "
        "launch.transfer 20% | after window start")
    # a gap no span covers says so
    assert spanview.name_gaps(merged, window, [])[0][0].startswith(
        "upstream:no span 100% | ")


def test_launches_lie_inside_their_compute_spans():
    spans, _busy, _w = stretch()
    # the launch's spans hang under every member's tree: folded
    spans.append(sp("sched.compute", 19, 31, "c-again", "d"))
    got = spanview.launches_inside(spans, [(20 * MS, 30 * MS)])
    assert got["inside"] == got["launches"] == 1 and got["share"] == 100.0
    # a program run no compute span holds, by 3 ms
    out = spanview.launches_inside(spans, [(20 * MS, 30 * MS),
                                           (70 * MS, 75 * MS)])
    assert out["inside"] == 1 and out["launches"] == 2
    assert out["worst_outside_ms"] == 44.0
    ok = spanview.launches_inside(spans, [(18.5 * MS, 31.4 * MS)])
    assert ok["inside"] == 1                      # within the 1 ms


# -- counters ----------------------------------------------------------------

def test_stage_share_of_the_window():
    win = {"window_s": 40.0,
           "c0": {"stages": {"encode.collector_blocked": [10, 1.0]}},
           "c1": {"stages": {"encode.collector_blocked": [110, 21.0]}}}
    assert spanview.stage_share_pct(win, "encode",
                                    "collector_blocked") == 50.0
    # a program without the stage (the parent commit): nothing to read
    assert spanview.stage_share_pct(win, "decode",
                                    "collector_blocked") is None


def test_kernel_times_split_the_busy_union_three_ways():
    """The hash loop's event spans its body's operations; the Pallas
    call is named; the rest is pack. Disjoint, and they sum to busy."""
    evs = [("XLA Ops", "copy.369", 0.0, 10.0),
           ("XLA Ops", "gf_matmul.1", 10.0, 20.0),
           ("XLA Ops", "reshape.47", 30.0, 5.0),
           ("XLA Ops", "while.83", 40.0, 50.0),
           ("XLA Ops", "fusion.12", 41.0, 3.0),      # the loop's body
           ("XLA Ops", "fusion.12", 50.0, 3.0),
           ("XLA Modules", "jit_put_step(1)", 0.0, 95.0)]
    ops_only = [e for e in evs if e[0] == "XLA Ops"]
    got = spanview.kernel_times(ops_only, (0.0, 100.0))
    assert got == {"gf": 20e-9, "hash": 50e-9, "pack": 15e-9}
    assert sum(got.values()) * 1e9 == tracered.busy_union(ops_only)[0]
    # with the module's own event the rest of its run is pack too: the
    # three then sum to the busy union of EVERY line, the harness's
    assert spanview.kernel_times(evs, (0.0, 100.0)) == {
        "gf": 20e-9, "hash": 50e-9, "pack": 25e-9}
    # cut to the window, like the busy time beside it
    cut = spanview.kernel_times(ops_only, (15.0, 60.0))
    assert cut == {"gf": 15e-9, "hash": 20e-9, "pack": 5e-9}
    # the recorded v5e trace (before the Pallas call had its name)
    small = os.path.join(HERE, "small_trace.xplane.pb.gz")
    planes, _mark = tracered.load_trace(small)
    first = [e for e in next(iter(planes.values())) if e[0] == "XLA Ops"]
    lo = min(e[2] for e in first)
    hi = max(e[2] + e[3] for e in first)
    k = spanview.kernel_times(first, (lo, hi))
    assert k["gf"] == 0.0 and k["hash"] > 0 and k["pack"] > 0
    assert sum(k.values()) * 1e9 == pytest.approx(
        tracered.busy_union(first)[0])
