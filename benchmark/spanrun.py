#!/usr/bin/env python3
"""benchmark/spanrun.py — one TRACED run of one cell with the program's
span trees laid over the device trace. For whoever reads PERF.md's
"where the time goes"; the driver never runs it.

    python3 benchmark/spanrun.py --workload <cell> --seed <n> --seconds <s>
        [--no-profile] [--rehearse] [--keep-lines FILE]

`run.py --trace 1` cannot do this yet: the harness has no seam through
which the program's window recorder is begun at the window's start, the
clock anchors are taken around the stretch's annotation, and the trace
is read for more than busy time before it is deleted. A PR that is not
a `benchmark` PR may not edit `harness.py` or `node.py`, so until one
adds those lines (PERF.md section 7 lists them) this script lays the
same seam AROUND the harness, from outside: it wraps four of its
functions for the length of one run, calls `harness.run_cell` as
`run.py` does, and then computes, from `benchlib/spanview.py`, what the
readers of that later PR will compute from `win`. Its last stdout line
is `run.py`'s result line with the span-read metrics added to
`metrics`, the host state in front of every `breakdown.idle_gaps` name,
and `beside.spans` (reconciliation, clock agreement, idle attribution).
`--no-profile` leaves the profiler off and only records spans (an
untraced run's end-to-end metrics plus the span-read ones): what the
recorder alone costs. `--rehearse` is `rehearse.py`'s tiny XLA-CPU
size: it prints DRY RUN, no device number, and exits 3.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import shutil    # noqa: E402
import sys       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

ROOT_OF_OP = {"PUT": "PutObject", "GET": "GetObject"}
ENGINE_OF_OP = {"PUT": "engine.put_object", "GET": "engine.get_object"}


class _Stretch:
    """The open annotation, with perf_counter_ns read around its two
    ends: the anchors of the clock map."""

    def __init__(self, inner, seam: dict):
        self.inner, self.seam = inner, seam

    def enter(self):
        a = time.perf_counter_ns()
        self.inner.__enter__()
        self.seam["anchors"] = {"enter": [a, time.perf_counter_ns()]}
        return self

    def __exit__(self, *exc):
        a = time.perf_counter_ns()
        out = self.inner.__exit__(*exc)
        self.seam["anchors"]["exit"] = [a, time.perf_counter_ns()]
        return out


def lay_seam(harness, node_mod, loadgen, tracered, spanview, seam: dict,
             keep_lines: str = "") -> None:
    """The lines the harness would carry, wrapped around it."""
    from minio_tpu.utils.telemetry import SPANS

    start, collect = loadgen.Clients.start, loadgen.Clients.collect
    counters = node_mod.Node.counters

    def clients_start(self, port):
        start(self, port)
        SPANS.record_begin()            # just before the window's t0

    def clients_collect(self):
        out = collect(self)
        # after the clients are in: requests that straddle the close
        # are whole
        seam["rec"] = SPANS.record_end()
        return out

    def node_counters(self):
        out = counters(self)
        # the harness reads its clock right after c0 and c1: the first
        # and last stamps after the recorder began are the window marks
        # (with the process's CPU seconds: the recorder's own pair ends
        # after the trace is read, which would dilute the cores)
        seam.setdefault("marks", []).append(time.perf_counter_ns())
        seam.setdefault("cpu_s", []).append(time.process_time())
        return out

    def start_trace():
        import jax
        out = os.path.join(harness.REPO, ".bench_out",
                           f"trace-{os.getpid()}")
        shutil.rmtree(out, ignore_errors=True)
        jax.profiler.start_trace(out)
        return out, _Stretch(
            jax.profiler.TraceAnnotation(tracered.WINDOW_MARK),
            seam).enter()

    def reduce_trace(out, ca, cb, rehearsal):
        import jax
        jax.profiler.stop_trace()
        try:
            path = tracered.find_xplane(out)
            planes, mark = tracered.load_trace(path)
            if rehearsal and not planes:
                return None
            if mark is None:
                raise RuntimeError("the trace has no window mark")
            red = tracered.reduce_planes(planes, clip=mark)
            # -- what the harness does not keep --
            first = tracered.clip_events(next(iter(planes.values())), *mark)
            seam["mark"] = mark
            seam["merged"] = tracered.busy_union(first)[1]
            seam["modules"] = [(s, s + d) for line, _op, s, d in first
                               if line == "XLA Modules"]
            seam["kernels"] = spanview.kernel_times(first, mark)
            if keep_lines:
                _dump_lines(path, keep_lines)
        finally:
            shutil.rmtree(out, ignore_errors=True)
        red["blocks"] = {v: cb["verbs"][v]["blocks"]
                         - ca["verbs"][v]["blocks"] for v in cb["verbs"]}
        red["decode_by_lost"] = {
            r: n - ca["decode_by_lost"].get(r, 0)
            for r, n in cb["decode_by_lost"].items()}
        seam["blocks"] = red["blocks"]
        return red

    loadgen.Clients.start = clients_start
    loadgen.Clients.collect = clients_collect
    node_mod.Node.counters = node_counters
    harness._start_trace = start_trace
    harness._reduce_trace = reduce_trace


def _dump_lines(path: str, dest: str) -> None:
    """Every plane's lines with their first events, to a file: what a
    builder looks at by hand before writing a reader against a trace."""
    import gzip
    from jax.profiler import ProfileData
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space = ProfileData.from_serialized_xspace(f.read())
    os.makedirs(os.path.dirname(dest) or ".", exist_ok=True)
    with open(dest, "w") as f:
        for plane in space.planes:
            f.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name} ({len(evs)} events)\n")
                for ev in evs[:12]:
                    f.write(f"    {ev.name[:160]!r} {ev.start_ns} "
                            f"{ev.duration_ns}\n")


def span_metrics(result: dict, seam: dict, op: str, verb: str,
                 spanview) -> tuple[dict, dict]:
    """-> (metrics by the names ISSUE 25 gives them, `beside.spans`)."""
    rec = seam["rec"]
    spans = rec["spans"]
    marks = seam["marks"]
    lo, hi = marks[0], marks[-1]
    window_s = (hi - lo) / 1e9
    root, eng = ROOT_OF_OP[op], ENGINE_OF_OP[op]
    sfx = "." + op.lower()
    ms = spanview.mean_ms

    def req(name, what):
        return ms(spanview.per_request(spans, root, name, what, lo, hi))

    def grp(name, what="dur"):
        return ms(spanview.per_span(spans, root, name, what, lo, hi))

    metrics = {
        "endpoint_self_ms" + sfx: req(root, "self"),
        "engine_self_ms" + sfx: req(eng, "self"),
        "server_cpu_cores" + sfx:
            (seam["cpu_s"][-1] - seam["cpu_s"][0]) / window_s,
    }
    if op == "PUT":
        metrics["body_hash_ms.put"] = req("s3.body_hash", "busy")
        metrics["shard_write_ms.put"] = grp("pipeline.shard_write")
        metrics["commit_ms.put"] = req("put.commit", "dur")
    else:
        metrics["shard_read_ms.get"] = grp("get.read_shards")

    kids = spanview.children_of(spans)
    mine = spanview.roots(spans, root, lo, hi)
    cover = [spanview.coverage(r, kids) for r in mine]
    root_ms = ms([r["t1_ns"] - r["t0_ns"] for r in mine])
    layers = {name: req(name, what) for name, what in (
        ("s3.auth", "dur"), ("s3.respond", "self"),
        ("put.read_stream", "busy"), ("put.buffer_wait", "dur"),
        ("put.hash_verify", "dur"), ("pipeline.encode", "dur"),
        ("pipeline.verify_decode", "dur"), ("get.join", "dur"),
        ("sched.queue", "dur"), ("sched.collect", "dur"),
        ("sched.slot", "dur"), ("sched.transfer", "dur"),
        ("sched.h2d", "dur"), ("sched.compute", "dur"),
        ("sched.fetch", "dur"))}
    cpu = [sp["cpu_ns"] for sp in spans
           if sp["name"] == root and "cpu_ns" in sp
           and lo <= sp["t1_ns"] <= hi]
    beside = {
        "roots_recorded": rec["roots"], "roots_dropped": rec["dropped"],
        "requests": len(mine), "spans": len(spans),
        "window_s": window_s, "root_ms": root_ms,
        "request_thread_cpu_ms": ms(cpu),
        "outside_parent_ms_max": max(
            (spanview.outside_ns(r, kids) for r in mine),
            default=0) / 1e6,
        "coverage_min": min(cover, default=None),
        "coverage_mean": sum(cover) / len(cover) if cover else None,
        "per_request_ms": {k: v for k, v in layers.items()
                           if v is not None},
    }

    tr = result.get("device", {})
    if seam.get("mark") and "anchors" in seam:
        offset = spanview.clock_offset(seam["anchors"], seam["mark"])
        on_trace = spanview.on_trace_clock(spans, offset)
        busy = [(s, e) for s, e, _a, _b in seam["merged"]]
        shares = spanview.idle_shares(
            spanview.idle_states(on_trace, busy, seam["mark"]))
        metrics["idle_upstream_share" + sfx] = shares["upstream"]
        metrics["idle_former_share" + sfx] = shares["former"]
        beside["idle_launch_share"] = shares["launch"]
        beside["anchor_offsets_ns"] = [
            seam["mark"][0] - sum(seam["anchors"]["enter"]) / 2,
            seam["mark"][1] - sum(seam["anchors"]["exit"]) / 2]
        beside["launches"] = spanview.launches_inside(
            on_trace, seam["modules"])
        beside["launches_2ms"] = spanview.launches_inside(
            on_trace, seam["modules"], 2e6)["inside"]
        result["breakdown"]["idle_gaps"] = spanview.name_gaps(
            seam["merged"], seam["mark"], on_trace)
        blocks = seam["blocks"].get(verb, 0)
        kernels = seam["kernels"]
        beside["device_s_by_kernel"] = kernels
        beside["busy_s_minus_kernels"] = \
            tr["busy_s"] - sum(kernels.values())
        for name, s in kernels.items() if blocks else ():
            metrics[f"device_ms_per_block.{name}{sfx}"] = 1e3 * s / blocks
    return {k: v for k, v in metrics.items() if v is not None}, beside


UNITS = {"server_cpu_cores": "cores", "idle_upstream_share": "%",
         "idle_former_share": "%"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--no-profile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-lines", default="",
                    help="write the trace's planes, lines and first "
                         "events to this file before it is deleted")
    args = ap.parse_args(argv)

    rehearsal = None
    if args.rehearse:
        import rehearse as rh
        print("DRY RUN platform=cpu — a rehearsal, not a chip result",
              flush=True)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from minio_tpu.object import codec as codec_mod
        codec_mod._device_is_tpu = lambda: True
        codec_mod.DEVICE_MIN_BYTES = 0
        rehearsal = {"node": {"block_size": rh.TINY_BLOCK},
                     "mix": {"object_bytes": 16 * rh.TINY_BLOCK,
                             "populate_objects": 8, "room_MiB_s": 64,
                             "keep_one_in": 2, "check_whole": 8}}
    from benchlib import harness, loadgen, spanview, tracered
    from benchlib import node as node_mod
    seam: dict = {}
    lay_seam(harness, node_mod, loadgen, tracered, spanview, seam,
             args.keep_lines)
    watchdog = harness.guard(1150)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  not args.no_profile, T_PROCESS_START,
                                  rehearsal=rehearsal)
    except (harness.NoResult, ImportError) as e:
        print(f"benchmark: no result — {e}", file=sys.stderr)
        return 2
    watchdog.cancel()
    mix = harness.load_cell(args.workload)["mix"]
    metrics, beside = span_metrics(result, seam, mix["op"],
                                   node_mod.VERB_OF_OP[mix["op"]],
                                   spanview)
    result["beside"]["spans"] = beside
    if args.rehearse:
        # CPU times are not printed under a device metric's name
        print("DRY RUN span metrics found " + json.dumps(
            {"metrics": sorted(metrics),
             "requests": beside["requests"], "spans": beside["spans"],
             "coverage_min": beside["coverage_min"],
             "dropped": beside["roots_dropped"]}), flush=True)
        return 3
    for name, value in metrics.items():
        unit = UNITS.get(name.rsplit(".", 1)[0], "ms")
        result["metrics"][name] = {"value": value, "unit": unit}
    print("spans " + json.dumps(beside), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
