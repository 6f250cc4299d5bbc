"""Reduction of a profiler trace to device busy time, idle share and
the operations that took most of it.

Input is a list of device events `(line_name, op_name, start_ns,
duration_ns)`; `load_xplane` makes that list from an `.xplane.pb` with
nothing but `jax.profiler.ProfileData` (a `.gz` of one is read too). Busy is the UNION of the
intervals in which any operation ran on the device — not a sum, so
nested or overlapping events (an XLA module, its ops, async copies)
are not counted twice.

The profiler records from inside `start_trace` until `stop_trace`; the
harness's counters cover a shorter stretch. It brackets that stretch
with a `TraceAnnotation` named WINDOW_MARK, which the profiler writes
to the host plane on the device planes' own clock (nanoseconds since
the profile began). `find_mark` reads it back and `reduce_planes(...,
clip=)` cuts every device event to it, so busy time, the window and the
block counts are all over the same interval.
"""

from __future__ import annotations

import glob
import gzip
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
WINDOW_MARK = "bench_traced_stretch"
# lines of a device plane that are not operations running on the chip
SKIP_LINES = ("Steps", "Framework Name Scope", "Framework Ops",
              "Source code")


def load_trace(path: str, mark: str = WINDOW_MARK
               ) -> tuple[dict[str, list[tuple]], tuple[float, float] | None]:
    """One pass over an `.xplane.pb`: -> ({device plane name: [(line,
    op, start_ns, duration_ns), ...]}, (start_ns, end_ns) of the
    annotation `mark` on the planes that are not a device's, or None)."""
    from jax.profiler import ProfileData
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        space = ProfileData.from_serialized_xspace(f.read())
    planes: dict[str, list[tuple]] = {}
    span = None
    for plane in space.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        evs = []
        for line in plane.lines:
            if device and line.name in SKIP_LINES:
                continue
            for ev in line.events:
                if device:
                    evs.append((line.name, short_name(ev.name),
                                float(ev.start_ns), float(ev.duration_ns)))
                elif span is None and ev.name == mark:
                    start = float(ev.start_ns)
                    span = (start, start + float(ev.duration_ns))
        if device:
            planes[plane.name] = evs
    return planes, span


def load_xplane(path: str) -> dict[str, list[tuple]]:
    return load_trace(path)[0]


def find_mark(path: str, name: str = WINDOW_MARK
              ) -> tuple[float, float] | None:
    return load_trace(path, name)[1]


def short_name(hlo: str) -> str:
    """The trace names an XLA op by its whole HLO line, `%copy.7 =
    u32[8,16,131072]{...} copy(...)`: keep the op's own name."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def busy_union(events: list[tuple]) -> tuple[float, list[tuple]]:
    """-> (busy ns, merged [(start, end, first op name, last op name)])"""
    merged: list[list] = []
    for _line, op, start, dur in sorted(events, key=lambda e: e[2]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end >= merged[-1][1]:
                merged[-1][1] = end
                merged[-1][3] = op
        else:
            merged.append([start, end, op, op])
    return sum(e - s for s, e, _a, _b in merged), \
        [tuple(x) for x in merged]


def top_ops(events: list[tuple], n: int = 10) -> list[list]:
    """Operations by summed duration, under the trace's own names, from
    the line that carries most of the time (XLA ops on a TPU plane) so
    a module and its ops are not both listed."""
    by_line: dict[str, float] = {}
    for line, _op, _s, dur in events:
        by_line[line] = by_line.get(line, 0.0) + dur
    if not by_line:
        return []
    ops_line = "XLA Ops" if "XLA Ops" in by_line \
        else max(by_line, key=by_line.get)
    total: dict[str, float] = {}
    for line, op, _s, dur in events:
        if line == ops_line:
            total[op] = total.get(op, 0.0) + dur
    return [[op, ns / 1e9] for op, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(merged: list[tuple], window: tuple[float, float],
              n: int = 10) -> list[list]:
    """The longest stretches with nothing on the device. What the host
    was doing in them needs the program's spans on this clock (the
    `tracing` issue); until then a gap is named by the operations on
    either side of it."""
    gaps = []
    prev_end, prev_op = window[0], "window start"
    for start, end, first, last in merged:
        if start > prev_end:
            gaps.append([f"after {prev_op} before {first}",
                         (start - prev_end) / 1e9])
        prev_end, prev_op = max(prev_end, end), last
    if window[1] > prev_end:
        gaps.append([f"after {prev_op} before window end",
                     (window[1] - prev_end) / 1e9])
    return sorted(gaps, key=lambda g: -g[1])[:n]


def clip_events(events: list[tuple], lo: float, hi: float) -> list[tuple]:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for line, op, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((line, op, s, e - s))
    return out


def reduce_planes(planes: dict[str, list[tuple]],
                  clip: tuple[float, float] | None = None) -> dict:
    """busy_s averaged over the device planes, the window, and the
    breakdown of the first plane. `clip` = (start_ns, end_ns) is the
    stretch the caller's counters cover, on the trace's clock
    (`find_mark`): events are cut to it and it is the window, edge gaps
    included. Without it the window is the span of the events
    themselves (the self-check's recorded trace). `outside_s` is the
    busy time the profiler recorded before and after the stretch,
    `events_span_s` the span of the events that were kept."""
    if not planes:
        raise ValueError("the trace has no device plane")
    outside_s = 0.0
    if clip is not None:
        whole = sum(busy_union(evs)[0] for evs in planes.values())
        planes = {name: clip_events(evs, *clip)
                  for name, evs in planes.items()}
    busy, spans, merged = [], [], None
    for evs in planes.values():
        ns, intervals = busy_union(evs)
        busy.append(ns / 1e9)
        if merged is None:
            merged = intervals
        if evs:
            spans.append((min(e[2] for e in evs),
                          max(e[2] + e[3] for e in evs)))
    first = next(iter(planes.values()))
    lo = min(s for s, _e in spans) if spans else 0.0
    hi = max(e for _s, e in spans) if spans else 0.0
    events_span_s = (hi - lo) / 1e9
    if clip is not None:
        lo, hi = clip
        outside_s = (whole / 1e9 - sum(busy)) / len(busy)
    return {"busy_s": sum(busy) / len(busy),
            "window_s": (hi - lo) / 1e9,
            "events_span_s": events_span_s,
            "outside_s": outside_s,
            "device_ops": top_ops(first),
            "idle_gaps": idle_gaps(merged, (lo, hi))}
