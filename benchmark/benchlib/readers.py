"""Arithmetic the per-layer readers share. A reader
(`benchmark/layer_metrics/<metric>.py`) gets the traced run's window
— client records, the program's counters at both marks, the trace
reduction — and returns a number, or None when it finds nothing to
read (the harness then leaves the metric out; it never prints 0 for a
share of a peak).
"""

from __future__ import annotations

import numpy as np

from benchlib import workbytes


def percentile(values: list[float], q: float) -> float | None:
    return float(np.percentile(values, q)) if values else None


def latency_ms(win: dict, op: str, q: float) -> float | None:
    """q-th percentile over ALL requests of `op` that ended in the
    window, request sent -> last body byte, on the client's clock."""
    if win["op"] != op:
        return None
    return percentile([(r[1] - r[0]) * 1e3 for r in win["records"]], q)


def _verb_delta(win: dict, verb: str, field: str) -> int:
    return win["c1"]["verbs"][verb][field] - win["c0"]["verbs"][verb][field]


def blocks_per_launch(win: dict, verb: str) -> float | None:
    batches = _verb_delta(win, verb, "batches")
    return _verb_delta(win, verb, "blocks") / batches if batches else None


def stage_mean_ms(win: dict, verb: str, stage: str) -> float | None:
    key = f"{verb}.{stage}"
    n0, s0 = win["c0"]["stages"].get(key, [0, 0.0])
    n1, s1 = win["c1"]["stages"].get(key, [0, 0.0])
    return (s1 - s0) / (n1 - n0) * 1e3 if n1 > n0 else None


def device_routed_share(win: dict, verb: str) -> float | None:
    dev = _verb_delta(win, verb, "batches")
    cpu = _verb_delta(win, verb, "cpu_routed")
    return 100.0 * dev / (dev + cpu) if dev + cpu else None


def compiles(win: dict, verb: str) -> float | None:
    """Programs built or loaded between the marks; reported under the
    cell's own verb."""
    return float(win["compiles"]) if win["verb"] == verb else None


def hbm_share(win: dict, verb: str) -> float | None:
    """Least bytes the verb's dispatched blocks must move, over the
    chip's HBM peak, over the device's busy time — all three over the
    traced stretch of the window (the profiler's events are cut to the
    stretch the block counts cover). Busy time is the union of every
    device operation, not the events of one kernel's name, so renaming,
    splitting or fusing the step cannot empty it."""
    tr = win.get("trace")
    if not tr or win["verb"] != verb or tr["busy_s"] <= 0:
        return None
    blocks = tr["blocks"].get(verb, 0)
    if not blocks:
        return None
    g = win["geometry"]
    least_s = workbytes.verb_bytes(verb, blocks, g["k"], g["m"],
                                   g["block_size"],
                                   tr.get("decode_by_lost")) \
        / win["peak"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
