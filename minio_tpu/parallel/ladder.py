"""The closed ladder of launch sizes, and the boot load of its programs.

Every fused step (models/pipeline.py) is one XLA program per static
(B, k, S[, r]). Streams hand the former groups of any block count, so
without a ladder any B in 1..max_batch may launch — each a compile of
17-56 s at 12+4 on a v5e, or a 4-5 s load from the compile cache,
inside a request. Here a launch's block count maps to its RUNG: the
launch is padded with zero blocks up to the rung, the step runs at the
rung's B, and the pad blocks' rows and digests are cut off on the
device before anything is fetched. The set of programs a geometry can
launch at full-block S is then the rungs — finite, and enumerable by
the program itself, so boot loads it (`load_encode`).

The rungs are derived from the constants the program already has —
the verb's group size (`engine.ENCODE_BATCH_BLOCKS`,
`engine.GET_BATCH_BLOCKS`, `healing.HEAL_BATCH_BLOCKS`) and the
former's cap (`scheduler.max_batch`) — never written down beside them:

  * every multiple of the group up to the cap: whole-group traffic
    pads nothing;
  * 1, and the fewest further rungs such that a launch of B >= 2 pads
    by at most a third of its blocks, each the roundest number its gap
    allows (12 rather than 11: launches of small even groups land on
    it unpadded).

Defaults (group 8, cap 32): 1 2 4 6 8 12 16 20 24 32.
"""

from __future__ import annotations

import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..utils import eventlog, telemetry

# programs boot loads at a time: see PERF.md §5 (6) for the chip's
# readings at 1, 2, 4 and all at once
LOAD_WORKERS = 4


def _roundest(lo: int, hi: int) -> int:
    """The multiple of the largest power of two in [lo, hi] (the
    largest such multiple)."""
    step = 1 << hi.bit_length()
    while step > 1:
        r = hi - hi % step
        if r >= lo:
            return r
        step >>= 1
    return hi


@functools.lru_cache(maxsize=None)
def rungs(group: int, cap: int) -> tuple[int, ...]:
    """The launch sizes of a verb whose streams submit groups of
    `group` blocks to a former that fuses up to `cap`."""
    group, cap = max(group, 1), max(cap, 1)
    out = {1, cap} | set(range(group, cap + 1, group))
    for b in range(2, cap):
        hi = min(b + b // 3, cap)
        if not any(b <= r <= hi for r in out):
            out.add(_roundest(b, hi))
    return tuple(sorted(out))


def group_of(verb: str) -> int:
    """Blocks a stream submits at a time, by verb."""
    from ..object import engine, healing
    return {"encode": engine.ENCODE_BATCH_BLOCKS,
            "decode": engine.GET_BATCH_BLOCKS,
            "recover": healing.HEAL_BATCH_BLOCKS}[verb]


def rungs_of(verb: str, cap: int = 0) -> tuple[int, ...]:
    if not cap:
        from . import scheduler
        cap = scheduler.MAX_BATCH_BLOCKS
    return rungs(group_of(verb), cap)


def rung(verb: str, blocks: int, cap: int = 0) -> int:
    """The B a launch of `blocks` blocks runs at. A lone group larger
    than the cap (the former splits between groups, never inside one)
    rounds up to a multiple of the verb's group."""
    ladder = rungs_of(verb, cap)
    if blocks > ladder[-1]:
        g = group_of(verb)
        return -(-blocks // g) * g
    return next(r for r in ladder if r >= blocks)


def pad_blocks(arr: np.ndarray, to: int) -> np.ndarray:
    """`arr` with zero blocks appended along axis 0 up to `to` rows;
    `arr` itself when it has them."""
    if arr.shape[0] >= to:
        return arr
    out = np.zeros((to,) + arr.shape[1:], dtype=arr.dtype)
    out[:arr.shape[0]] = arr
    return out


# ---------------------------------------------------------------------------
# boot: load the encode rungs for the geometry the drive set declares
# ---------------------------------------------------------------------------

_TLS = threading.local()


@functools.cache
def _listen() -> None:
    """Count, per thread, what JAX's compile path reports (registered
    once a process): a load from the persistent cache fires
    cache_hits, and any program that was not in this process fires
    backend_compile_duration."""
    import jax.monitoring as mon

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _TLS.hits = getattr(_TLS, "hits", 0) + 1

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _TLS.built = getattr(_TLS, "built", 0) + 1
    mon.register_event_listener(on_event)
    mon.register_event_duration_secs_listener(on_duration)


def _load_one(parent, codec, blocks: int, cuts, algo) -> dict:
    """One encode rung's programs, through the codec's own jitted
    entry points: the step at B = `blocks`, and the cuts that take a
    padded launch's outputs back to each real count in `cuts`."""
    verb = "encode"
    with telemetry.span("boot.load_program", parent=parent, verb=verb,
                        B=blocks, S=codec.shard_size,
                        cuts=len(cuts)) as sp:
        _TLS.hits = _TLS.built = 0
        t0 = time.perf_counter()
        try:
            codec.load_encode_program(blocks, cuts, algo)
            how = "hit" if _TLS.hits else \
                "compiled" if _TLS.built else "resident"
        except Exception as e:  # noqa: BLE001 — the node still boots:
            # a program that did not load compiles inside its first
            # launch, and a launch that fails falls to the host path
            how = "error"
            eventlog.emit_once(
                "device.decline", stage="boot", reason="error",
                detail=f"B={blocks}: {type(e).__name__}: {e}"[:300])
            if sp is not None:
                sp.mark_error(f"{type(e).__name__}: {e}")
        if sp is not None:
            sp.attrs["cached"] = how
        return {"verb": verb, "B": blocks, "S": codec.shard_size,
                "cached": how, "seconds": time.perf_counter() - t0}


def load_encode(codec, algo, cap: int = 0,
                workers: int = LOAD_WORKERS) -> list[dict]:
    """Lower and compile — from the persistent compile cache when it
    is warm — every encode rung of `codec`'s geometry at its
    full-block S, `workers` at a time, largest first. -> one record a
    program. On a host without a TPU (or with the mesh route on, whose
    programs are not these) nothing is loaded."""
    from ..object.codec import _device_is_tpu, _mesh_active
    if not _device_is_tpu() or _mesh_active() is not None \
            or codec.m == 0 or codec._device_hash_kernel(algo) is None:
        return []
    _listen()
    ladder = rungs_of("encode", cap)
    with telemetry.span("boot.load_programs", verb="encode",
                        k=codec.k, m=codec.m, S=codec.shard_size,
                        programs=len(ladder), workers=workers) as sp:
        jobs = [(b, tuple(range(below + 1, b)))
                for below, b in zip((0,) + ladder, ladder)]
        with ThreadPoolExecutor(max_workers=max(1, workers),
                                thread_name_prefix="boot-load") as pool:
            return list(pool.map(
                lambda job: _load_one(sp, codec, job[0], job[1], algo),
                sorted(jobs, reverse=True)))
