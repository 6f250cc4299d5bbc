"""The closed ladder of launch sizes, and the boot load of its programs.

Every fused step (models/pipeline.py) is one XLA program per static
(B, k, S[, r]). Streams hand the former groups of any block count, so
without a ladder any B in 1..max_batch may launch — each a compile of
17-56 s at 12+4 on a v5e, or a 4-5 s load from the compile cache,
inside a request. Here a launch's block count maps to its RUNG: the
launch is padded with zero blocks up to the rung, the step runs at the
rung's B, and the pad blocks' rows and digests are cut off the host's
view of what the step made (a pad block over the link costs less than
one more program on the device: PERF.md §6, PR 34). The set of
programs a geometry can launch at full-block S is then the rungs —
finite, and enumerable by the program itself, so boot loads it
(`load_encode`).

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

An object whose whole body is under one block is one short block, and
launches at its S RUNG (`s_rungs`: a few shard lengths below the full
block's, derived from the GF kernel's tile), never in a launch of
full-block S: a 40 KiB body rides a (B, 12, 16384) launch, not a
(B, 12, 349526) one. Below the full S a pad block costs at most a
quarter of a full-S one, so such a launch's B ladder is coarser
(`subblock_rungs`: every power of two up to the cap). Those programs
load on demand, behind the first such launch the device takes
(`load_encode_ragged`), never at boot.
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


@functools.lru_cache(maxsize=None)
def s_rungs(shard_size: int) -> tuple[int, ...]:
    """The shard lengths a launch of objects under one block runs at
    (its S rungs): the GF kernel's lane tile (`rs_pallas._TS`, which
    every S is padded up to on the device anyway) times each power of
    four below the geometry's full-block `shard_size`, then that S —
    at most 4x the columns of the smallest rung a block fits. Every
    rung is a multiple of the hash's 32-byte packet and of the link
    form's word."""
    from ..ops import rs_pallas
    out, s = [], rs_pallas._TS
    while s < shard_size:
        out.append(s)
        s *= 4
    return (*out, shard_size)


def s_rung(shard_size: int, s_t: int) -> int:
    """The S rung of a block of shard length `s_t`: the smallest one
    that holds it."""
    return next(r for r in s_rungs(shard_size) if r >= s_t)


def group_of(verb: str) -> int:
    """Blocks a stream submits at a time, by verb."""
    from ..object import engine, healing
    return {"encode": engine.ENCODE_BATCH_BLOCKS,
            "decode": engine.GET_BATCH_BLOCKS,
            "recover": healing.HEAL_BATCH_BLOCKS}[verb]


@functools.lru_cache(maxsize=None)
def subblock_rungs(cap: int) -> tuple[int, ...]:
    """The launch sizes of objects under one block at an S rung below
    the full S: 1 and every power of two up to the cap, and the cap."""
    cap = max(cap, 1)
    return tuple(sorted({cap} | {1 << i for i in range(cap.bit_length())
                                 if 1 << i <= cap}))


def rungs_of(verb: str, cap: int = 0,
             subblock: bool = False) -> tuple[int, ...]:
    """A verb's launch sizes; `subblock`: of launches at an S rung
    below the full S."""
    if not cap:
        from . import scheduler
        cap = scheduler.MAX_BATCH_BLOCKS
    return subblock_rungs(cap) if subblock else rungs(group_of(verb), cap)


def rung(verb: str, blocks: int, cap: int = 0,
         subblock: bool = False) -> int:
    """The B a launch of `blocks` blocks runs at. A lone group larger
    than the cap (the former splits between groups, never inside one)
    rounds up to a multiple of the verb's group."""
    ladder = rungs_of(verb, cap, subblock)
    if blocks > ladder[-1]:
        g = group_of(verb)
        return -(-blocks // g) * g
    return next(r for r in ladder if r >= blocks)


def launch_size(verb: str, rows: int, blocks=None,
                subblock: bool = False) -> tuple[int, int]:
    """(real blocks, the B the step runs at) of a launch over an array
    of `rows` blocks: the array is padded to its rung already when the
    caller says how many of its rows are real (`blocks`: the batch
    former's staging buffer), and is brought up to it otherwise."""
    return (rows, rung(verb, rows, 0, subblock)) if blocks is None \
        else (blocks, rows)


def pad_blocks(arr: np.ndarray, to: int) -> np.ndarray:
    """`arr` with zero blocks appended along axis 0 up to `to` rows;
    `arr` itself when it has them."""
    if arr.shape[0] >= to:
        return arr
    out = np.zeros((to,) + arr.shape[1:], dtype=arr.dtype)
    out[:arr.shape[0]] = arr
    return out


# ---------------------------------------------------------------------------
# boot: load the encode rungs for the geometry the drive set declares;
# the first short block: load the rungs of the row that carries them
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


def _load_one(parent, codec, blocks: int, algo,
              ragged: bool = False) -> dict:
    """One encode rung's program, through the codec's own jitted
    entry point: the step at B = `blocks` and the codec's S (`ragged`:
    the step of a launch that carries short blocks)."""
    verb = "encode"
    with telemetry.span("boot.load_program", parent=parent, verb=verb,
                        B=blocks, S=codec.shard_size) as sp:
        _TLS.hits = _TLS.built = 0
        t0 = time.perf_counter()
        try:
            codec.load_encode_program(blocks, algo, ragged=ragged)
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
                workers: int = LOAD_WORKERS, ragged: bool = False,
                trigger: str = "boot", demand=None,
                s: int = 0) -> list[dict]:
    """Lower and compile — from the persistent compile cache when it
    is warm — every encode rung of `codec`'s geometry at its
    full-block S, `workers` at a time, largest first. -> one record a
    program. On a host without a TPU (or with the mesh route on, whose
    programs are not these) nothing is loaded. `ragged`: the rungs of
    the row a launch with a short block runs (`load_encode_ragged`
    asks), with a `demand`: the rungs launches are waiting for go
    first, and each is told as its program is in; `s`: at that S rung
    instead (objects under one block: `subblock_rungs`)."""
    from ..object.codec import _device_is_tpu, _mesh_active
    if not _device_is_tpu() or _mesh_active() is not None \
            or codec.m == 0 or codec._device_hash_kernel(algo) is None:
        return []
    _listen()
    s = s or codec.shard_size
    ladder = rungs_of("encode", cap, s < codec.shard_size)
    if s != codec.shard_size:
        # the programs at an S rung: those of the geometry's codec at
        # that shard length
        from ..object.codec import Codec
        codec = Codec(codec.k, codec.m, s * codec.k)
    row = "encode_and_hash_batch" + (".ragged" if ragged else "")
    with telemetry.span("boot.load_programs", verb="encode", row=row,
                        trigger=trigger, k=codec.k, m=codec.m,
                        S=s, programs=len(ladder),
                        workers=workers) as sp:
        todo = sorted(ladder, reverse=True)
        mu = threading.Lock()

        def load_next(_worker) -> list[dict]:
            done = []
            while True:
                with mu:
                    if not todo:
                        return done
                    blocks = todo.pop(demand.first(todo) if demand else 0)
                try:
                    done.append(_load_one(sp, codec, blocks, algo, ragged))
                finally:
                    if demand:
                        demand.met(blocks)
        workers = max(1, min(workers, len(todo)))
        with ThreadPoolExecutor(max_workers=workers,
                                thread_name_prefix="boot-load") as pool:
            return [rec for done in pool.map(load_next, range(workers))
                    for rec in done]


class _Demand:
    """The ragged rungs of one geometry while they load: which of them
    launches are waiting for, and an event a rung set as it is in."""

    def __init__(self, rungs):
        self.events = {b: threading.Event() for b in rungs}
        self.wanted: set[int] = set()

    def first(self, todo) -> int:
        """Index in `todo` of the job to load next: the largest rung a
        launch waits for, else the largest there is."""
        return next((i for i, b in enumerate(todo)
                     if b in self.wanted), 0)

    def met(self, blocks: int) -> None:
        self.events[blocks].set()

    def wait(self, blocks: int) -> None:
        if blocks in self.events:
            self.wanted.add(blocks)
            # check: allow(deadline) a program load; load_encode_ragged sets every event when it ends
            self.events[blocks].wait()


# geometry + S + algorithm -> the load of its ragged rungs: made by the
# first launch with a short block at that S that routes to the device,
# never at boot
_RAGGED: dict[tuple, _Demand] = {}
_RAGGED_MU = threading.Lock()


def load_encode_ragged(codec, algo, cap: int = 0, want: int = 0,
                       s: int = 0) -> bool:
    """Start, ONCE a geometry, S and process, the load of the rungs of
    the encode row that carries short blocks — in the background, on
    the boot-load pool: the codec calls this when a launch with a
    short block first routes to the device, so a store whose short
    blocks never reach it (or that stores none) never pays for them
    (ROADMAP A6 i: the rule for every program boot does not need).
    `want`: the rung the launch that asks is about to wait for; `s`:
    the launch's S rung when it carries objects under one block (0:
    the full block's; `trigger=first_subblock` below it).
    -> whether this call started it."""
    s = s or codec.shard_size
    sub = s < codec.shard_size
    key = (codec.k, codec.m, s, algo.value)
    with _RAGGED_MU:
        if key in _RAGGED:
            return False
        demand = _RAGGED[key] = _Demand(rungs_of("encode", cap, sub))
        demand.wanted.add(want)

    def run() -> None:
        try:
            # a trace of its own: no request's, and boot's is closed
            with telemetry.trace("node.load_programs"):
                load_encode(codec, algo, cap, ragged=True,
                            trigger="first_subblock" if sub
                            else "first_short_block", demand=demand, s=s)
        finally:
            for b in demand.events:
                demand.met(b)
    threading.Thread(target=run, name="boot-load-ragged",
                     daemon=True).start()
    return True


def await_ragged(codec, algo, blocks: int, s: int = 0) -> None:
    """A ragged launch at rung `blocks` (and S rung `s`; 0: the full
    block's) whose program is still loading waits for that load —
    which it moves to the head of the loader's queue — as any jit call
    waits for its compile: a second compile of the same program beside
    the loader's would take as long and twice the cores. Nothing to
    wait for when no load was started (an engine without a former) or
    the rung is off the ladder (it compiles in the call)."""
    demand = _RAGGED.get(
        (codec.k, codec.m, s or codec.shard_size, algo.value))
    if demand is not None:
        demand.wait(blocks)
