"""Multi-chip sharding of the erasure data path.

Mapping of the reference's distribution axes onto a TPU mesh (reference
parallelism inventory: SURVEY §2.5):

  dp ("data")     — independent objects/blocks: batch dim of the shard
                    tensors. The analog of the reference's per-request
                    goroutine fan-out (its RAM-gated admission control).
  sp ("sequence") — byte columns of a block. Blocks are GF-columnwise
                    independent, so a huge object's bytes shard across
                    chips with zero cross-talk in encode/decode — the
                    storage analog of sequence/context parallelism (no
                    ring needed; the "attention" here is column-local).
  tp              — output-shard rows (the coding matrix's rows) can be
                    row-sharded for very wide sets; with n <= 32 shards
                    the matrix is tiny, so tp is folded into dp unless
                    explicitly requested.
  ep              — erasure-set routing (sipHashMod object->set) stays on
                    the host control plane (object/sets.py), exactly like
                    the reference's static "expert" routing.

Collectives used (all ride ICI inside a pool): all_to_all for the
SP→TP digest reshard; psum for global counters/consistency checks.
Cross-host traffic (remote drives) stays on the gRPC/HTTP data plane
(storage/), mirroring the reference's DCN split.

Serving integration (VERDICT r4 #1): object/codec.py dispatches its
fused put/get/heal batches through the `mesh_*` helpers below when
MINIO_TPU_MESH=1 asks for it and more than one device is visible (the
virtual CPU mesh in tests; opt-in on real chips until the route passes
there — PERF.md, PR 21). Shard-row counts that don't divide the sp axis
are zero-padded for the digest reshard (pad-row digests are dropped
before returning); byte columns must divide sp exactly, and a batch
whose S does not is declined with a `device.decline stage=mesh` event.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import rs_matrix, rs_tpu
from ..models import pipeline
from ..utils import device, eventlog, lockcheck


def make_mesh(n_devices: int | None = None, devices=None,
              sp: int | None = None) -> Mesh:
    """Factor n devices into a (dp, sp) mesh. By default sp (byte-column
    sharding, scales with object size) takes the largest factor <= 8;
    pass `sp` to pin the split (tests exercise both axes)."""
    if devices is None:
        devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    n = len(devices)
    if sp is None:
        sp = 1
        for cand in range(min(n, 8), 0, -1):
            if n % cand == 0:
                sp = cand
                break
    if n % sp:
        raise ValueError(f"sp={sp} does not divide {n} devices")
    dp = n // sp
    dev_array = np.asarray(devices).reshape(dp, sp)
    return Mesh(dev_array, axis_names=("dp", "sp"))


_DEFAULT_MESH: Optional[Mesh] | bool = None


def default_mesh() -> Optional[Mesh]:
    """Process-wide mesh over every visible device, or None when the
    process is single-device. Built once: the device set is fixed for a
    process lifetime, and the jitted step caches key on the mesh."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = make_mesh() if device.probe().count > 1 else False
    return _DEFAULT_MESH or None


def _digest_reshard(rows3: jax.Array, n_rows: int, sp_size: int,
                    shard_len: int, algo: str) -> jax.Array:
    """Shared SP→TP digest pass: (B/dp, n_rows, S/sp) column-sharded
    shard rows -> (B/dp, n_pad/sp, 32) digests of WHOLE rows.

    Bitrot digests are sequential over a shard's full byte stream, so
    the pipeline re-shards from column-sharded to shard-row-sharded
    with an all_to_all over sp (the storage analog of a
    sequence-parallel attention's SP→TP switch), then each device
    hashes its rows whole. n_rows that doesn't divide sp is zero-padded
    (pad-row digests hash garbage nobody reads; callers slice them
    off)."""
    n_pad = -(-n_rows // sp_size) * sp_size
    if n_pad != n_rows:
        rows3 = jnp.pad(rows3, ((0, 0), (0, n_pad - n_rows), (0, 0)))
    rows = jax.lax.all_to_all(rows3, "sp", split_axis=1, concat_axis=2,
                              tiled=True)       # (B/dp, n_pad/sp, S)
    b_loc, r_loc, s_full = rows.shape
    return pipeline._hash_rows(
        rows.reshape(b_loc * r_loc, s_full), shard_len or s_full, b"",
        algo).reshape(b_loc, r_loc, 32)


@functools.lru_cache(maxsize=64)
def sharded_put_step(mesh: Mesh, k: int, m: int,
                     algo: str = "highwayhash", shard_len: int = 0):
    """Build the jitted multi-chip PUT step over `mesh`: the full
    encode+bitrot pipeline with real collectives.

    In:  data (B, k, S) uint8, B % dp == 0, S % sp == 0.
    Out: parity (B, m, S) column-sharded like the input; digests
         (B, k+m, 32) per-shard bitrot digests (HighwayHash256 or
         SHA-256 per `algo`); a psum'd consistency counter.

    Encode runs column-sharded (sp = byte columns, GF-columnwise
    independent — zero collectives); digests ride _digest_reshard's
    all_to_all. (k+m) need not divide sp — pad rows are sliced off.
    """
    pm = np.asarray(rs_matrix.parity_matrix(k, m))
    m2 = rs_tpu._bit_expand_cached(pm.tobytes(), pm.shape)
    n = k + m
    sp_size = mesh.devices.shape[1]

    def local_step(data):  # data: (B/dp, k, S/sp)
        parity = rs_tpu.gf_matmul_xla(jnp.asarray(m2, jnp.bfloat16), data)
        full = jnp.concatenate([data, parity], axis=-2)  # (B/dp, n, S/sp)
        digests = _digest_reshard(full, n, sp_size, shard_len, algo)
        # global consistency counter (exercises psum across both axes)
        total = jax.lax.psum(
            jax.lax.psum(jnp.sum(parity.astype(jnp.int32) & 1), "sp"), "dp")
        return parity, digests, total

    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None, "sp"),),
        out_specs=(P("dp", None, "sp"), P("dp", "sp", None), P()),
        check_vma=False)
    jitted = jax.jit(fn)

    def run(data):
        parity, digests, total = jitted(data)
        return parity, digests[:, :n], total
    return run


@functools.lru_cache(maxsize=64)
def sharded_get_step(mesh: Mesh, k: int, m: int, present_mask: int,
                     algo: str = "highwayhash", shard_len: int = 0):
    """Multi-chip fused verify+decode (the r3 flagship in SPMD form):
    survivors (B, k, S) in decode `used` order, column-sharded ->
    (missing data rows, survivor bitrot digests).

    The decode matmul is GF-columnwise independent (zero collectives);
    the digest pass reshards survivors SP→TP with an all_to_all so
    each device hashes whole shard rows — identical collective pattern
    to the PUT pipeline, so GET-with-failures scales the same way.
    k that doesn't divide the sp axis is zero-padded for the digest
    reshard (pad-row digests are dropped before returning).
    """
    dm, _used, missing = rs_matrix.missing_data_matrix(
        k, m, present_mask)
    m2 = rs_tpu._bit_expand_cached(dm.tobytes(), dm.shape)
    sp_size = mesh.devices.shape[1]

    def local_step(survivors):  # (B/dp, k, S/sp)
        out = rs_tpu.gf_matmul_xla(jnp.asarray(m2, jnp.bfloat16),
                                   survivors)
        digests = _digest_reshard(survivors, k, sp_size, shard_len, algo)
        return out, digests

    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None, "sp"),),
        out_specs=(P("dp", None, "sp"), P("dp", "sp", None)),
        check_vma=False)
    jitted = jax.jit(fn)

    def run(survivors):
        out, digests = jitted(survivors)
        return out, digests[:, :k]            # drop the pad rows
    return run, missing


@functools.lru_cache(maxsize=64)
def sharded_heal_step(mesh: Mesh, k: int, m: int, present_mask: int,
                      rows: tuple = (), algo: str = "highwayhash",
                      shard_len: int = 0):
    """Multi-chip heal with the fused single-device semantics
    (models/pipeline.heal_step): verify the survivors, rebuild the lost
    shards, and digest the rebuilt shards for their new bitrot frames —
    all sharded. Byte-column independence keeps the matmul
    collective-free; digests ride the same SP→TP all_to_all as PUT.

    `rows` restricts recovery to those shard indices (empty = all
    missing). Returns (run, idxs): run(survivors (B, k, S)) ->
    (recovered (B, R, S), survivor_digests (B, k, 32),
    recovered_digests (B, R, 32)); idxs maps output rows to shard
    indices.
    """
    rec, idxs = rs_matrix.recover_rows(k, m, present_mask, rows)
    m2 = rs_tpu._bit_expand_cached(rec.tobytes(), rec.shape)
    r_cnt = len(idxs)
    sp_size = mesh.devices.shape[1]

    def local_step(survivors):  # (B/dp, k, S/sp)
        out = rs_tpu.gf_matmul_xla(jnp.asarray(m2, jnp.bfloat16),
                                   survivors)
        both = jnp.concatenate([survivors, out], axis=-2)
        digests = _digest_reshard(both, k + r_cnt, sp_size, shard_len,
                                  algo)
        return out, digests

    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None, "sp"),),
        out_specs=(P("dp", None, "sp"), P("dp", "sp", None)),
        check_vma=False)
    jitted = jax.jit(fn)

    def run(survivors):
        out, digests = jitted(survivors)
        return out, digests[:, :k], digests[:, k:k + r_cnt]
    return run, idxs


def shard_array(mesh: Mesh, arr, spec: P):
    return jax.device_put(arr, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# numpy-facing serving dispatch (object/codec.py calls these)
# ---------------------------------------------------------------------------

class _Dispatches:
    """Thread-safe mesh-dispatch counter (BatchScheduler workers and
    direct callers bump it concurrently). Compares like an int."""

    def __init__(self):
        self._n = 0
        self._mu = threading.Lock()

    def bump(self):
        with self._mu:
            self._n += 1

    @property
    def value(self) -> int:
        return self._n

    def __index__(self):
        return self._n

    def __eq__(self, other):
        return self._n == other

    def __gt__(self, other):
        return self._n > other

    def __lt__(self, other):
        return self._n < other

    def __add__(self, other):
        return self._n + other

    def __repr__(self):
        return f"_Dispatches({self._n})"


DISPATCHES = _Dispatches()    # mesh device calls (tests/metrics)

# On NON-TPU backends, host-side mesh dispatches serialize on this
# lock (held through materialization, so dispatches fully serialize):
# two threads executing collective (all_to_all) programs concurrently
# can starve the virtual-device execution pool of each other's
# participants and deadlock — observed on the 8-virtual-device CPU
# mesh under concurrent per-request dispatch (the scheduler-bypass
# A/B), and the same hazard exists for any concurrent direct caller.
# Real TPU pools keep concurrent dispatch (the scheduler's INFLIGHT
# overlap): the PjRt TPU client runs concurrent executions safely.
_DISPATCH_MU = lockcheck.mutex("mesh.dispatch")
_NULL_MU = contextlib.nullcontext()


def _dispatch_guard(mesh: Mesh):
    devs = mesh.devices.flat
    return _NULL_MU if devs[0].platform == "tpu" else _DISPATCH_MU


def _shardable(mesh: Mesh, b: int, s: int) -> Optional[tuple[int, int]]:
    """(dp, sp) when a (B, *, S) batch can shard over `mesh`: byte
    columns must split exactly (no pad — GF columns are real data);
    short batches are padded up to dp by the callers."""
    dp, sp = mesh.devices.shape
    if s == 0 or s % sp:
        eventlog.emit_once("device.decline", stage="mesh",
                           reason="unshardable")
        return None
    return dp, sp


def _pad_batch(data: np.ndarray, dp: int) -> tuple[np.ndarray, int]:
    b = data.shape[0]
    pad = -b % dp
    if pad:
        data = np.concatenate(
            [data, np.zeros((pad,) + data.shape[1:], np.uint8)])
    return data, b


def mesh_encode_and_hash(mesh: Mesh, data: np.ndarray, k: int, m: int,
                         algo: str = "highwayhash"):
    """Sharded form of Codec.encode_and_hash_batch: (B, k, S) ->
    (parity (B, m, S), digests (B, k+m, 32)) numpy, or None when the
    shapes can't shard over this mesh (caller falls through to the
    single-device path)."""
    b_, k_, s = data.shape
    geom = _shardable(mesh, b_, s)
    if geom is None:
        return None
    dp, _sp = geom
    data, b = _pad_batch(np.ascontiguousarray(data, np.uint8), dp)
    step = sharded_put_step(mesh, k, m, algo)
    with _dispatch_guard(mesh):
        arr = shard_array(mesh, data, P("dp", None, "sp"))
        parity, digests, _total = step(arr)
        DISPATCHES.bump()
        return np.asarray(parity)[:b], np.asarray(digests)[:b]


def mesh_verify_and_decode(mesh: Mesh, survivors: np.ndarray, k: int,
                           m: int, present_mask: int, shard_len: int,
                           algo: str = "highwayhash"):
    """Sharded form of Codec.verify_and_decode_batch: survivors
    (B, k, S) in `used` order -> (missing (B, r, S), missing_idxs,
    survivor_digests (B, k, 32)), or None when unshardable."""
    b_, _k, s = survivors.shape
    geom = _shardable(mesh, b_, s)
    if geom is None:
        return None
    # nothing missing -> nothing to fuse with; bail BEFORE building a
    # jitted step that would only pollute the lru cache
    _dm, _used, missing = rs_matrix.missing_data_matrix(
        k, m, present_mask)
    if not missing:
        return None
    dp, _sp = geom
    survivors, b = _pad_batch(
        np.ascontiguousarray(survivors, np.uint8), dp)
    run, missing = sharded_get_step(mesh, k, m, present_mask, algo,
                                    shard_len)
    with _dispatch_guard(mesh):
        arr = shard_array(mesh, survivors, P("dp", None, "sp"))
        out, digests = run(arr)
        DISPATCHES.bump()
        return np.asarray(out)[:b], missing, np.asarray(digests)[:b]


def mesh_verify_and_recover(mesh: Mesh, survivors: np.ndarray, k: int,
                            m: int, present_mask: int, rows,
                            shard_len: int, algo: str = "highwayhash"):
    """Sharded form of Codec.verify_and_recover_batch: -> (out
    (B, R, S), idxs, survivor_digests, out_digests), or None."""
    b_, _k, s = survivors.shape
    geom = _shardable(mesh, b_, s)
    if geom is None:
        return None
    # requested rows that are actually missing, BEFORE building a step
    _rec, idxs = rs_matrix.recover_rows(k, m, present_mask,
                                        tuple(sorted(rows)))
    if not idxs:
        return None
    dp, _sp = geom
    survivors, b = _pad_batch(
        np.ascontiguousarray(survivors, np.uint8), dp)
    run, idxs = sharded_heal_step(mesh, k, m, present_mask,
                                  tuple(sorted(rows)), algo, shard_len)
    with _dispatch_guard(mesh):
        arr = shard_array(mesh, survivors, P("dp", None, "sp"))
        out, sdig, odig = run(arr)
        DISPATCHES.bump()
        return (np.asarray(out)[:b], idxs, np.asarray(sdig)[:b],
                np.asarray(odig)[:b])
