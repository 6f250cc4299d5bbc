"""Pipelined data path: stage executor semantics, staging-pool
back-pressure, pipelined PUT correctness (short last block, zero-byte,
single-block, multi-batch), on-disk byte identity vs the serial loop,
GET lookahead prefetch, quorum-error propagation, and the OBD fault
counters."""

from __future__ import annotations

import io
import os

import numpy as np
import pytest

from minio_tpu.object import ErasureSetObjects, api_errors
from minio_tpu.object import engine as engine_mod
from minio_tpu.parallel import pipeline as pl
from minio_tpu.storage import XLStorage, errors as serr, new_format_erasure_v3
from minio_tpu.storage.naughty import NaughtyDisk

K, M = 4, 2
NDISKS = K + M
BLOCK = 1 << 16


def make_engine(tmp_path, sub="", naughty=False):
    fmts = new_format_erasure_v3(1, NDISKS)
    disks = []
    for j in range(NDISKS):
        d = XLStorage(str(tmp_path / f"{sub}d{j}"))
        d.write_format(fmts[0][j])
        disks.append(NaughtyDisk(d) if naughty else d)
    e = ErasureSetObjects(disks, K, M, block_size=BLOCK)
    e.make_bucket("b")
    return e


def payload(size, seed=7) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def put_pipelined(eng, name, data: bytes):
    """PUT through the pipelined loop regardless of size: an unknown
    stream length bypasses the fits-one-batch serial heuristic."""
    return eng.put_object("b", name, io.BytesIO(data), size=-1)


# ---------------------------------------------------------------------------
# StagePipeline executor
# ---------------------------------------------------------------------------

def test_stage_pipeline_preserves_order():
    seen: list[int] = []
    pipe = pl.StagePipeline([lambda x: x * 10,
                             lambda x: seen.append(x)], depth=2)
    for i in range(50):
        pipe.submit(i)
    pipe.close()
    assert seen == [i * 10 for i in range(50)]


def test_stage_pipeline_raises_original_error_and_drops():
    class Boom(RuntimeError):
        pass

    dropped: list = []

    def stage2(x):
        if x == 3:
            raise Boom("writer died")

    pipe = pl.StagePipeline([lambda x: x, stage2], depth=1,
                            on_drop=dropped.append)
    with pytest.raises(Boom):
        for i in range(100):
            pipe.submit(i)
    assert pipe.failed
    # close(abort=True) after a caller-side raise must not re-raise
    pipe.close(abort=True)
    # items queued behind the failure were handed to on_drop
    assert dropped


def test_stage_pipeline_close_reraises_tail_error():
    class Boom(RuntimeError):
        pass

    def stage(x):
        raise Boom("late failure")

    pipe = pl.StagePipeline([stage], depth=4)
    pipe.submit(1)      # may or may not raise here (timing)
    with pytest.raises(Boom):
        pipe.close()


def test_staging_pool_is_shared_per_width():
    a = pl.staging_pool(12345)
    b = pl.staging_pool(12345)
    assert a is b and a.width == 12345


# ---------------------------------------------------------------------------
# pipelined PUT correctness
# ---------------------------------------------------------------------------

def test_pipelined_put_roundtrip_sizes(tmp_path, monkeypatch):
    """Zero-byte, single-block, short-last-block and multi-batch
    objects through the pipelined loop (batch cap shrunk so small
    fixtures span many batches)."""
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    eng = make_engine(tmp_path)
    for size in [0, 1, 100, BLOCK - 1, BLOCK, BLOCK + 1, 2 * BLOCK,
                 5 * BLOCK + 12345]:
        data = payload(size, seed=size)
        oi = put_pipelined(eng, f"o{size}", data)
        assert oi.size == size
        import hashlib
        assert oi.etag == hashlib.md5(data).hexdigest()
        _, it = eng.get_object("b", f"o{size}")
        assert b"".join(it) == data, size
    # known-size exact batch multiple: EOF short-circuit (no probe
    # buffer acquired for a stream that is already fully staged)
    data = payload(4 * BLOCK, seed=99)
    eng.put_object("b", "exact", data)
    _, it = eng.get_object("b", "exact")
    assert b"".join(it) == data


@pytest.mark.parametrize("block_size", [BLOCK, BLOCK + 3])
def test_pipelined_shards_byte_identical_to_serial(tmp_path,
                                                   monkeypatch,
                                                   block_size):
    """The pipeline must not change a single byte on disk: same object
    through the serial and pipelined loops -> identical part files on
    every drive (klauspost-identical shard bytes + identical bitrot
    framing). The BLOCK+3 geometry has a nonzero pad tail
    (block_size % k != 0) and the pipelined engine puts a decoy object
    FIRST, so the comparison covers staging-buffer reuse: a stale pad
    tail would leak the decoy's bytes into the second object's
    shards."""
    import glob
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    fmts = new_format_erasure_v3(1, NDISKS)

    def mk(sub):
        disks = []
        for j in range(NDISKS):
            d = XLStorage(str(tmp_path / f"{sub}d{j}"))
            d.write_format(fmts[0][j])
            disks.append(d)
        e = ErasureSetObjects(disks, K, M, block_size=block_size)
        e.make_bucket("b")
        return e

    data = payload(7 * block_size + 4321, seed=42)

    monkeypatch.setattr(pl, "ENABLED", False)
    e_serial = mk("s")
    e_serial.put_object("b", "obj", data)

    monkeypatch.setattr(pl, "ENABLED", True)
    e_pipe = mk("p")
    put_pipelined(e_pipe, "decoy",
                  bytes([0xAA]) * (6 * block_size))  # dirty the ring
    put_pipelined(e_pipe, "obj", data)

    for j in range(NDISKS):
        parts_s = sorted(glob.glob(
            str(tmp_path / f"sd{j}" / "b" / "obj" / "*" / "part.1")))
        parts_p = sorted(glob.glob(
            str(tmp_path / f"pd{j}" / "b" / "obj" / "*" / "part.1")))
        assert len(parts_s) == len(parts_p) == 1, j
        with open(parts_s[0], "rb") as f:
            want = f.read()
        with open(parts_p[0], "rb") as f:
            got = f.read()
        assert got == want, f"drive {j} shard bytes diverge"


@pytest.mark.parametrize("block_size", [BLOCK, BLOCK + 6],
                         ids=["S-word-aligned", "S-not-word-aligned"])
def test_device_parity_reaches_the_drives_as_contiguous_rows(
        tmp_path, monkeypatch, block_size):
    """On the device route parity crosses the link as 32-bit words and
    is handed to `_write_shards_batch` as a VIEW of what crossed
    (models/pipeline.host_rows): (B, m, S) uint8, every row
    C-contiguous — a drive takes a row as one iovec, nothing is copied
    — also when S is no multiple of the word (BLOCK + 6 over k = 4:
    S = 16386), where the rows lie two pad bytes apart. The part files
    are the host route's, byte for byte."""
    import glob

    from minio_tpu.object import codec as codec_mod
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    fmts = new_format_erasure_v3(1, NDISKS)

    def mk(sub):
        disks = []
        for j in range(NDISKS):
            d = XLStorage(str(tmp_path / f"{sub}d{j}"))
            d.write_format(fmts[0][j])
            disks.append(d)
        e = ErasureSetObjects(disks, K, M, block_size=block_size)
        e.make_bucket("b")
        return e

    # whole blocks: a short last block would start the background load
    # of the ragged rungs (tests/test_ladder.py has that), which runs on
    # under the tests that follow
    data = payload(6 * block_size, seed=34)
    mk("h").put_object("b", "obj", data)             # the host route

    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    seen = []
    real = ErasureSetObjects._write_shards_batch

    def spy(self, rows, parity, dd, dp, *a, **kw):
        seen.append(parity)
        return real(self, rows, parity, dd, dp, *a, **kw)
    monkeypatch.setattr(ErasureSetObjects, "_write_shards_batch", spy)
    put_pipelined(mk("v"), "obj", data)

    s = -(-block_size // K)
    device_made = [p for p in seen if not p.flags.owndata]
    assert device_made, "no group rode the device route"
    for parity in seen:
        assert parity.dtype == np.uint8 and parity.shape[1:] == (M, s)
        assert all(parity[b, j].flags.c_contiguous
                   for b in range(parity.shape[0]) for j in range(M))
    for j in range(NDISKS):
        (want,) = glob.glob(
            str(tmp_path / f"hd{j}" / "b" / "obj" / "*" / "part.1"))
        (got,) = glob.glob(
            str(tmp_path / f"vd{j}" / "b" / "obj" / "*" / "part.1"))
        with open(want, "rb") as f, open(got, "rb") as g:
            assert g.read() == f.read(), f"drive {j} shard bytes diverge"


def test_pipeline_off_escape_hatch(tmp_path, monkeypatch):
    monkeypatch.setattr(pl, "ENABLED", False)
    called = []
    orig = ErasureSetObjects._encode_stream_serial

    def spy(self, *a, **kw):
        called.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(ErasureSetObjects, "_encode_stream_serial", spy)
    eng = make_engine(tmp_path)
    data = payload(3 * BLOCK + 7)
    eng.put_object("b", "o", io.BytesIO(data), size=-1)
    assert called                      # serial loop selected
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == data


def test_single_batch_stream_stays_serial(tmp_path, monkeypatch):
    """A stream that fits one encode batch has nothing to overlap —
    the known-size heuristic keeps it on the serial loop."""
    called = []
    orig = ErasureSetObjects._encode_stream_pipelined

    def spy(self, *a, **kw):
        called.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(ErasureSetObjects, "_encode_stream_pipelined",
                        spy)
    eng = make_engine(tmp_path)
    eng.put_object("b", "small", payload(BLOCK))       # known size
    assert not called
    eng.put_object("b", "big",
                   payload(engine_mod.ENCODE_BATCH_BLOCKS * BLOCK + 1))
    assert called


def test_pipelined_put_quorum_error_propagates(tmp_path, monkeypatch):
    """Writer death below quorum mid-stream fails the PUT with the
    REAL quorum error (fail-fast through the pipeline), and every
    staging buffer returns to the ring."""
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    eng = make_engine(tmp_path, naughty=True)
    for j in range(3):                  # 3 dead > m=2 tolerable
        eng.disks[j].fail_verbs["append_file"] = serr.FaultyDisk("dead")
        eng.disks[j].fail_verbs["create_file"] = serr.FaultyDisk("dead")
    width = 2 * K * (-(-BLOCK // K))
    pool = pl.staging_pool(width)
    with pytest.raises(api_errors.InsufficientWriteQuorum):
        put_pipelined(eng, "doomed", payload(6 * BLOCK))
    # buffers all recycled (the wreck didn't leak the ring) — and all
    # DISTINCT: a double pool.put of one buffer would hand the same
    # bytearray to two later streams (silent cross-stream corruption).
    # The pool allocates lazily, so "all recycled" = every CREATED
    # buffer is back in the queue.
    import time as _t
    deadline = _t.monotonic() + 5
    while pool._q.qsize() < pool._created and _t.monotonic() < deadline:
        _t.sleep(0.01)
    assert pool._created > 0
    assert pool._q.qsize() == pool._created
    held = [pool.get(timeout=1.0) for _ in range(pool.capacity)]
    try:
        assert len({id(b) for b in held}) == pool.capacity
    finally:
        for b in held:
            pool.put(b)


def test_pipelined_put_records_overlap_stats(tmp_path, monkeypatch):
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    eng = make_engine(tmp_path)
    before = pl.STATS.snapshot()
    put_pipelined(eng, "o", payload(6 * BLOCK))
    after = pl.STATS.snapshot()
    assert after["put_streams"] == before["put_streams"] + 1
    assert after["put_batches"] >= before["put_batches"] + 3
    assert after["put_wall_s"] > before["put_wall_s"]


# ---------------------------------------------------------------------------
# GET lookahead prefetch
# ---------------------------------------------------------------------------

def test_get_prefetch_multigroup_roundtrip(tmp_path, monkeypatch):
    """An object spanning several read groups roundtrips with the
    lookahead on, and the prefetch counters move."""
    monkeypatch.setattr(engine_mod, "GET_BATCH_BLOCKS", 2)
    eng = make_engine(tmp_path)
    data = payload(9 * BLOCK + 17, seed=9)
    eng.put_object("b", "o", data)
    before = pl.STATS.snapshot()
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == data
    after = pl.STATS.snapshot()
    assert after["get_prefetched"] > before["get_prefetched"]


def test_get_prefetch_degraded_read_reconstructs(tmp_path, monkeypatch):
    """Hedged-read degradation under the lookahead: two drives failing
    shard reads mid-GET still reconstruct every group, byte-identical,
    and flag the object for heal."""
    monkeypatch.setattr(engine_mod, "GET_BATCH_BLOCKS", 2)
    eng = make_engine(tmp_path, naughty=True)
    data = payload(8 * BLOCK + 99, seed=11)
    eng.put_object("b", "o", data)
    flagged = []
    eng.on_degraded_read = lambda b, o: flagged.append((b, o))
    for j in (0, 1):
        eng.disks[j].fail_verbs["read_file_stream"] = \
            serr.FaultyDisk("dead reader")
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == data
    assert flagged


def test_get_prefetch_off_is_serial(tmp_path, monkeypatch):
    monkeypatch.setattr(engine_mod, "GET_BATCH_BLOCKS", 2)
    monkeypatch.setattr(pl, "ENABLED", False)
    eng = make_engine(tmp_path)
    data = payload(6 * BLOCK, seed=3)
    eng.put_object("b", "o", data)
    before = pl.STATS.snapshot()
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == data
    after = pl.STATS.snapshot()
    assert after["get_prefetched"] == before["get_prefetched"]


# ---------------------------------------------------------------------------
# OBD fault counters
# ---------------------------------------------------------------------------

def test_obd_surfaces_drive_fault_counters(tmp_path):
    from minio_tpu.utils.obd import drive_fault_counters, local_obd
    eng = make_engine(tmp_path, naughty=True)
    eng.disks[0].fail_verbs["append_file"] = serr.FaultyDisk("x")
    try:
        eng.put_object("b", "o", payload(BLOCK))
    except api_errors.ObjectApiError:
        pass
    entries = drive_fault_counters(eng.disks)
    assert len(entries) == NDISKS
    assert all("faults" in e for e in entries)        # NaughtyDisk stats
    assert entries[0]["faults"]["total_ops"] > 0
    out = local_obd([], storage_drives=eng.disks)
    assert len(out["drive_faults"]) == NDISKS
    # a None slot reports offline instead of crashing the bundle
    entries = drive_fault_counters([None] + list(eng.disks[1:]))
    assert entries[0]["online"] is False


def test_obd_surfaces_transport_counters():
    from minio_tpu.distributed.storage_rpc import RemoteStorage
    from minio_tpu.utils.obd import drive_fault_counters
    rs = RemoteStorage("127.0.0.1", 1, "/tmp/none", "ak", "sk",
                       timeout=0.2)
    with pytest.raises(serr.StorageError):
        rs.list_vols()
    entries = drive_fault_counters([rs])
    t = entries[0]["transport"]
    assert t["calls"] >= 1 and t["net_errors"] >= 1
    assert t["offline_trips"] == 1 and t["online"] is False
    rs.rc.close()


def test_staging_ring_sized_from_admission_budget(monkeypatch):
    """configure_pool_buffers() derives the ring capacity from the
    RAM-gated admission budget (~2 buffers per admitted stream) for
    rings created after boot; the env knob pins it; tiny budgets keep
    the floor (ROADMAP PR 2 follow-up)."""
    from minio_tpu.parallel import pipeline as pl
    old = pl.POOL_BUFFERS
    try:
        monkeypatch.setattr(pl, "_POOL_ENV_SET", False)
        assert pl.configure_pool_buffers(24) == 48
        assert pl.POOL_BUFFERS == 48
        pool = pl.staging_pool(48 * 1024 + 1)   # fresh width -> new ring
        assert pool.capacity == 48
        assert pl.configure_pool_buffers(1) == 4          # floor
        # with MINIO_TPU_PIPELINE_POOL set, the operator's value wins
        monkeypatch.setattr(pl, "_POOL_ENV_SET", True)
        pl.POOL_BUFFERS = 7
        assert pl.configure_pool_buffers(100) == 7
    finally:
        pl.POOL_BUFFERS = old


# ---------------------------------------------------------------------------
# put_step_ragged: a launch some of whose blocks are short
# ---------------------------------------------------------------------------

_RK, _RM, _RS = 4, 2, 203       # S = 203: 6 whole packets + 11 bytes


@pytest.mark.parametrize("lengths", [
    (203,), (1,), (203, 102), (203, 203, 31), (203, 203, 203, 203),
    (32, 203, 33, 202, 64, 1), (203, 203, 101, 203, 7, 203, 203, 160),
], ids=lambda t: "-".join(map(str, t)))
def test_put_step_ragged_is_put_step_a_block_at_a_time(lengths):
    """Parity of the first lengths[b] columns and all k+m digests of
    every block equal the static put_step's over that block alone at
    its own shard length; parity beyond the length is zero."""
    from minio_tpu.models import pipeline as steps
    rng = np.random.default_rng(len(lengths) * 1000 + lengths[-1])
    b = len(lengths)
    data = np.zeros((b, _RK, _RS), np.uint8)
    for i, n in enumerate(lengths):
        data[i, :, :n] = rng.integers(0, 256, (_RK, n), dtype=np.uint8)
    parity, digests = steps.put_step_ragged(
        data, np.asarray(lengths, np.int32), _RK, _RM)
    parity = steps.host_rows(np.asarray(parity), _RS)
    digests = np.asarray(digests)
    assert parity.shape == (b, _RM, _RS)
    assert digests.shape == (b, _RK + _RM, 32)
    for i, n in enumerate(lengths):
        want_p, want_d = steps.put_step(
            np.ascontiguousarray(data[i:i + 1, :, :n]), _RK, _RM)
        assert np.array_equal(parity[i, :, :n],
                              steps.host_rows(np.asarray(want_p), n)[0]), i
        assert not parity[i, :, n:].any(), i
        assert np.array_equal(digests[i], np.asarray(want_d)[0]), i


def test_put_step_ragged_is_one_program_a_shape():
    from minio_tpu.models import pipeline as steps
    data = np.zeros((2, _RK, _RS), np.uint8)
    steps.put_step_ragged(data, np.array([203, 5], np.int32), _RK, _RM)
    before = steps.put_step_ragged._cache_size()
    steps.put_step_ragged(data, np.array([77, 203], np.int32), _RK, _RM)
    assert steps.put_step_ragged._cache_size() == before

# ---------------------------------------------------------------------------
# the link form: S-wide outputs cross as 32-bit words
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,at_blocks", [
    ((1, 4, 346), 8), ((6, 3, 512), 8), ((7, 7, 33), 8),   # small: at 8
    ((8, 4, 346), 8), ((3, 9, 346), 3), ((2, 16, 512), 2), ((20, 8, 512), 20),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_link_rows_is_the_same_bytes_as_words(shape, at_blocks):
    """`link_rows` -> `host_rows` is the identity on the bytes, for an
    S that is a multiple of the word and one that is not; a result of
    under 8 blocks of under 8 rows is converted at 8 blocks (XLA's TPU
    lowering compiles the small shapes for half a minute each) and
    leaves at its own size."""
    import jax
    from minio_tpu.models import pipeline as steps
    b, r, s = shape
    rows = np.random.default_rng(b * r + s).integers(
        0, 256, shape, dtype=np.uint8)
    words = np.asarray(jax.jit(steps.link_rows)(rows))
    assert (words.dtype, words.shape) == (np.uint32, (b, r, -(-s // 4)))
    back = steps.host_rows(words, s)
    assert back.dtype == np.uint8 and np.array_equal(back, rows)
    assert np.shares_memory(back, words)
    made = [v.aval.shape for eqn in jax.make_jaxpr(steps.link_rows)(rows).eqns
            for v in eqn.outvars if v.aval.dtype == np.uint32]
    assert made[0] == (at_blocks, r, -(-s // 4)) and made[-1] == words.shape
