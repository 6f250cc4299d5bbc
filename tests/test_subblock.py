"""Objects whose whole body is under one block: laid at their S rung
(parallel/ladder.s_rungs), coalesced only with each other at that rung,
routed by the rung (object/codec.subblock_on_device) and never by how
many happened to coalesce — and written byte for byte as the full-S
layout writes them.

The geometry is 4+2 with 512 KiB blocks (S = 131072, S rungs 16384,
65536, 131072); the sizes are the octave midpoints of MinIO warp's
`--obj.size 10MiB --obj.randsize` range (40 KiB - 10 MiB at 4 MiB
blocks) scaled by the block: seven under one block, one over it."""

from __future__ import annotations

import functools
import glob
import io
import threading

import numpy as np
import pytest

from minio_tpu import bitrot as bitrot_mod
from minio_tpu.object import ErasureSetObjects
from minio_tpu.object import codec as codec_mod
from minio_tpu.object.codec import Codec
from minio_tpu.ops import highwayhash_py, rs_pallas, rs_ref
from minio_tpu.parallel import ladder
from minio_tpu.parallel import pipeline as pl
from minio_tpu.parallel.scheduler import BatchScheduler
from minio_tpu.storage import XLStorage, new_format_erasure_v3
from minio_tpu.utils import telemetry

HH = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S
K, M = 4, 2
N = K + M
BLOCK = 1 << 19
S = BLOCK // K                                   # 131072
# the mix's octave midpoints of 40 KiB - 10 MiB at 4 MiB blocks, scaled
SIZES = [n * BLOCK // (4 << 20) for n in
         (57926, 115852, 231705, 463410, 926819, 1853638, 3707276, 7414552)]


def _engine(tmp_path, sched=None) -> ErasureSetObjects:
    fmts = new_format_erasure_v3(1, N)
    disks = []
    for j in range(N):
        d = XLStorage(str(tmp_path / f"d{j}"))
        d.write_format(fmts[0][j])
        disks.append(d)
    eng = ErasureSetObjects(disks, K, M, block_size=BLOCK, scheduler=sched)
    eng.make_bucket("b")
    return eng


def _body(nbytes: int) -> bytes:
    return np.random.default_rng(nbytes).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


@functools.lru_cache(maxsize=None)
def _part_files(nbytes: int) -> tuple[bytes, ...]:
    """The plain reference's part files of `_body(nbytes)`, by shard
    index: `ops/rs_ref` parity, `ops/highwayhash_py` digests, a
    [digest][shard] frame a block."""
    body = _body(nbytes)
    files = [b""] * N
    for at in range(0, len(body), BLOCK):
        shards = rs_ref.encode(rs_ref.split(body[at:at + BLOCK], K), M)
        for i, row in enumerate(shards):
            h = highwayhash_py.HighwayHash(bitrot_mod.MAGIC_HIGHWAYHASH_KEY)
            h.update(row.tobytes())
            files[i] += h.digest256() + row.tobytes()
    return tuple(files)


def _shard_of_drive(key: str) -> list[int]:
    from minio_tpu.storage.datatypes import hash_order
    return [s - 1 for s in hash_order(f"b/{key}", N)]


# ---------------------------------------------------------------------------
# the rungs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("full, want", [
    (349526, (16384, 65536, 262144, 349526)),     # 12+4, 4 MiB
    (524288, (16384, 65536, 262144, 524288)),     # 8+8, 4 MiB
    (S, (16384, 65536, S)),
    (16384, (16384,)),                            # no rung below it
    (5462, (5462,)),
])
def test_s_rungs_are_the_tile_times_powers_of_four(full, want):
    rungs = ladder.s_rungs(full)
    assert rungs == want
    for r in rungs[:-1]:
        assert r % rs_pallas._TS == 0 and r % 32 == 0
        assert (r // rs_pallas._TS) & (r // rs_pallas._TS - 1) == 0
    for s_t in (1, 31, 16383, 16384, 16385, 65537, full - 1, full):
        if s_t > full:
            continue
        r = ladder.s_rung(full, s_t)
        assert r >= s_t and r in rungs
        assert all(x < s_t for x in rungs if x < r)


def test_a_subblock_launch_pads_to_a_power_of_two():
    cap = 32
    assert ladder.subblock_rungs(cap) == (1, 2, 4, 8, 16, 32)
    for b in range(1, cap + 1):
        r = ladder.rung("encode", b, cap, subblock=True)
        assert b <= r < 2 * b or r == b == 1


# ---------------------------------------------------------------------------
# PUT -> drive frames -> GET, every size class, both loops, both routes
# ---------------------------------------------------------------------------

@pytest.fixture()
def device_route(monkeypatch):
    """XLA-CPU stands in for the chip; the crossover sits at the
    smallest S rung, so one rung goes to the host and two to the
    device."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    monkeypatch.setattr(codec_mod, "SUBBLOCK_HOST_MAX_S", 16384)


@pytest.mark.parametrize("nbytes", SIZES, ids=str)
@pytest.mark.parametrize("route", ["host", "device"])
@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_every_size_of_the_mix_matches_the_plain_reference(
        tmp_path, monkeypatch, request, loop, route, nbytes):
    """Every drive's part file is the reference's byte for byte, a GET
    returns the body, and an object under one block went as ONE block
    at its S rung: on the host at the host's rungs, in a launch of its
    own rung on the device's."""
    monkeypatch.setattr(pl, "ENABLED", loop == "pipelined")
    sched = None
    if route == "device":
        request.getfixturevalue("device_route")
        sched = BatchScheduler(max_wait=0.001)
    body = _body(nbytes)
    try:
        eng = _engine(tmp_path, sched)
        with telemetry.trace("test.put") as root:
            eng.put_object("b", "obj", io.BytesIO(body),
                           size=-1 if loop == "pipelined" else nbytes)
        st = sched.stats()["verbs"]["encode"] if sched else None
        _oi, it = eng.get_object("b", "obj")
        assert b"".join(it) == body
    finally:
        if sched is not None:
            sched.close()
    want = _part_files(nbytes)
    for j, shard in enumerate(_shard_of_drive("obj")):
        (path,) = glob.glob(str(tmp_path / f"d{j}" / "b" / "obj" / "*"
                                / "part.1"))
        with open(path, "rb") as f:
            assert f.read() == want[shard], (j, shard)
    encodes = [sp for sp in root.walk() if sp.name == "pipeline.encode"]
    s_t = -(-nbytes // K)
    if nbytes >= BLOCK:
        assert all("subblock" not in sp.attrs for sp in encodes)
        if st is not None:
            assert (st["subblock_blocks"], st["batches"], st["blocks"],
                    st["short_blocks"]) == (0, 1, 2, 1)
        return
    rung = ladder.s_rung(S, s_t)
    (enc,) = encodes
    assert (enc.attrs["subblock"], enc.attrs["S"]) == (1, rung)
    if st is None:
        return
    on_device = rung > 16384
    assert st["subblock_blocks"] == 1 and st["groups"] == 1
    assert (st["subblock_device_blocks"], st["subblock_launches"],
            st["batches"], st["cpu_routed"]) \
        == ((1, 1, 1, 0) if on_device else (0, 0, 0, 1))
    # a launch at its rung: its zeros are the columns past S_t alone
    assert (st["short_blocks"], st["short_shard_bytes"], st["pad_bytes"],
            st["uploaded_bytes"]) == ((1, s_t, K * (rung - s_t), K * rung)
                                      if on_device else (0, 0, 0, 0))


# ---------------------------------------------------------------------------
# the former: buckets, rungs and the route
# ---------------------------------------------------------------------------

@pytest.fixture()
def launches(monkeypatch):
    """Every launch the former makes: (S, rows, blocks, subblock,
    lengths) as `Codec.encode_and_hash_batch` is entered."""
    seen: list = []
    lock = threading.Lock()
    real = Codec.encode_and_hash_batch

    def spy(self, data, algo, lengths=None, subblock=False, **kw):
        with lock:
            seen.append((data.shape[2], data.shape[0], kw.get("blocks"),
                         subblock, None if lengths is None
                         else list(map(int, lengths))))
        return real(self, data, algo, lengths=lengths, subblock=subblock,
                    **kw)
    monkeypatch.setattr(Codec, "encode_and_hash_batch", spy)
    return seen


def _subblock(seed: int, s_t: int):
    rng = np.random.default_rng(seed)
    rung = ladder.s_rung(S, s_t)
    data = np.zeros((1, K, rung), np.uint8)
    data[0, :, :s_t] = rng.integers(0, 256, (K, s_t), dtype=np.uint8)
    return data, np.array([s_t], np.int32)


def _check(data, lengths, out) -> None:
    parity, digests = out
    n = int(lengths[0])
    rows = rs_ref.encode(np.ascontiguousarray(data[0, :, :n]), M)
    assert np.array_equal(parity[0, :, :n], rows[K:])
    assert np.array_equal(digests[0], bitrot_mod.hash_shards_batch(rows, HH))


def test_subblocks_coalesce_at_their_rung_and_never_with_full_s(
        device_route, launches):
    """Submitted in one grace window: three objects at the 65536 rung,
    one at the top rung (the full S), and an object of 2.5 blocks whose
    short last block rides its group at the full S. The three fuse
    into ONE launch at 65536 on the subblock ladder (B 4); the top-rung
    object and the 2.5-block group, both at the full S, launch apart."""
    codec = Codec(K, M, BLOCK)
    sched = BatchScheduler(max_wait=0.5)
    try:
        subs = [_subblock(i, s_t) for i, s_t in
                enumerate((20000, 40000, 65536, 100000))]
        rng = np.random.default_rng(9)
        whole = rng.integers(0, 256, (3, K, S), dtype=np.uint8)
        whole[2, :, S // 2:] = 0
        wl = np.array([S, S, S // 2], np.int32)
        futs = [sched.submit(codec, d, HH, lengths=n, subblock=True)
                for d, n in subs]
        wfut = sched.submit(codec, whole, HH, lengths=wl)
        outs = [f.result(120) for f in futs]
        wout = wfut.result(120)
        st = sched.stats()["verbs"]["encode"]
    finally:
        sched.close()
    for (d, n), out in zip(subs, outs):
        _check(d, n, out)
    assert wout[0].shape == (3, M, S)
    by_s = sorted(launches)
    assert by_s == [
        (65536, 4, 3, True, [20000, 40000, 65536]),
        (S, 1, 1, True, [100000]),
        (S, 4, 3, False, [S, S, S // 2])]
    assert (st["subblock_blocks"], st["subblock_device_blocks"],
            st["subblock_launches"], st["batches"]) == (4, 4, 2, 3)


@pytest.mark.parametrize("count", [1, 5])
@pytest.mark.parametrize("s_t", [9000, 50000], ids=["host-rung",
                                                    "device-rung"])
def test_the_route_is_the_rungs_whatever_coalesced(device_route, launches,
                                                   count, s_t):
    """One object or five in the same grace window: at the host's rung
    every future is resolved (None) at submit, no launch; at the
    device's rung they all ride one launch."""
    codec = Codec(K, M, BLOCK)
    sched = BatchScheduler(max_wait=0.2)
    try:
        subs = [_subblock(i, s_t) for i in range(count)]
        futs = [sched.submit(codec, d, HH, lengths=n, subblock=True)
                for d, n in subs]
        host = ladder.s_rung(S, s_t) <= 16384
        if host:
            assert all(f.done() and f.result() is None for f in futs)
        outs = [f.result(120) for f in futs]
        st = sched.stats()["verbs"]["encode"]
    finally:
        sched.close()
    if host:
        assert launches == [] and st["cpu_routed"] == count
        assert st["subblock_device_blocks"] == 0
        return
    for (d, n), out in zip(subs, outs):
        _check(d, n, out)
    assert [(s, b) for s, _r, b, _sub, _n in launches] == [(65536, count)]
    assert st["subblock_device_blocks"] == count
    assert st["cpu_routed"] == 0


def test_the_crossover_is_measured_at_the_full_s():
    """The constant the route reads: no S rung below 12+4's full S goes
    to the device, the full S does (PERF.md, the crossover table)."""
    full = 349526
    assert [codec_mod.subblock_on_device(r) for r in ladder.s_rungs(full)] \
        == [False, False, False, True]
