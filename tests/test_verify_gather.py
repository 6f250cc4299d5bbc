"""A degraded GET's verify+decode step without group-sized copies: the
decode launch's survivors are gathered once into a buffer of the
geometry's staging ring, and every shard no launch covered is hashed
where the read left it (bitrot.hash_rows).

These pin the in-place hash to the stacked one, the bodies of degraded
GETs at the benchmark geometries (12+4, 8+8) through the CPU-backed
batch former, bitrot caught on both verify routes, and the ring
buffer's lifetime: back only when nothing reads it, never after a
failed launch.
"""

import os
import threading

import numpy as np
import pytest

from minio_tpu import bitrot as bitrot_mod
from minio_tpu.object import codec as codec_mod
from minio_tpu.object import engine as engine_mod
from minio_tpu.parallel import pipeline as pl
from minio_tpu.parallel.bpool import BytePool
from minio_tpu.parallel.scheduler import BatchScheduler

HH = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S
ALGOS = [bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S,
         bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256,
         bitrot_mod.BitrotAlgorithm.SHA256,
         bitrot_mod.BitrotAlgorithm.BLAKE2B512]


# ---------------------------------------------------------------------------
# (a) bitrot.hash_rows == hash_shards_batch
# ---------------------------------------------------------------------------

def _rows(n, length, seed):
    """n rows, each its own read-only bytes-backed view (as the shard
    reader hands them on)."""
    rng = np.random.default_rng(seed)
    return [np.frombuffer(rng.integers(0, 256, length, dtype=np.uint8)
                          .tobytes(), dtype=np.uint8) for _ in range(n)]


@pytest.mark.parametrize("algo", ALGOS, ids=lambda a: a.name)
@pytest.mark.parametrize("n,length", [(12, 5462), (8, 8192), (1, 4096),
                                      (5, 777), (3, 31), (2, 0)],
                         ids=["12x5462", "8x8192", "n1", "odd-short",
                              "under-a-packet", "empty"])
def test_hash_rows_equals_hash_shards_batch(algo, n, length):
    rows = _rows(n, length, seed=n * 1000 + length)
    assert all(not r.flags.writeable for r in rows)
    got = bitrot_mod.hash_rows(rows, algo)
    want = bitrot_mod.hash_shards_batch(np.stack(rows), algo)
    assert got.shape == (n, algo.digest_size) and got.dtype == np.uint8
    assert np.array_equal(got, want)


def test_hash_rows_takes_strided_and_sliced_rows():
    """A row that is not contiguous is hashed as its bytes are, like the
    stacked call does."""
    base = np.random.default_rng(3).integers(0, 256, (4, 2000),
                                             dtype=np.uint8)
    rows = [base[0, ::2], base[1, :1000], base[2, 1000:], base[3, 1::2]]
    got = bitrot_mod.hash_rows(rows, HH)
    want = bitrot_mod.hash_shards_batch(np.stack(rows), HH)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,length", [(96, 5462), (1, 4096), (5, 777),
                                      (2, 0)],
                         ids=["12x8-blocks", "n1", "odd-short", "empty"])
def test_gather_rows_copies_each_row_once(n, length):
    """The decode launch's input: every survivor frame in its row."""
    rows = _rows(n, length, seed=n + length)
    out = np.full(n * length, 0xEE, np.uint8)
    engine_mod._gather_rows(rows, out)
    assert np.array_equal(out.reshape(n, length), np.stack(rows))


def test_hh256_rows_rejects_unequal_rows():
    from minio_tpu.utils import native
    if not native.rows_available():
        pytest.skip("native library not built")
    with pytest.raises(ValueError):
        native.hh256_rows(bitrot_mod.MAGIC_HIGHWAYHASH_KEY,
                          [np.zeros(4, np.uint8), np.zeros(5, np.uint8)])


# ---------------------------------------------------------------------------
# engine fixtures
# ---------------------------------------------------------------------------

GEOMS = [(12, 4), (8, 8)]


@pytest.fixture()
def dev_routed(monkeypatch):
    """Every batch routes to the 'device' (XLA-CPU here), so GETs defer
    their digests and the decode buckets ride the fused launch."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)


def _engine(tmp_path, k, m):
    from tests.test_engine import make_engine
    e = make_engine(tmp_path, n=k + m, k=k, m=m)
    e.make_bucket("bucket")
    return e


def _payload(size, seed):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _shard_file(tmp_path, eng, name, shard):
    """The part file of shard index `shard` (0-based)."""
    fi = eng._read_one("bucket", name)
    drive = fi.erasure.distribution.index(shard + 1)
    return os.path.join(str(tmp_path), f"d{drive}", "bucket", name,
                        fi.data_dir, "part.1")


def _flip(path, at):
    raw = bytearray(open(path, "rb").read())
    raw[at] ^= 0x5A
    open(path, "wb").write(bytes(raw))


def _ring(monkeypatch, eng, k, m, capacity):
    """A fresh staging ring of `capacity` buffers for the geometry's GET
    width, in place of whatever the registry held."""
    width = engine_mod.GET_BATCH_BLOCKS * k * eng.codec(k, m).shard_size
    ring = BytePool(width, capacity)
    monkeypatch.setitem(pl._pools, width, ring)
    return ring


def _gathers():
    return {b: engine_mod._GET_GATHERS.value(buffer=b)
            for b in ("pooled", "fresh")}


@pytest.fixture()
def sched():
    s = BatchScheduler(max_batch=64, max_wait=0.02)
    yield s
    s.close()


def _get(eng, name="obj"):
    _oi, it = eng.get_object("bucket", name)
    return b"".join(it)


# ---------------------------------------------------------------------------
# (b) degraded GETs at 12+4 and 8+8 return exact bodies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,m", GEOMS, ids=["ec12p4", "ec8p8"])
@pytest.mark.parametrize("lost", ["parity", "data"])
def test_degraded_get_body_exact(dev_routed, monkeypatch, tmp_path, sched,
                                 k, m, lost):
    from tests.test_engine import BLOCK
    eng = _engine(tmp_path, k, m)
    eng.scheduler = sched
    ring = _ring(monkeypatch, eng, k, m, capacity=4)
    data = _payload(3 * BLOCK + 4321, seed=k * 10 + len(lost))
    eng.put_object("bucket", "obj", data)
    os.remove(_shard_file(tmp_path, eng, "obj",
                          k if lost == "parity" else 1))

    calls = {"rows": 0, "stacked": 0}
    hash_rows, stacked = bitrot_mod.hash_rows, bitrot_mod.hash_shards_batch

    def rows_spy(rows, algo=HH):
        calls["rows"] += 1
        return hash_rows(rows, algo)

    def stacked_spy(shards, algo=HH):
        calls["stacked"] += 1
        return stacked(shards, algo)
    monkeypatch.setattr(bitrot_mod, "hash_rows", rows_spy)
    monkeypatch.setattr(bitrot_mod, "hash_shards_batch", stacked_spy)
    before = _gathers()
    eng.on_degraded_read = lambda b, o: None

    assert _get(eng) == data
    after = _gathers()
    assert calls["stacked"] == 0            # the GET stacks nothing
    if lost == "parity":
        # every data shard present: no launch, all hashed in place
        assert calls["rows"] >= 1
        assert after == before
    else:
        # two buckets (whole blocks, the short last one), each gathered
        # into the ring, verified by the fused launch's digests
        assert after["pooled"] - before["pooled"] == 2
        assert after["fresh"] == before["fresh"]
        assert sched.stats()["verbs"]["decode"]["batches"] >= 1
        assert ring._q.qsize() == 2         # both buffers came back


# ---------------------------------------------------------------------------
# (c) bitrot is caught on both routes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,m", GEOMS, ids=["ec12p4", "ec8p8"])
@pytest.mark.parametrize("lost", ["parity", "data"])
def test_flipped_byte_caught(dev_routed, monkeypatch, tmp_path, sched,
                             k, m, lost):
    """A byte flipped in data shard 2's first frame: in a parity-only
    object the in-place host verify catches it, in an object that lost
    a data shard the fused launch's digests do. Either way the shard is
    dropped, the block re-read, the body right and heal flagged."""
    from tests.test_engine import BLOCK
    eng = _engine(tmp_path, k, m)
    eng.scheduler = sched
    _ring(monkeypatch, eng, k, m, capacity=4)
    data = _payload(2 * BLOCK, seed=k + 7)
    eng.put_object("bucket", "obj", data)
    os.remove(_shard_file(tmp_path, eng, "obj",
                          k if lost == "parity" else 0))
    _flip(_shard_file(tmp_path, eng, "obj", 2), 32 + 11)

    seen = {"host_bad": 0, "fused": 0}
    hash_rows = bitrot_mod.hash_rows

    def rows_spy(rows, algo=HH):
        got = hash_rows(rows, algo)
        want = bitrot_mod.hash_shards_batch(np.stack(rows), algo)
        seen["host_bad"] += int(not np.array_equal(got, want))
        return got
    monkeypatch.setattr(bitrot_mod, "hash_rows", rows_spy)
    fused = codec_mod.Codec.verify_and_decode_batch

    def fused_spy(self, *a, **kw):
        seen["fused"] += 1
        return fused(self, *a, **kw)
    monkeypatch.setattr(codec_mod.Codec, "verify_and_decode_batch",
                        fused_spy)
    flagged = []
    eng.on_degraded_read = lambda b, o: flagged.append(o)

    assert _get(eng) == data
    assert flagged == ["obj"]
    assert seen["host_bad"] == 0            # in place == stacked
    if lost == "parity":
        assert seen["fused"] == 0
    else:
        assert seen["fused"] >= 1


# ---------------------------------------------------------------------------
# (d) the ring: capacity 1 under two GETs, rows never alias it, a failed
#     launch never gives its buffer back
# ---------------------------------------------------------------------------

def test_two_gets_share_a_ring_of_one(dev_routed, monkeypatch, tmp_path,
                                      sched):
    from tests.test_engine import BLOCK
    k, m = 12, 4
    eng = _engine(tmp_path, k, m)
    eng.scheduler = sched
    ring = _ring(monkeypatch, eng, k, m, capacity=1)
    data = _payload(3 * BLOCK, seed=41)        # one bucket a GET
    eng.put_object("bucket", "obj", data)
    os.remove(_shard_file(tmp_path, eng, "obj", 0))
    eng.on_degraded_read = lambda b, o: None

    # both GETs have gathered before either submits: the second found
    # the ring empty while the first held its one buffer
    both = threading.Barrier(2, timeout=60)
    submit = sched.submit_decode

    def submit_after_both(*a, **kw):
        both.wait()
        return submit(*a, **kw)
    monkeypatch.setattr(sched, "submit_decode", submit_after_both)
    # a buffer goes back scribbled over, as the next GET would: a
    # rebuilt row that aliased it would corrupt the body
    put = ring.put

    def put_scribbled(buf):
        np.frombuffer(buf, dtype=np.uint8)[:] = 0xAA
        put(buf)
    monkeypatch.setattr(ring, "put", put_scribbled)
    before = _gathers()
    outs = [None, None]

    def read(i):
        outs[i] = _get(eng)
    threads = [threading.Thread(target=read, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    after = _gathers()
    assert outs == [data, data]
    assert after["fresh"] - before["fresh"] == 1
    assert after["pooled"] - before["pooled"] == 1
    assert ring._q.qsize() == 1             # the pooled one came back


def test_failed_launch_keeps_its_buffer(dev_routed, monkeypatch, tmp_path,
                                        sched):
    from tests.test_engine import BLOCK
    k, m = 8, 8
    eng = _engine(tmp_path, k, m)
    eng.scheduler = sched
    ring = _ring(monkeypatch, eng, k, m, capacity=2)
    data = _payload(2 * BLOCK, seed=43)
    eng.put_object("bucket", "obj", data)
    os.remove(_shard_file(tmp_path, eng, "obj", 3))
    eng.on_degraded_read = lambda b, o: None

    def boom(self, *a, **kw):
        raise RuntimeError("launch failed")
    monkeypatch.setattr(codec_mod.Codec, "verify_and_decode_batch", boom)
    returned = []
    put = ring.put
    monkeypatch.setattr(ring, "put",
                        lambda buf: (returned.append(buf), put(buf)))
    before = _gathers()

    assert _get(eng) == data                 # host decode + host verify
    assert _gathers()["pooled"] - before["pooled"] == 1
    assert returned == []                    # never back to the ring
    assert ring._q.qsize() == 0
    # ... and the ring may allocate one in its place
    assert ring.try_get() is not None and ring.try_get() is not None
