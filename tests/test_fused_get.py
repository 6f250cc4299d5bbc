"""Fused verify+decode / verify+recover device programs and their engine
wiring (VERDICT r2 item 1).

The reference treats bitrot verification as inseparable from decode
(streamingBitrotReader.ReadAt inside Erasure.Decode,
cmd/bitrot-streaming.go:111-150 + cmd/erasure-decode.go:211); these tests
pin the device-fused forms (models/pipeline.get_step / heal_step) to the
host oracles and drive the engine's deferred-verify GET/heal paths end to
end, including bitrot injected after the deferral decision.
"""

import numpy as np
import pytest

from minio_tpu import bitrot as bitrot_mod
from minio_tpu.models import pipeline
from minio_tpu.object import codec as codec_mod
from minio_tpu.object.codec import Codec
from minio_tpu.ops import gf256, rs_matrix, rs_ref, rs_tpu

HH = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S


def make_batch(seed, b, k, s):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (b, k, s), dtype=np.int64).astype(np.uint8)


def encode_full(data_b, k, m):
    return np.stack([rs_ref.encode(blk, m) for blk in data_b])


# ---------------------------------------------------------------------------
# matrix + kernel identity
# ---------------------------------------------------------------------------

def test_missing_data_matrix_oracle():
    k, m = 4, 2
    data = make_batch(0, 1, k, 64)[0]
    full = rs_ref.encode(data, m)
    for lost in [(0,), (1, 3), (0, 4), (2, 5)]:
        mask = sum(1 << i for i in range(k + m) if i not in lost)
        dm, used, missing = rs_matrix.missing_data_matrix(k, m, mask)
        assert missing == tuple(i for i in lost if i < k)
        if not missing:
            assert dm.shape[0] == 0
            continue
        surv = np.stack([full[u] for u in used])
        got = gf256.gf_matmul(np.asarray(dm, np.uint8), surv)
        want = np.stack([full[i] for i in missing])
        assert (got == want).all()


def test_get_step_reconstructs_and_digests():
    k, m, s, b = 4, 2, 256, 3
    data = make_batch(1, b, k, s)
    full = encode_full(data, k, m)
    lost = (1, 4)
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    dm, used, missing = rs_matrix.missing_data_matrix(k, m, mask)
    surv = np.stack([full[:, u] for u in used], axis=1)  # (B, k, S)
    m2 = rs_tpu._bit_expand_cached(dm.tobytes(), dm.shape)
    out, digests = pipeline.get_step(surv, m2, dm.shape[0], k, s)
    # the rows cross in the link form; host_rows is the (B, r, S) view
    out = pipeline.host_rows(np.asarray(out), s)
    digests = np.asarray(digests)
    assert out.shape == (b, len(missing), s) and out.dtype == np.uint8
    # reconstructed rows byte-identical
    for r, mi in enumerate(missing):
        assert (out[:, r] == full[:, mi]).all()
    # survivor digests match the streaming-bitrot host hash
    for bi in range(b):
        for col, u in enumerate(used):
            want = bitrot_mod.hash_shard(full[bi, u].tobytes(), HH)
            assert digests[bi, col].tobytes() == want


def test_get_step_short_shard_len():
    """Digests must cover only the true payload prefix (last block of a
    part is shorter than the padded column width)."""
    k, m, s, slen = 4, 2, 128, 77
    data = make_batch(2, 2, k, s)
    data[:, :, slen:] = 0
    full = encode_full(data, k, m)
    mask = sum(1 << i for i in range(k + m) if i != 0)
    dm, used, missing = rs_matrix.missing_data_matrix(k, m, mask)
    surv = np.stack([full[:, u] for u in used], axis=1)
    m2 = rs_tpu._bit_expand_cached(dm.tobytes(), dm.shape)
    _out, digests = pipeline.get_step(surv, m2, dm.shape[0], k, slen)
    want = bitrot_mod.hash_shard(full[0, used[0]][:slen].tobytes(), HH)
    assert np.asarray(digests)[0, 0].tobytes() == want


def test_heal_step_recovers_and_digests_outputs():
    k, m, s, b = 4, 2, 256, 2
    data = make_batch(3, b, k, s)
    full = encode_full(data, k, m)
    lost = (0, 5)  # one data + one parity
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    rec, used, missing = rs_matrix.recover_matrix(k, m, mask)
    rec = np.ascontiguousarray(np.asarray(rec, np.uint8))
    surv = np.stack([full[:, u] for u in used], axis=1)
    m2 = rs_tpu._bit_expand_cached(rec.tobytes(), rec.shape)
    out, sdig, odig = pipeline.heal_step(surv, m2, rec.shape[0], k, s)
    out = pipeline.host_rows(np.asarray(out), s)
    sdig, odig = np.asarray(sdig), np.asarray(odig)
    for r, mi in enumerate(missing):
        assert (out[:, r] == full[:, mi]).all()
        for bi in range(b):
            want = bitrot_mod.hash_shard(full[bi, mi].tobytes(), HH)
            assert odig[bi, r].tobytes() == want
    for bi in range(b):
        for col, u in enumerate(used):
            want = bitrot_mod.hash_shard(full[bi, u].tobytes(), HH)
            assert sdig[bi, col].tobytes() == want


def test_codec_fused_wrappers_route_and_match():
    k, m, s = 4, 2, 192
    codec = Codec(k, m, k * s)
    data = make_batch(4, 3, k, s)
    full = encode_full(data, k, m)
    lost = (2, 4)
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    _dm, used, missing = rs_matrix.missing_data_matrix(k, m, mask)
    surv = np.stack([full[:, u] for u in used], axis=1)

    # not device-routed -> None (CPU host path takes over)
    assert codec.verify_and_decode_batch(surv, mask, s, HH) is None

    got = codec.verify_and_decode_batch(surv, mask, s, HH, force="device")
    assert got is not None
    out, missing_idx, sdig = got
    assert tuple(missing_idx) == missing
    assert (out[:, 0] == full[:, missing[0]]).all()

    got2 = codec.verify_and_recover_batch(
        surv, mask, set(lost), s, HH, force="device")
    assert got2 is not None
    out2, idxs2, _sdig2, odig2 = got2
    assert tuple(idxs2) == tuple(sorted(lost))
    for r, mi in enumerate(idxs2):
        assert (out2[:, r] == full[:, mi]).all()
        want = bitrot_mod.hash_shard(full[0, mi].tobytes(), HH)
        assert odig2[0, r].tobytes() == want


# ---------------------------------------------------------------------------
# engine wiring: deferred verify through GET / heal
# ---------------------------------------------------------------------------

@pytest.fixture()
def dev_routed(monkeypatch):
    """Route every batch to the 'device' (XLA-CPU in tests) so the
    engine's deferred-verify fused paths run."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)


def _engine(tmp_path):
    from tests.test_engine import make_engine
    e = make_engine(tmp_path)
    e.make_bucket("bucket")
    return e


def _payload(size, seed=11):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _shard_files(tmp_path, name):
    import glob
    import os
    return sorted(glob.glob(os.path.join(
        str(tmp_path), "d*", "bucket", name, "*", "part.1")))


def test_engine_get_fused_degraded(dev_routed, tmp_path):
    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(3 * BLOCK + 4321)
    eng.put_object("bucket", "obj", data)
    # kill two drives' shard files (k=4, m=2 tolerates 2)
    import os
    for f in _shard_files(tmp_path, "obj")[:2]:
        os.remove(f)
    _oi, it = eng.get_object("bucket", "obj")
    assert b"".join(it) == data


def test_engine_get_fused_detects_bitrot(dev_routed, tmp_path):
    """Corrupt one shard's payload: the deferred device verify must catch
    it, drop the shard, and still return correct bytes via hedged
    re-read + reconstruct."""
    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(2 * BLOCK + 99, seed=13)
    eng.put_object("bucket", "obj", data)
    # corrupt the drive holding DATA shard 0 (drive i holds shard
    # distribution[i]-1; a corrupted parity shard would never be read
    # on the healthy path)
    fi = eng._read_one("bucket", "obj")
    drive = fi.erasure.distribution.index(1)
    f = _shard_files(tmp_path, "obj")[drive]
    raw = bytearray(open(f, "rb").read())
    raw[40] ^= 0xFF  # inside the first frame's payload (digest is 0..31)
    open(f, "wb").write(bytes(raw))

    flagged = []
    eng.on_degraded_read = lambda b, o: flagged.append(o)
    _oi, it = eng.get_object("bucket", "obj")
    assert b"".join(it) == data
    assert "obj" in flagged  # bitrot must queue a heal


def test_engine_heal_fused_writes_identical_frames(dev_routed, tmp_path):
    """Fused heal (verify+recover+rehash on device) must write shard
    files byte-identical to the originals, including the streaming
    bitrot frame digests."""
    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(4 * BLOCK + 17, seed=17)
    eng.put_object("bucket", "obj", data)
    files = _shard_files(tmp_path, "obj")
    import os
    victims = files[1:3]
    originals = {f: open(f, "rb").read() for f in victims}
    for f in victims:
        os.remove(f)
        # drop xl.meta too so the drive reads as outdated
        os.remove(os.path.join(os.path.dirname(os.path.dirname(f)),
                               "xl.meta"))
    res = eng.heal_object("bucket", "obj")
    assert res.disks_healed == 2
    for f, want in originals.items():
        assert open(f, "rb").read() == want

    _oi, it = eng.get_object("bucket", "obj")
    assert b"".join(it) == data


def test_engine_heal_fused_survives_corrupt_survivor(dev_routed,
                                                     tmp_path):
    """A corrupt survivor during a fused heal must be detected by the
    deferred verify and healed around via the host rebuild path."""
    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(2 * BLOCK, seed=19)
    eng.put_object("bucket", "obj", data)
    files = _shard_files(tmp_path, "obj")
    import os
    victim = files[0]
    original = open(victim, "rb").read()
    os.remove(victim)
    os.remove(os.path.join(os.path.dirname(os.path.dirname(victim)),
                           "xl.meta"))
    # corrupt a different, healthy survivor
    f = files[3]
    raw = bytearray(open(f, "rb").read())
    raw[45] ^= 0x55
    open(f, "wb").write(bytes(raw))

    res = eng.heal_object("bucket", "obj")
    assert res.disks_healed == 1
    assert open(victim, "rb").read() == original


def test_engine_get_defer_uses_stored_algo(dev_routed, tmp_path):
    """Frames written under one bitrot algorithm must verify with THAT
    algorithm even after the server's configured algo changes (review
    r3: deferred verify compared against self.bitrot_algo)."""
    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(2 * BLOCK + 5, seed=23)
    eng.put_object("bucket", "obj", data)          # HH256S frames
    eng.bitrot_algo = bitrot_mod.BitrotAlgorithm.SHA256
    _oi, it = eng.get_object("bucket", "obj")
    assert b"".join(it) == data


def test_engine_heal_declined_bucket_still_verifies(dev_routed,
                                                    monkeypatch,
                                                    tmp_path):
    """A heal group whose fused device call declines (tail bucket below
    the device size gate) must still verify the deferred survivor
    digests — otherwise bitrot gets laundered into freshly-digested
    healed shards (review r3 finding 1)."""
    from minio_tpu.object import healing as healing_mod
    from tests.test_engine import BLOCK
    eng = _engine(tmp_path)
    data = _payload(5 * BLOCK, seed=29)            # 5 blocks: groups 4+1
    eng.put_object("bucket", "obj", data)
    fi = eng._read_one("bucket", "obj")
    dist = fi.erasure.distribution
    files = _shard_files(tmp_path, "obj")

    shard_size = fi.erasure.shard_size()
    # defer on (4-block group >= gate) but 1-block tail bucket declines
    gate = 3 * 4 * shard_size
    monkeypatch.setattr(healing_mod, "HEAL_BATCH_BLOCKS", 4)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", gate)

    import os
    victim = files[dist.index(6)]                  # drive w/ last shard
    original = open(victim, "rb").read()
    os.remove(victim)
    os.remove(os.path.join(os.path.dirname(os.path.dirname(victim)),
                           "xl.meta"))
    # corrupt survivor shard 0's LAST block frame (the tail bucket)
    f = files[dist.index(1)]
    raw = bytearray(open(f, "rb").read())
    frame = 32 + shard_size
    raw[4 * frame + 32 + 5] ^= 0x77
    open(f, "wb").write(bytes(raw))

    res = eng.heal_object("bucket", "obj")
    assert res.disks_healed == 1
    assert open(victim, "rb").read() == original   # no laundered bitrot


def test_engine_get_decode_rides_batch_former(dev_routed, tmp_path):
    """With a scheduler attached, degraded-GET decode buckets must go
    through the cross-request former (decode verb dispatches > 0) and
    still return byte-identical data; concurrent degraded GETs of one
    object coalesce their buckets."""
    import threading
    from minio_tpu.parallel.scheduler import BatchScheduler

    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(3 * BLOCK + 777, seed=31)
    eng.put_object("bucket", "obj", data)
    import os
    for f in _shard_files(tmp_path, "obj")[:2]:
        os.remove(f)
    sched = BatchScheduler(max_batch=64, max_wait=0.1)
    eng.scheduler = sched
    try:
        outs: list = [None] * 3

        def read(i):
            _oi, it = eng.get_object("bucket", "obj")
            outs[i] = b"".join(it)

        threads = [threading.Thread(target=read, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(o == data for o in outs)
        st = sched.stats()["verbs"]["decode"]
        assert st["batches"] >= 1         # decode rode the former
        assert st["coalesced"] >= 1       # concurrent GETs fused
    finally:
        eng.scheduler = None
        sched.close()


def test_engine_heal_recover_rides_batch_former(dev_routed, tmp_path):
    """Bulk heal reconstruction must route its fused
    verify+recover+rehash buckets through the former and write frames
    byte-identical to the originals."""
    from minio_tpu.parallel.scheduler import BatchScheduler

    eng = _engine(tmp_path)
    from tests.test_engine import BLOCK
    data = _payload(4 * BLOCK + 33, seed=37)
    eng.put_object("bucket", "obj", data)
    files = _shard_files(tmp_path, "obj")
    import os
    victim = files[2]
    original = open(victim, "rb").read()
    os.remove(victim)
    os.remove(os.path.join(os.path.dirname(os.path.dirname(victim)),
                           "xl.meta"))
    sched = BatchScheduler(max_batch=64, max_wait=0.05)
    eng.scheduler = sched
    try:
        res = eng.heal_object("bucket", "obj")
        assert res.disks_healed == 1
        assert open(victim, "rb").read() == original
        assert sched.stats()["verbs"]["recover"]["batches"] >= 1
    finally:
        eng.scheduler = None
        sched.close()
