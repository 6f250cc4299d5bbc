"""Bit-identity of the batched device HighwayHash against the scalar
implementation (itself pinned to published vectors in test_bitrot.py),
plus the fused put_step (encode + digests) against the host oracle."""

import numpy as np
import pytest

from minio_tpu import bitrot as bitrot_mod
from minio_tpu.bitrot import MAGIC_HIGHWAYHASH_KEY as KEY
from minio_tpu.ops import rs_ref
from minio_tpu.ops.highwayhash_jax import hh256_batch
from minio_tpu.ops.highwayhash_py import HighwayHash


def _want(data: bytes) -> bytes:
    h = HighwayHash(KEY)
    h.update(data)
    return h.digest256()


# every remainder branch: 0, <4, mod4 0..3, the >=16 branch, exact
# packets, multi-packet, scan + leftover (each length is a separate XLA
# compile — keep the list lean but branch-complete)
@pytest.mark.parametrize("length", [
    0, 1, 3, 15, 16, 18, 21, 31, 32, 33, 100, 129, 1000,
])
def test_hh256_batch_identity(length):
    rng = np.random.default_rng(length)
    n = 4
    data = rng.integers(0, 256, (n, max(length, 1)), dtype=np.uint8)
    data = data[:, :length]
    got = np.asarray(hh256_batch(KEY, data))
    assert got.shape == (n, 32)
    for i in range(n):
        assert got[i].tobytes() == _want(data[i].tobytes()), f"row {i}"


def test_hh256_batch_matches_bitrot_hasher():
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (3, 87382), dtype=np.uint8)
    got = np.asarray(hh256_batch(KEY, data))
    for i in range(3):
        want = bitrot_mod.hash_shard(
            data[i], bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256)
        assert got[i].tobytes() == want


def test_put_step_fused_oracle():
    from minio_tpu.models.pipeline import put_step
    k, m = 4, 2
    s = 1031  # odd length exercises the remainder path
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (2, k, s), dtype=np.uint8)
    parity, digests = put_step(data, k, m)
    parity, digests = np.asarray(parity), np.asarray(digests)
    assert parity.shape == (2, m, s)
    assert digests.shape == (2, k + m, 32)
    for b in range(2):
        want = rs_ref.encode(data[b], m)
        assert (parity[b] == want[k:]).all()
        for row in range(k + m):
            assert digests[b, row].tobytes() == _want(want[row].tobytes())


def test_put_step_padded_shard_len():
    """Zero-padded columns must not change the digests of the true
    shard_len prefix (the engine pads S up for kernel alignment)."""
    from minio_tpu.models.pipeline import put_step
    k, m = 4, 2
    s, pad = 500, 140
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    padded = np.pad(data, ((0, 0), (0, 0), (0, pad)))
    par_p, dg_p = put_step(padded, k, m, s)
    par, dg = put_step(data, k, m)
    assert (np.asarray(par_p)[..., :s] == np.asarray(par)).all()
    assert (np.asarray(dg_p) == np.asarray(dg)).all()


def test_codec_fused_matches_cpu_path():
    """The engine's fused route must produce the same bytes the CPU path
    writes (digests + shards)."""
    from minio_tpu.object.codec import Codec
    codec = Codec(4, 2, 8192)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (3, 4, 2048), dtype=np.uint8)
    out = codec.encode_and_hash_batch(
        data, bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S, force="device")
    assert out is not None
    parity, digests = out
    want_full = codec.encode_batch(data, force="numpy")
    # the device made parity only: the data rows stay the caller's
    assert parity.shape == (3, 2, 2048)
    assert (parity == want_full[:, 4:]).all()
    want_dg = bitrot_mod.hash_shards_batch(
        want_full.reshape(-1, 2048),
        bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S).reshape(3, 6, 32)
    assert (digests == want_dg).all()


def test_codec_fused_declines_unsupported_algo():
    from minio_tpu.object.codec import Codec
    codec = Codec(4, 2, 8192)
    data = np.zeros((1, 4, 64), dtype=np.uint8)
    assert codec.encode_and_hash_batch(
        data, bitrot_mod.BitrotAlgorithm.BLAKE2B512,
        force="device") is None


def test_codec_fused_sha256():
    import hashlib
    from minio_tpu.object.codec import Codec
    codec = Codec(4, 2, 8192)
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (2, 4, 1024), dtype=np.uint8)
    out = codec.encode_and_hash_batch(
        data, bitrot_mod.BitrotAlgorithm.SHA256, force="device")
    assert out is not None
    parity, digests = out
    want_full = codec.encode_batch(data, force="numpy")
    assert (parity == want_full[:, 4:]).all()
    for b in range(2):
        for r in range(6):
            assert digests[b, r].tobytes() == hashlib.sha256(
                want_full[b, r].tobytes()).digest()


def test_codec_decode_stacked_matches_numpy():
    from minio_tpu.object.codec import Codec
    from minio_tpu.ops import rs_matrix, rs_ref
    codec = Codec(4, 2, 4 * 512)
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, (3, 4, 512), dtype=np.uint8)
    full = np.stack([rs_ref.encode(d, 2) for d in data])  # (3, 6, 512)
    # lose shards 0 and 3: survivors 1,2,4,5
    mask = sum(1 << i for i in (1, 2, 4, 5))
    _, used = rs_matrix.decode_matrix(4, 2, mask)
    stacked = np.stack([full[b][list(used)] for b in range(3)])
    for force in ("numpy", "device"):
        out = codec.decode_stacked(stacked, mask, force=force)
        assert (out == data).all(), force
