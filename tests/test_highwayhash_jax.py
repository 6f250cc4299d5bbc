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
    from minio_tpu.models.pipeline import host_rows, put_step
    k, m = 4, 2
    s = 1031  # odd length exercises the remainder path
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (2, k, s), dtype=np.uint8)
    parity, digests = put_step(data, k, m)
    # parity crosses as 32-bit words (1031 -> 258 of them a row)
    assert parity.dtype == np.uint32 and parity.shape == (2, m, 258)
    parity, digests = host_rows(np.asarray(parity), s), np.asarray(digests)
    assert parity.shape == (2, m, s) and parity.dtype == np.uint8
    assert digests.shape == (2, k + m, 32)
    for b in range(2):
        want = rs_ref.encode(data[b], m)
        assert (parity[b] == want[k:]).all()
        for row in range(k + m):
            assert digests[b, row].tobytes() == _want(want[row].tobytes())


def test_put_step_padded_shard_len():
    """Zero-padded columns must not change the digests of the true
    shard_len prefix (the engine pads S up for kernel alignment)."""
    from minio_tpu.models.pipeline import host_rows, put_step
    k, m = 4, 2
    s, pad = 500, 140
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    padded = np.pad(data, ((0, 0), (0, 0), (0, pad)))
    par_p, dg_p = put_step(padded, k, m, s)
    par, dg = put_step(data, k, m)
    assert (host_rows(np.asarray(par_p), s)
            == host_rows(np.asarray(par), s)).all()
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


# ---------------------------------------------------------------------------
# the row lengths as an OPERAND (hh256_batch_ragged): one program a
# padded width, rows hashed over their own first `length` bytes
# ---------------------------------------------------------------------------

_RAGGED_WIDTH = 203      # 6 whole packets + 11: scan, leftover, remainder


def _ragged(data: np.ndarray, lengths) -> np.ndarray:
    from minio_tpu.ops.highwayhash_jax import hh256_batch_ragged
    got = np.asarray(hh256_batch_ragged(KEY, data, np.asarray(lengths)))
    assert got.shape == (data.shape[0], 32)
    return got


@pytest.mark.parametrize("rem", range(32))
def test_hh256_ragged_every_remainder_equals_the_host_hash(rem):
    """Rows of ONE padded array whose lengths leave remainder `rem`
    after 0, 1, 3 and 5 whole packets: each digest is the host hash of
    the row's own bytes, whatever lies beyond them in the array."""
    rng = np.random.default_rng(rem)
    lengths = [rem, 32 + rem, 96 + rem, 160 + rem]
    data = rng.integers(0, 256, (len(lengths), _RAGGED_WIDTH),
                        dtype=np.uint8)
    got = _ragged(data, lengths)
    for i, n in enumerate(lengths):
        assert got[i].tobytes() == _want(data[i, :n].tobytes()), (i, n)


@pytest.mark.parametrize("width", [1, 7, 31, 32, 33, 64, 100, 129, 1000])
def test_hh256_ragged_at_full_length_is_the_static_program(width):
    """lengths == L on every row: the static program's digests, bit for
    bit — arrays narrower than a packet included."""
    rng = np.random.default_rng(width)
    data = rng.integers(0, 256, (5, width), dtype=np.uint8)
    got = _ragged(data, [width] * 5)
    assert np.array_equal(got, np.asarray(hh256_batch(KEY, data)))


@pytest.mark.parametrize("rows", [1, 3, 4, 16, 33])
def test_hh256_ragged_mixed_batch(rows):
    """A batch that mixes empty rows, rows under a packet, rows that
    end on a packet, whole rows and everything between, at row counts
    on and off the sublane groups."""
    rng = np.random.default_rng(rows)
    width = 349
    pool = [0, 1, 5, 31, 32, 33, 64, 174, 175, 320, 348, 349]
    lengths = [pool[int(i)] for i in rng.integers(0, len(pool), rows)]
    lengths[-1] = width
    data = rng.integers(0, 256, (rows, width), dtype=np.uint8)
    got = _ragged(data, lengths)
    for i, n in enumerate(lengths):
        assert got[i].tobytes() == _want(data[i, :n].tobytes()), (i, n)


def test_hh256_ragged_is_one_program_a_width():
    """The lengths are an operand: new lengths at the same (N, L) build
    nothing."""
    from minio_tpu.ops.highwayhash_jax import _hh256_ragged_impl
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (4, 77), dtype=np.uint8)
    _ragged(data, [77, 1, 40, 0])
    before = _hh256_ragged_impl._cache_size()
    _ragged(data, [3, 76, 32, 64])
    assert _hh256_ragged_impl._cache_size() == before


def test_remainder_table_is_the_scalar_algorithms_packet(monkeypatch):
    """The 32 x 32 source-index table against the remainder packet the
    scalar implementation (pinned to the published vectors) builds."""
    from minio_tpu.ops.highwayhash_jax import _REMAINDER_SRC
    packets = []
    monkeypatch.setattr(HighwayHash, "_update_packet",
                        lambda self, p: packets.append(bytes(p)))
    assert (_REMAINDER_SRC[0] == -1).all()
    for n in range(1, 32):
        tail = bytes(range(1, n + 1))           # byte i holds i + 1
        HighwayHash(KEY)._update_remainder(tail)
        assert bytes(tail[at] if at >= 0 else 0
                     for at in _REMAINDER_SRC[n]) == packets[-1], n
