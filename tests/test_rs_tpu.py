"""Identity tests: TPU/XLA RS kernels vs the numpy oracle.

Mirrors the reference's kernel-matrix test strategy (its
erasure-encode/decode test matrices over data x parity x size x missing
patterns), with the host oracle as ground truth.

Runs on the CPU backend (conftest forces JAX_PLATFORMS=cpu); the same code
path runs on real TPU where pallas kernels additionally activate.
"""

import numpy as np
import pytest

from minio_tpu.ops import rs_matrix, rs_ref, rs_tpu

CONFIGS = [(2, 2), (4, 2), (5, 3), (8, 4), (12, 4), (16, 4), (8, 8)]
SIZES = [1, 31, 128, 1000, 4096, 65536]


def _rand_shards(k, s, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (k, s)).astype(np.uint8)


class TestEncodeIdentity:
    @pytest.mark.parametrize("k,m", CONFIGS)
    def test_single_block(self, k, m):
        data = _rand_shards(k, 1000, k * 7 + m)
        ref = rs_ref.encode(data, m)
        out = np.asarray(rs_tpu.encode(data, k, m, use_pallas=False))
        assert (out == ref).all()

    @pytest.mark.parametrize("size", SIZES)
    def test_sizes_12_4(self, size):
        k, m = 12, 4
        data = _rand_shards(k, size, size)
        ref = rs_ref.encode(data, m)
        out = np.asarray(rs_tpu.encode(data, k, m, use_pallas=False))
        assert (out == ref).all()

    def test_batched(self):
        k, m, b, s = 12, 4, 8, 512
        rng = np.random.default_rng(42)
        data = rng.integers(0, 256, (b, k, s)).astype(np.uint8)
        out = np.asarray(rs_tpu.encode(data, k, m, use_pallas=False))
        for i in range(b):
            assert (out[i] == rs_ref.encode(data[i], m)).all()

    def test_zeros_and_ones(self):
        k, m = 4, 2
        for fill in (0, 1, 255):
            data = np.full((k, 64), fill, dtype=np.uint8)
            out = np.asarray(rs_tpu.encode(data, k, m, use_pallas=False))
            assert (out == rs_ref.encode(data, m)).all()


class TestReconstructIdentity:
    @pytest.mark.parametrize("k,m", [(4, 2), (12, 4), (8, 8)])
    def test_reconstruct_data(self, k, m):
        n = k + m
        data = _rand_shards(k, 777, 5)
        full = rs_ref.encode(data, m)
        rng = np.random.default_rng(6)
        for _ in range(8):
            missing = set(int(i) for i in rng.choice(n, m, replace=False))
            mask = sum(1 << i for i in range(n) if i not in missing)
            _, used = rs_matrix.decode_matrix(k, m, mask)
            stack = full[list(used)]
            out = np.asarray(rs_tpu.reconstruct_data(
                stack, mask, k, m, use_pallas=False))
            assert (out == data).all(), sorted(missing)

    def test_recover_missing(self):
        k, m = 12, 4
        n = k + m
        data = _rand_shards(k, 300, 9)
        full = rs_ref.encode(data, m)
        # drop 2 data + 2 parity
        missing = [3, 7, 13, 15]
        mask = sum(1 << i for i in range(n) if i not in missing)
        r, used, miss = rs_matrix.recover_matrix(k, m, mask)
        assert list(miss) == missing
        stack = full[list(used)]
        out = np.asarray(rs_tpu.recover_missing(
            stack, mask, k, m, use_pallas=False))
        assert out.shape == (len(missing), 300)
        for row, idx in enumerate(missing):
            assert (out[row] == full[idx]).all()


class TestPallasInterpret:
    """The Pallas GF kernel against the host oracle under the TPU
    interpreter (pltpu.force_tpu_interpret_mode): the same kernel body
    Mosaic compiles on the chip, run on the CPU backend — so the kernel
    has tier-1 coverage at every served geometry, at r = 1, 2, 3
    recover shapes, and at S that is not a multiple of the lane tile."""

    @staticmethod
    def _apply(matrix, data):
        from jax.experimental.pallas import tpu as pltpu
        from minio_tpu.ops import rs_pallas
        with pltpu.force_tpu_interpret_mode():
            return np.asarray(rs_pallas.gf_matmul_pallas(
                np.asarray(matrix, np.uint8), data))

    @pytest.mark.parametrize("k,m", [(12, 4), (8, 8), (16, 4)])
    def test_encode_matches_oracle(self, k, m):
        from minio_tpu.ops import rs_pallas
        s = rs_pallas._TS + 777               # two tiles, ragged tail
        rng = np.random.default_rng(k * 31 + m)
        data = rng.integers(0, 256, (2, k, s), dtype=np.uint8)
        out = self._apply(rs_matrix.parity_matrix(k, m), data)
        assert out.shape == (2, m, s)
        for b in range(2):
            assert (out[b] == rs_ref.encode(data[b], m)[k:]).all()

    @pytest.mark.parametrize("k,m,lost", [
        (12, 4, (3,)), (12, 4, (0, 13)), (12, 4, (1, 5, 14)),
        (8, 8, (0,)), (8, 8, (2, 9)), (16, 4, (4, 7, 18))])
    def test_recover_rows_match_oracle(self, k, m, lost):
        from minio_tpu.ops import rs_pallas
        n = k + m
        s = rs_pallas._TS // 2 + 13           # under one tile
        data = _rand_shards(k, s, sum(lost) + k)
        full = rs_ref.encode(data, m)
        mask = sum(1 << i for i in range(n) if i not in lost)
        rec, used, missing = rs_matrix.recover_matrix(k, m, mask)
        assert tuple(missing) == lost and rec.shape[0] == len(lost)
        out = self._apply(rec, full[list(used)])
        for row, idx in enumerate(lost):
            assert (out[row] == full[idx]).all(), idx


class TestBitPacking:
    def test_unpack_pack_roundtrip(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        x = rng.integers(0, 256, (3, 5, 64)).astype(np.uint8)
        bits = rs_tpu.unpack_bits(jnp.asarray(x))
        assert bits.shape == (3, 40, 64)
        back = np.asarray(rs_tpu.pack_bits(bits))
        assert (back == x).all()
