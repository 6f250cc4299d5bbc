"""TPU Reed-Solomon kernels: GF(2^8) coding as binary matmul on the MXU.

Design (TPU-first, not a port):

The reference's hot loop multiplies shard bytes by a constant GF(2^8) matrix
using SIMD table lookups (its codec library's AVX2 4-bit-table kernels).
Table lookups are gather-shaped — hostile to the MXU. Instead we use the
fact that multiplication by a constant c in GF(2^8) is *linear over GF(2)*:
there is an 8x8 bit-matrix B_c with bits(c*x) = B_c bits(x) (mod 2).

So the whole (m x k) GF(2^8) coding matrix expands into an (8m x 8k) 0/1
matrix M2 (ops/gf256.expand_to_gf2), and a block of k shards expands into a
(8k x S) 0/1 matrix of bit-planes. Then

    parity_bits = (M2 @ data_bits) mod 2

is one dense matmul — exactly MXU-shaped, batched over blocks with vmap.
XOR-accumulate == integer-accumulate + mod 2, and the contraction length
(8k <= 128 for k <= 16) keeps every partial sum < 2^8, exactly representable
in bf16/f32 accumulation.

Encode, reconstruct, and heal are all the *same* kernel with a different
matrix (parity rows / inverted submatrix / missing-row recovery matrix), so
one compiled program serves PutObject, GetObject-with-missing-shards, and
the healing scanner. Matrices are tiny (<= 128x128) and cached on device.

Two implementations:
  * `gf_matmul_xla`   — pure jnp; XLA fuses unpack/matmul/pack. Baseline.
  * `gf_matmul_pallas`— fused Pallas kernel: bytes stay in VMEM, bit-planes
    never touch HBM. (ops/rs_pallas.py)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import device
from . import rs_matrix


def _bit_expand_matrix(m: np.ndarray) -> jnp.ndarray:
    """(r,k) GF(2^8) matrix -> (8r, 8k) bf16 0/1 matrix on device."""
    from . import gf256
    return jnp.asarray(gf256.expand_to_gf2(m), dtype=jnp.bfloat16)


def unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(..., k, S) uint8 -> (..., 8k, S) bit-planes, bit p of byte i at row
    8i+p (LSB-first to match gf256.expand_to_gf2 layout)."""
    k = x.shape[-2]
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(*x.shape[:-2], k * 8, x.shape[-1])


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 8r, S) 0/1 uint8 -> (..., r, S) bytes (LSB-first)."""
    r8 = bits.shape[-2]
    r = r8 // 8
    b = bits.reshape(*bits.shape[:-2], r, 8, bits.shape[-1])
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return (b * weights[None, :, None]).sum(axis=-2, dtype=jnp.uint8)


def gf_matmul_xla(m2: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Apply a bit-expanded GF matrix to shard bytes.

    m2:   (8r, 8k) bf16 0/1 — from _bit_expand_matrix
    data: (..., k, S) uint8 shard bytes (batch dims leading)
    ->    (..., r, S) uint8 output shard bytes
    """
    bits = unpack_bits(data).astype(jnp.bfloat16)
    # contraction over 8k (<=128): exact in f32 accumulation
    acc = jnp.einsum(
        "rc,...cs->...rs", m2, bits,
        preferred_element_type=jnp.float32)
    out_bits = acc.astype(jnp.int32) & 1
    return pack_bits(out_bits.astype(jnp.uint8))


# ---------------------------------------------------------------------------
# Public codec ops (jitted, batched)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _encode_impl(data: jnp.ndarray, k: int, m: int, use_pallas: bool) -> jnp.ndarray:
    pm = rs_matrix.parity_matrix(k, m)
    if use_pallas:
        from . import rs_pallas
        parity = rs_pallas.gf_matmul_pallas(pm, data)
    else:
        parity = gf_matmul_xla(_bit_expand_matrix(pm), data)
    return jnp.concatenate([data, parity], axis=-2)


def encode(data, data_shards: int, parity_shards: int, *,
           use_pallas: bool | None = None) -> jax.Array:
    """Batched RS encode.

    data: (B, k, S) or (k, S) uint8 data shards (device or host array).
    Returns (B, n, S) / (n, S) with parity appended — byte-identical to the
    host oracle (rs_ref.encode).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    if use_pallas is None:
        use_pallas = default_use_pallas()
    return _encode_impl(data, data_shards, parity_shards, use_pallas)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _apply_matrix_impl(matrix_bits: jnp.ndarray, shards: jnp.ndarray,
                       r: int, k: int, use_pallas: bool) -> jnp.ndarray:
    m2 = matrix_bits.astype(jnp.bfloat16)
    if use_pallas:
        from . import rs_pallas
        return rs_pallas.gf_matmul_pallas_dev(m2, shards, r, k)
    return gf_matmul_xla(m2, shards)


def apply_matrix(matrix: np.ndarray, shards, *,
                 use_pallas: bool | None = None) -> jax.Array:
    """out = matrix (x) shards over GF(2^8), batched.

    matrix: (r, k) uint8 host matrix; shards: (..., k, S) uint8.
    The generic op behind reconstruct and heal.
    """
    shards = jnp.asarray(shards, dtype=jnp.uint8)
    if use_pallas is None:
        use_pallas = default_use_pallas()
    m2 = _bit_expand_cached(matrix.tobytes(), matrix.shape)
    return _apply_matrix_impl(m2, shards, matrix.shape[0], matrix.shape[1],
                              use_pallas)


@functools.lru_cache(maxsize=4096)
def _bit_expand_cached(matrix_bytes: bytes, shape: tuple[int, int]) -> np.ndarray:
    """Host-side cache of the GF(2) expansion. Returns numpy (never a device
    array: caching a tracer-stage device constant would leak tracers)."""
    from . import gf256
    m = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(shape)
    return gf256.expand_to_gf2(m)


def reconstruct_data(shards, present_mask: int, data_shards: int,
                     parity_shards: int, *, use_pallas: bool | None = None
                     ) -> jax.Array:
    """Rebuild all k data shards from k survivors.

    shards: (..., k, S) uint8 — the *first k present* shards in index order
    (rs_matrix.decode_matrix's `used` tuple gives the order the caller must
    stack them in).
    """
    d, _used = rs_matrix.decode_matrix(data_shards, parity_shards, present_mask)
    return apply_matrix(np.asarray(d), shards, use_pallas=use_pallas)


def recover_missing(shards, present_mask: int, data_shards: int,
                    parity_shards: int, *, use_pallas: bool | None = None
                    ) -> jax.Array:
    """Produce exactly the missing shards (data+parity) from k survivors —
    the heal kernel: one matmul instead of decode-then-reencode."""
    r, _used, _missing = rs_matrix.recover_matrix(
        data_shards, parity_shards, present_mask)
    return apply_matrix(np.asarray(r), shards, use_pallas=use_pallas)


def default_use_pallas() -> bool:
    """Pallas kernel on a real TPU; the XLA matmul elsewhere (Mosaic
    lowers for TPU only — CPU tests and the virtual mesh ride XLA)."""
    return device.probe().is_tpu
