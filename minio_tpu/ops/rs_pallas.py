"""Fused Pallas TPU kernel for GF(2^8) coding matmuls.

The XLA path (rs_tpu.gf_matmul_xla) materializes the 8x bit-plane expansion
of the shard bytes in HBM — 8x the memory traffic of the payload. This
kernel fuses unpack -> binary matmul -> mod2 -> pack inside VMEM so HBM
sees only input bytes and output bytes:

    grid = (batch, S/TS)
    per step: load (k, TS) bytes -> bit-expand to (8k, TS) in VMEM
              -> MXU dot with the (8r, 8k) 0/1 matrix -> f32 (8r, TS)
              -> &1 -> pack -> store (r, TS) bytes

Layout note (measured on v5e): the natural bit row order i*8+p (byte i,
bit p) forces a sublane *interleave* when stacking the 8 shifted planes —
Mosaic lowers that as an expensive relayout. We instead keep bit-planes
contiguous ("plane-major": row p*k+i) and permute the coding matrix's
rows/columns to match — algebraically identical, zero extra cost (the
permutation is applied to the tiny matrix on the host/trace side).

All in-kernel tensors are 2D: Mosaic (as of jax 0.9) rejects 3D reshapes
like (1,8)->(8,1,1), and rejects uint8 shifts / int8 dot operands, so the
unpack runs in int32 and the matmul in bf16 with f32 accumulation
(contraction <= 128 keeps every partial sum exactly representable).

Replaces the reference's SIMD table-lookup kernels (its codec library's
AVX2 galMulSlice path) with an MXU-shaped formulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane-dimension tile: bytes of shard processed per grid step.
_TS = 16384


@functools.lru_cache(maxsize=64)
def _plane_major_perms(r: int, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Permutations mapping canonical bit layout (byte-major, row i*8+p) to
    plane-major (row p*k+i) for an (r x k) byte matrix's GF(2) expansion."""
    rperm = np.array([j * 8 + q for q in range(8) for j in range(r)])
    cperm = np.array([i * 8 + p for p in range(8) for i in range(k)])
    return rperm, cperm


def _kernel(m2_ref, data_ref, out_ref, *, k: int, r: int):
    x = data_ref[0].astype(jnp.int32)                      # (k, TS)
    planes = [((x >> p) & 1) for p in range(8)]
    bits = jnp.concatenate(planes, axis=0)                 # (8k, TS) plane-major
    acc = jnp.dot(m2_ref[...], bits.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)      # (8r, TS)
    ob = acc.astype(jnp.int32) & 1                         # plane-major rows
    out = ob[0:r]
    for q in range(1, 8):
        out = out | (ob[q * r:(q + 1) * r] << q)
    out_ref[0] = out.astype(jnp.uint8)


def _run(m2p: jnp.ndarray, data: jnp.ndarray, r: int, k: int) -> jnp.ndarray:
    b, _, s = data.shape  # s is a multiple of _TS
    grid = (b, s // _TS)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r * 8, k * 8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k, _TS), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, r, _TS), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, s), jnp.uint8),
        name="gf_matmul",
    )(m2p, data)


def gf_matmul_pallas_dev(m2: jnp.ndarray, shards: jnp.ndarray,
                         r: int, k: int) -> jnp.ndarray:
    """Apply bit-expanded matrix m2 ((8r,8k), canonical byte-major layout,
    any numeric dtype) to (..., k, S) uint8 shard bytes."""
    rperm, cperm = _plane_major_perms(r, k)
    m2p = m2.astype(jnp.bfloat16)[rperm][:, cperm]
    lead = shards.shape[:-2]
    s = shards.shape[-1]
    data = shards.reshape(-1, k, s)
    pad = (-s) % _TS
    if pad:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, pad)))
    out = _run(m2p, data, r, k)
    if pad:
        out = out[..., :s]
    return out.reshape(*lead, r, s)


def gf_matmul_pallas(matrix: np.ndarray, shards: jnp.ndarray) -> jnp.ndarray:
    """Apply a host (r,k) GF(2^8) matrix to (..., k, S) shard bytes."""
    from . import rs_tpu
    r, k = matrix.shape
    m2 = jnp.asarray(rs_tpu._bit_expand_cached(
        np.ascontiguousarray(matrix, dtype=np.uint8).tobytes(), (r, k)),
        jnp.bfloat16)
    return gf_matmul_pallas_dev(m2, jnp.asarray(shards, jnp.uint8), r, k)
