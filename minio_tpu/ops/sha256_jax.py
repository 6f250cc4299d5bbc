"""Batched SHA-256 on device: all N streams advance in lockstep.

The reference uses sha256-simd (SHA-NI/AVX512 assembly) for content
hashes and the sha256 bitrot algorithm (cmd/bitrot.go:43-44,
pkg/hash/reader.go:31). A hash is sequential per stream; batching across
the B×n shard files of a PutObject batch is what maps it to the VPU —
the same shape as the HighwayHash kernel (ops/highwayhash_jax.py), but
simpler: SHA-256 is pure uint32 (rotates, xors, adds — no 64-bit lanes,
no multiplies, so none of the XLA algsimp pathologies either).

Graph-size discipline (single-core CPU hosts pay LLVM time per op): the
64 compression rounds and the 48 schedule extensions run as fori_loops
with dynamic indexing, so the compiled body is one round, not 64.

Bit-identity with hashlib.sha256 is enforced across padding branches by
tests/test_sha256_jax.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import device

U32 = jnp.uint32

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19], dtype=np.uint32)


def _ror(x, r: int):
    return (x >> U32(r)) | (x << U32(32 - r))


def _block_words(block_u8: jnp.ndarray) -> jnp.ndarray:
    """(N, 64) uint8 -> (16, N) u32 big-endian words."""
    b = block_u8.astype(U32).reshape(block_u8.shape[0], 16, 4)
    w = (b[:, :, 0] << U32(24)) | (b[:, :, 1] << U32(16)) | \
        (b[:, :, 2] << U32(8)) | b[:, :, 3]
    return w.T                                     # (16, N)


def _unrolled() -> bool:
    """Unroll the 112 per-block inner steps on TPU (loop trip overhead
    costs ~70 ms/batch otherwise); keep fori_loops on the CPU backend
    where each unrolled op is real single-core LLVM compile time."""
    return device.probe().is_tpu


def _one_round(abcdefgh, wi, ki):
    a, b, c, d, e, f, g, h = abcdefgh
    s1 = _ror(e, 6) ^ _ror(e, 11) ^ _ror(e, 25)
    ch = (e & f) ^ (~e & g)
    t1 = h + s1 + ch + ki + wi
    s0 = _ror(a, 2) ^ _ror(a, 13) ^ _ror(a, 22)
    maj = (a & b) ^ (a & c) ^ (b & c)
    return (t1 + s0 + maj, a, b, c, d + t1, e, f, g)


def _compress(state: jnp.ndarray, w16: jnp.ndarray,
              unroll: bool) -> jnp.ndarray:
    """state (8, N), w16 (16, N) -> new state (8, N)."""
    n = w16.shape[1]
    st = tuple(state[i] for i in range(8))

    if unroll:
        ws = [w16[i] for i in range(16)]
        for i in range(16, 64):
            w15, w2 = ws[i - 15], ws[i - 2]
            s0 = _ror(w15, 7) ^ _ror(w15, 18) ^ (w15 >> U32(3))
            s1 = _ror(w2, 17) ^ _ror(w2, 19) ^ (w2 >> U32(10))
            ws.append(ws[i - 16] + s0 + ws[i - 7] + s1)
        for i in range(64):
            st = _one_round(st, ws[i], U32(int(_K[i])))
        return state + jnp.stack(st)

    w = jnp.zeros((64, n), U32).at[:16].set(w16)

    def extend(i, w):
        w15 = lax.dynamic_slice_in_dim(w, i - 15, 1)[0]
        w2 = lax.dynamic_slice_in_dim(w, i - 2, 1)[0]
        w16_ = lax.dynamic_slice_in_dim(w, i - 16, 1)[0]
        w7 = lax.dynamic_slice_in_dim(w, i - 7, 1)[0]
        s0 = _ror(w15, 7) ^ _ror(w15, 18) ^ (w15 >> U32(3))
        s1 = _ror(w2, 17) ^ _ror(w2, 19) ^ (w2 >> U32(10))
        return lax.dynamic_update_slice_in_dim(
            w, (w16_ + s0 + w7 + s1)[None], i, 0)

    w = lax.fori_loop(16, 64, extend, w)
    kv = jnp.asarray(_K)

    def round_(i, abcdefgh):
        wi = lax.dynamic_slice_in_dim(w, i, 1)[0]
        ki = lax.dynamic_slice_in_dim(kv, i, 1)[0]
        return _one_round(abcdefgh, wi, ki)

    out = lax.fori_loop(0, 64, round_, st)
    return state + jnp.stack(out)


@functools.partial(jax.jit, static_argnums=(1,))
def _sha256_impl(data: jnp.ndarray, length: int) -> jnp.ndarray:
    n = data.shape[0]
    # standard padding: 0x80, zeros, 64-bit bit-length big-endian
    padded_len = ((length + 8) // 64 + 1) * 64
    pad = jnp.zeros((n, padded_len - length), jnp.uint8)
    pad = pad.at[:, 0].set(0x80)
    bitlen = length * 8
    tail = np.frombuffer(bitlen.to_bytes(8, "big"), np.uint8)
    pad = pad.at[:, -8:].set(jnp.asarray(tail)[None, :])
    msg = jnp.concatenate([data[:, :length], pad], axis=1)

    n_blocks = padded_len // 64
    # (N, blocks, 64) -> (blocks, 16, N) big-endian words
    blocks = msg.reshape(n, n_blocks, 64)
    state = jnp.broadcast_to(jnp.asarray(_H0)[:, None], (8, n)).astype(U32)
    unroll = _unrolled()

    def body(st, blk):                       # blk: (N, 64)
        return _compress(st, _block_words(blk), unroll), None

    state, _ = lax.scan(body, state,
                        jnp.transpose(blocks, (1, 0, 2)))
    # (8, N) u32 -> (N, 32) big-endian bytes
    b = jnp.stack([(state >> U32(24)) & U32(0xff),
                   (state >> U32(16)) & U32(0xff),
                   (state >> U32(8)) & U32(0xff),
                   state & U32(0xff)], axis=-1)   # (8, N, 4)
    return jnp.transpose(b, (1, 0, 2)).reshape(n, 32).astype(jnp.uint8)


def sha256_batch(data) -> jax.Array:
    """SHA-256 of every row of an (N, L) uint8 array -> (N, 32) digests,
    bit-identical to hashlib.sha256."""
    data = jnp.asarray(data, jnp.uint8)
    if data.ndim != 2:
        raise ValueError("data must be (N, L)")
    return _sha256_impl(data, data.shape[1])
