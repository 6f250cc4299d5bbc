"""Batched HighwayHash-256 on TPU: u32-pair emulation of the 64-bit lanes.

The reference's bitrot default is HighwayHash256 (cmd/bitrot.go:48-53,
streaming framing cmd/bitrot-streaming.go:46-58) computed per shard block
with AVX2 assembly. A hash is strictly sequential in its packet stream, so
a TPU can't parallelize *within* one shard — but a PutObject batch hashes
B×n independent shard blocks, and the VPU runs all of them in lockstep.

Layout choices that matter on the VPU:
  * no 64-bit integer lanes -> every u64 is a (lo, hi) pair of uint32
    arrays; adds carry via unsigned compare, 32x32->64 multiplies via
    16-bit split (with optimization barriers on the shifted operands —
    XLA's algebraic simplifier cycles on mul(shr(x)) patterns).
  * the state's four u64 lanes are kept permanently split into even
    (0, 2) and odd (1, 3) lane pairs, because the zipper-merge step mixes
    lanes pairwise: with the split representation every packet round is
    purely elementwise (no stack/reshape relayouts inside the scan).
  * streams fold into sublane GROUPS: a (2, N) state uses 2 of 8 VPU
    sublanes; reshaping to (2·G, N/G) with G stream groups stacked along
    sublanes fills the register file (G=4 on TPU -> full 8-sublane
    utilization). Packet words are pre-permuted once outside the scan so
    every round takes contiguous static slices.
  * packet rounds are unrolled _UNROLL-fold per lax.scan step to amortize
    loop overhead; the CPU backend keeps G=1 and a small unroll (each op
    is real single-core LLVM compile time there).

Bit-identity with the scalar implementation (ops/highwayhash_py.py, itself
pinned to the published HighwayHash vectors) is enforced by
tests/test_highwayhash_jax.py over lengths covering every remainder path,
and the grouped TPU layout is algebraically the same elementwise program
under a row relabeling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import device

_MUL0 = (0xdbe6d5d5fe4cce2f, 0xa4093822299f31d0,
         0x13198a2e03707344, 0x243f6a8885a308d3)
_MUL1 = (0x3bd39e10cb0ef593, 0xc0acf169b5f18a8c,
         0xbe5466cf34e90c6c, 0x452821e638d01377)

# packets unrolled per scan step / sublane stream-groups, per backend.
_UNROLL_TPU = 16
_UNROLL_CPU = 2
_GROUPS_TPU = 4
_GROUPS_CPU = 1


def _unroll() -> int:
    return _UNROLL_TPU if device.probe().is_tpu else _UNROLL_CPU


def _groups() -> int:
    return _GROUPS_TPU if device.probe().is_tpu else _GROUPS_CPU


U32 = jnp.uint32


def _word_perm(g: int) -> np.ndarray:
    """Row permutation for (8·g, n/g) per-packet words laid out w-major
    (row = word·g + group) -> [lo_e | hi_e | lo_o | hi_o] blocks of 2g
    rows each, lane-major then group within a block.

    Little-endian u64 lane j: lo = word 2j, hi = word 2j+1.
    """
    def block(words):
        return [w * g + grp for w in words for grp in range(g)]
    return np.array(block([0, 4]) + block([1, 5])
                    + block([2, 6]) + block([3, 7]))


# -- u64 emulation on (lo, hi) uint32 pairs ---------------------------------
# A "u64 vector" is a tuple (lo, hi) of identically-shaped uint32 arrays.

def _add64(a, b):
    lo = a[0] + b[0]
    carry = (lo < a[0]).astype(U32)
    return lo, a[1] + b[1] + carry


def _xor64(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def _or64(a, b):
    return a[0] | b[0], a[1] | b[1]


def _and64c(a, mask64: int):
    ml = U32(mask64 & 0xffffffff)
    mh = U32((mask64 >> 32) & 0xffffffff)
    return a[0] & ml, a[1] & mh


def _shl64c(a, s: int):
    if s == 0:
        return a
    if s >= 32:
        return jnp.zeros_like(a[0]), a[0] << U32(s - 32)
    return a[0] << U32(s), (a[1] << U32(s)) | (a[0] >> U32(32 - s))


def _shr64c(a, s: int):
    if s == 0:
        return a
    if s >= 32:
        return a[1] >> U32(s - 32), jnp.zeros_like(a[1])
    return (a[0] >> U32(s)) | (a[1] << U32(32 - s)), a[1] >> U32(s)


def _mul32(a32, b32):
    """(u32 a) * (u32 b) -> u64 pair, via 16-bit split.

    The high halves pass through an optimization barrier: XLA's algebraic
    simplifier cycles endlessly on `mul(shr(x, c), y)` patterns (circular
    rewrite; on big unrolled graphs the CPU compile never finishes), and
    the barrier hides the shift from the multiply."""
    m16 = U32(0xffff)
    al, ah = a32 & m16, lax.optimization_barrier(a32 >> U32(16))
    bl, bh = b32 & m16, lax.optimization_barrier(b32 >> U32(16))
    ll = al * bl
    lh = al * bh
    hl = ah * bl
    hh = ah * bh
    mid = lh + hl
    c_mid = (mid < lh).astype(U32)
    lo = ll + (mid << U32(16))
    c_lo = (lo < ll).astype(U32)
    hi = hh + (mid >> U32(16)) + (c_mid << U32(16)) + c_lo
    return lo, hi


def _zipper_merge(v1, v0):
    """Per-u64-lane byte shuffle of a (hi_lane=v1, lo_lane=v0) pair.

    v1/v0 are u64 pairs; returns (add1, add0) u64 pairs. Transcribed from
    highwayhash_py.HighwayHash._zipper_merge.
    """
    def t(x, mask, sh):
        m = _and64c(x, mask)
        return _shl64c(m, sh) if sh >= 0 else _shr64c(m, -sh)

    add0 = t(v0, 0xff000000, -24)
    for term in (t(v1, 0xff00000000, -24),
                 t(v0, 0xff0000000000, -16),
                 t(v1, 0xff000000000000, -16),
                 t(v0, 0xff0000, 0),
                 t(v0, 0xff00, 32),
                 t(v1, 0xff00000000000000, -8),
                 _shl64c(v0, 56)):
        add0 = _or64(add0, term)
    add1 = t(v1, 0xff000000, -24)
    for term in (t(v0, 0xff00000000, -24),
                 t(v1, 0xff0000, 0),
                 t(v1, 0xff0000000000, -16),
                 t(v1, 0xff00, 24),
                 t(v0, 0xff000000000000, -8),
                 t(v1, 0xff, 48),
                 t(v0, 0xff00000000000000, 0)):
        add1 = _or64(add1, term)
    return add1, add0


# -- state -------------------------------------------------------------------
# State: 8 u64 pairs of (2·G, N/G) u32 arrays — {v0,v1,mul0,mul1} ×
# {even lanes (0,2), odd lanes (1,3)}; within a pair, rows 0:G hold the
# low lane's G stream groups, rows G:2G the high lane's.

def _const_pair(vals2, g: int, cols: int):
    lo = np.repeat(np.array([v & 0xffffffff for v in vals2], np.uint32), g)
    hi = np.repeat(np.array([v >> 32 for v in vals2], np.uint32), g)
    return (jnp.broadcast_to(jnp.asarray(lo)[:, None], (2 * g, cols)),
            jnp.broadcast_to(jnp.asarray(hi)[:, None], (2 * g, cols)))


def _init_state(key: bytes, g: int, cols: int):
    k = [int.from_bytes(key[i * 8:(i + 1) * 8], "little") for i in range(4)]
    rot = [((v >> 32) | (v << 32)) & ((1 << 64) - 1) for v in k]
    st = {}
    for tag, lanes in (("e", (0, 2)), ("o", (1, 3))):
        mul0 = _const_pair([_MUL0[i] for i in lanes], g, cols)
        mul1 = _const_pair([_MUL1[i] for i in lanes], g, cols)
        st["mul0" + tag] = mul0
        st["mul1" + tag] = mul1
        st["v0" + tag] = _xor64(
            mul0, _const_pair([k[i] for i in lanes], g, cols))
        st["v1" + tag] = _xor64(
            mul1, _const_pair([rot[i] for i in lanes], g, cols))
    return st


@jax.jit
def _update(st, pe, po):
    """One packet round. pe/po: u64 pairs of (2G, N/G) — even/odd lanes.

    Jitted on its own so that a fused step holds ONE copy of the
    round's ~330 u32 ops and calls it from every unrolled packet
    (16 a scan step, the packets left over, the remainder, the
    finalize loop: 28 at S = 349526) instead of 28 inlined copies:
    XLA inlines the calls before it optimises, so the device program
    is the same, but tracing and lowering a step — Python under the
    GIL, 2.2 s a program on a v5e host, which a node loading its
    launch ladder at boot pays ten times — fall to a tenth."""
    v0e, v0o = st["v0e"], st["v0o"]
    v1e, v1o = st["v1e"], st["v1o"]
    mul0e, mul0o = st["mul0e"], st["mul0o"]
    mul1e, mul1o = st["mul1e"], st["mul1o"]

    v1e = _add64(v1e, _add64(mul0e, pe))
    v1o = _add64(v1o, _add64(mul0o, po))
    mul0e = _xor64(mul0e, _mul32(v1e[0], v0e[1]))
    mul0o = _xor64(mul0o, _mul32(v1o[0], v0o[1]))
    v0e = _add64(v0e, mul1e)
    v0o = _add64(v0o, mul1o)
    mul1e = _xor64(mul1e, _mul32(v0e[0], v1e[1]))
    mul1o = _xor64(mul1o, _mul32(v0o[0], v1o[1]))
    add1, add0 = _zipper_merge(v1o, v1e)
    v0e = _add64(v0e, add0)
    v0o = _add64(v0o, add1)
    add1, add0 = _zipper_merge(v0o, v0e)
    v1e = _add64(v1e, add0)
    v1o = _add64(v1o, add1)
    return {"v0e": v0e, "v0o": v0o, "v1e": v1e, "v1o": v1o,
            "mul0e": mul0e, "mul0o": mul0o, "mul1e": mul1e, "mul1o": mul1o}


def _packet_from_rows(w, g: int):
    """(8G, N/G) u32 in _word_perm order -> (pe, po) u64 pairs."""
    return ((w[0:2 * g], w[2 * g:4 * g]),
            (w[4 * g:6 * g], w[6 * g:8 * g]))


def _rot32half(x, n: int):
    """Rotate each 32-bit half of a u64 pair left by n (remainder step)."""
    if n == 0:
        return x
    return ((x[0] << U32(n)) | (x[0] >> U32(32 - n)),
            (x[1] << U32(n)) | (x[1] >> U32(32 - n)))


def _words_grouped(packets_u8: jnp.ndarray, g: int) -> jnp.ndarray:
    """(N, P, 32) uint8 packets -> (P, 8G, N/G) u32 in _word_perm order."""
    n, p, _ = packets_u8.shape
    words = lax.bitcast_convert_type(
        packets_u8.reshape(n, p, 8, 4), U32)      # (N, P, 8) LE words
    words = jnp.transpose(words, (1, 2, 0))       # (P, 8, N)
    words = words.reshape(p, 8, g, n // g).reshape(p, 8 * g, n // g)
    return words[:, _word_perm(g), :]


def _update_remainder(st, tail_u8, n_bytes: int, g: int):
    """tail_u8: (N, R) uint8 with R = n_bytes = L mod 32 (may be 0)."""
    if n_bytes == 0:
        return st
    N = tail_u8.shape[0]
    st = dict(st)
    inc = ((n_bytes << 32) + n_bytes)
    for tag in ("e", "o"):
        st["v0" + tag] = _add64(st["v0" + tag],
                                _const_pair([inc, inc], g, N // g))
        st["v1" + tag] = _rot32half(st["v1" + tag], n_bytes)

    mod4 = n_bytes & 3
    base = n_bytes & ~3
    packet = jnp.zeros((N, 32), jnp.uint8)
    if base:
        packet = packet.at[:, :base].set(tail_u8[:, :base])
    if n_bytes & 16:
        for i in range(4):
            packet = packet.at[:, 28 + i].set(tail_u8[:, base + mod4 + i - 4])
    elif mod4:
        rem = tail_u8[:, base:]
        packet = packet.at[:, 16].set(rem[:, 0])
        packet = packet.at[:, 17].set(rem[:, mod4 >> 1])
        packet = packet.at[:, 18].set(rem[:, mod4 - 1])
    w = _words_grouped(packet[:, None, :], g)[0]
    pe, po = _packet_from_rows(w, g)
    return _update(st, pe, po)


def _swap_blocks(x, g: int):
    """Swap the two lane blocks (rows 0:G <-> G:2G) of one array."""
    return jnp.concatenate([x[g:], x[:g]])


def _permute_and_update(st, g: int):
    # packet lanes = v0 lanes [2,3,0,1] with 32-bit halves swapped:
    # within each even/odd pair that is a lane-block swap + lo/hi swap.
    v0e, v0o = st["v0e"], st["v0o"]
    pe = (_swap_blocks(v0e[1], g), _swap_blocks(v0e[0], g))
    po = (_swap_blocks(v0o[1], g), _swap_blocks(v0o[0], g))
    return _update(st, pe, po)


def _finalize256(st, g: int):
    """-> (8, N) u32: the 32-byte digest as 8 little-endian words, rows
    in word order, columns in original stream order."""
    st = lax.fori_loop(0, 10, lambda i, s: _permute_and_update(s, g), st)

    def lane(name, l):
        # u64 lane l: (G, N/G) lo/hi slices of the e/o pair
        tag = "e" if l % 2 == 0 else "o"
        blk = l // 2
        x = st[name + tag]
        return (x[0][blk * g:(blk + 1) * g], x[1][blk * g:(blk + 1) * g])

    def modred(a3, a2, a1, a0):
        a3 = _and64c(a3, 0x3FFFFFFFFFFFFFFF)
        s1 = _or64(_shl64c(a3, 1), _shr64c(a2, 63))
        s2 = _or64(_shl64c(a3, 2), _shr64c(a2, 62))
        m1 = _xor64(_xor64(a1, s1), s2)
        m0 = _xor64(_xor64(a0, _shl64c(a2, 1)), _shl64c(a2, 2))
        return m1, m0

    def sum64(name1, name2, l):
        return _add64(lane(name1, l), lane(name2, l))

    h1, h0 = modred(sum64("v1", "mul1", 1), sum64("v1", "mul1", 0),
                    sum64("v0", "mul0", 1), sum64("v0", "mul0", 0))
    h3, h2 = modred(sum64("v1", "mul1", 3), sum64("v1", "mul1", 2),
                    sum64("v0", "mul0", 3), sum64("v0", "mul0", 2))
    # each h is a pair of (G, N/G); stack to (8, G, N/G) word-major,
    # then flatten group rows back to N columns
    out = jnp.stack([h0[0], h0[1], h1[0], h1[1],
                     h2[0], h2[1], h3[0], h3[1]])      # (8, G, N/G)
    return out.reshape(8, -1)                          # (8, N) group-major


# -- public op ---------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _hh256_impl(data: jnp.ndarray, length: int, key: bytes) -> jnp.ndarray:
    n_in = data.shape[0]
    g = _groups()
    pad_rows = (-n_in) % g
    if pad_rows:
        data = jnp.concatenate(
            [data, jnp.zeros((pad_rows, data.shape[1]), jnp.uint8)])
    n = n_in + pad_rows
    full = length // 32
    rem = length % 32
    st = _init_state(key, g, n // g)

    if full:
        words = _words_grouped(
            data[:, :full * 32].reshape(n, full, 32), g)  # (F, 8G, N/G)
        u = min(_unroll(), full)
        main = (full // u) * u

        def body(st, w):
            for j in range(u):
                pe, po = _packet_from_rows(w[j * 8 * g:(j + 1) * 8 * g], g)
                st = _update(st, pe, po)
            return st, None

        st, _ = lax.scan(body, st, words[:main].reshape(
            full // u, u * 8 * g, n // g))
        for j in range(main, full):
            pe, po = _packet_from_rows(words[j], g)
            st = _update(st, pe, po)
    if rem:
        st = _update_remainder(st, data[:, full * 32:length], rem, g)
    out = _finalize256(st, g)                          # (8, N) u32
    # (8, N) -> (N, 8) -> little-endian bytes; the group fold in
    # _finalize256 restored original stream order (groups were split
    # contiguously: stream s lives in group s // (N/G))
    digests = lax.bitcast_convert_type(
        jnp.transpose(out, (1, 0)), jnp.uint8).reshape(n, 32)
    return digests[:n_in]


def hh256_batch(key: bytes, data) -> jax.Array:
    """HighwayHash-256 of every row of an (N, L) uint8 array -> (N, 32).

    Device-batched: all N hashes advance in lockstep on the VPU. Byte-
    identical to the scalar/native implementations for any L (including the
    remainder paths of the reference algorithm).
    """
    data = jnp.asarray(data, jnp.uint8)
    if data.ndim != 2:
        raise ValueError("data must be (N, L)")
    return _hh256_impl(data, data.shape[1], bytes(key))


# -- ragged rows: the row lengths are an operand ------------------------------
# A program of its own beside the static one, which stays as it is: the
# launches of whole blocks run exactly what they ran.

def _remainder_sources() -> np.ndarray:
    """(32, 32) int32, row n = where each byte of the remainder packet
    of an n-byte remainder comes from: an index into those n bytes, or
    -1 for a zero byte. `_update_remainder`'s packet, as a table."""
    src = np.full((32, 32), -1, np.int32)
    for n in range(1, 32):
        mod4, base = n & 3, n & ~3
        src[n, :base] = np.arange(base)
        if n & 16:
            src[n, 28:] = base + mod4 - 4 + np.arange(4)
        elif mod4:
            src[n, 16:19] = (base, base + (mod4 >> 1), base + mod4 - 1)
    return src


_REMAINDER_SRC = _remainder_sources()


@functools.partial(jax.jit, static_argnums=(2,))
def _hh256_ragged_impl(data: jnp.ndarray, lengths: jnp.ndarray,
                       key: bytes) -> jnp.ndarray:
    n_in, width = data.shape
    g = _groups()
    lengths = lengths.astype(jnp.int32)
    pad_rows = (-n_in) % g
    if pad_rows:
        data = jnp.concatenate(
            [data, jnp.zeros((pad_rows, width), jnp.uint8)])
        lengths = jnp.concatenate(
            [lengths, jnp.zeros((pad_rows,), jnp.int32)])
    n = n_in + pad_rows
    cols = n // g
    full = width // 32

    def laid(x):
        # a value a row -> the state's (2G, N/G) layout: stream s is
        # column s % (N/G) of group s // (N/G), in both lane blocks
        x = x.reshape(g, cols)
        return jnp.concatenate([x, x])

    def keep(live, new, old):
        return jax.tree.map(lambda a, b: jnp.where(live, a, b), new, old)

    whole = laid(lengths // 32)           # whole packets of each row
    st = _init_state(key, g, cols)

    if full:
        words = _words_grouped(
            data[:, :full * 32].reshape(n, full, 32), g)  # (F, 8G, N/G)
        u = min(_unroll(), full)
        main = (full // u) * u

        def packet_round(st, w, i):
            # every row takes the round in lockstep; a row whose
            # packets have run out keeps the state it had
            pe, po = _packet_from_rows(w, g)
            return keep(i < whole, _update(st, pe, po), st)

        def body(st, xs):
            w, at = xs
            for j in range(u):
                st = packet_round(st, w[j * 8 * g:(j + 1) * 8 * g],
                                  at * u + j)
            return st, None

        st, _ = lax.scan(body, st, (
            words[:main].reshape(full // u, u * 8 * g, cols),
            jnp.arange(full // u, dtype=jnp.int32)))
        for j in range(main, full):
            st = packet_round(st, words[j], j)

    # the remainder step, n = length % 32 a row: its 32-byte window is
    # cut where the row's whole packets end (clamped into the array,
    # the table's indices shifted by as much)
    rem = lengths % 32
    if width < 32:
        data = jnp.concatenate(
            [data, jnp.zeros((n, 32 - width), jnp.uint8)], axis=1)
    at = lengths - rem
    lo = jnp.minimum(at, max(width, 32) - 32)
    window = jax.vmap(
        lambda row, o: lax.dynamic_slice(row, (o,), (32,)))(data, lo)
    src = jnp.asarray(_REMAINDER_SRC)[rem]                 # (N, 32)
    packet = jnp.where(
        src >= 0,
        jnp.take_along_axis(
            window, jnp.clip(src + (at - lo)[:, None], 0, 31), axis=1),
        jnp.uint8(0))
    nl = laid(rem.astype(U32))
    after = dict(st)
    for tag in ("e", "o"):
        after["v0" + tag] = _add64(st["v0" + tag], (nl, nl))
        after["v1" + tag] = tuple((x << nl) | (x >> (U32(32) - nl))
                                  for x in st["v1" + tag])
    pe, po = _packet_from_rows(
        _words_grouped(packet[:, None, :], g)[0], g)
    st = keep(nl > 0, _update(after, pe, po), st)

    out = _finalize256(st, g)
    digests = lax.bitcast_convert_type(
        jnp.transpose(out, (1, 0)), jnp.uint8).reshape(n, 32)
    return digests[:n_in]


def hh256_batch_ragged(key: bytes, data, lengths) -> jax.Array:
    """HighwayHash-256 of the first lengths[i] bytes of row i of an
    (N, L) uint8 array -> (N, 32): `hh256_batch` with the row lengths
    an OPERAND, so one program serves every mix of lengths up to L.
    All rows run the L // 32 packet rounds in lockstep and a row keeps
    its state once its own packets are through; the remainder step
    takes n = length % 32 a row. Byte-identical to the static program
    a row at a time, and with every length == L to `hh256_batch`."""
    data = jnp.asarray(data, jnp.uint8)
    if data.ndim != 2:
        raise ValueError("data must be (N, L)")
    return _hh256_ragged_impl(data, jnp.asarray(lengths, jnp.int32),
                              bytes(key))
