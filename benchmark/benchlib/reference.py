"""The plain reference: what a PUT must leave on the drives, computed
from the body alone.

Straight from the public definitions, sharing no code, tables or
constants-made-at-run-time with the program:

  * Reed-Solomon over GF(2^8), polynomial 0x11d, systematic matrix =
    Vandermonde (n x k, element r^c) times the inverse of its top
    k x k (the construction of Backblaze's JavaReedSolomon, which
    upstream's codec library reproduces);
  * HighwayHash-256 (Alakuijala, Cox, Wassenberg 2017), keyed with
    upstream's fixed bitrot key (cmd/bitrot.go: HH-256 of the first
    100 decimals of pi under a zero key) — a constant of the on-disk
    format, written out below;
  * streaming bitrot framing: a part file is, block after block,
    [32-byte digest of the shard block][the shard block];
  * placement: drive j of the set holds shard hash_order(key)[j] - 1
    (CRC-32/IEEE of "bucket/key" picks the rotation);
  * ETag of a single-part PUT: hex MD5 of the body.

Plain numpy: vectorised over many equal-length shard blocks at once,
no kernels, no batching tricks beyond that.
"""

from __future__ import annotations

import hashlib
import zlib

import numpy as np

BITROT_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")
DIGEST_BYTES = 32

# ---------------------------------------------------------------------------
# GF(2^8) and the systematic Reed-Solomon matrix
# ---------------------------------------------------------------------------


def _gf_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11d
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] * n) % 255])


def _mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for r, row in enumerate(a):
        for c in range(len(b[0])):
            acc = 0
            for i, v in enumerate(row):
                acc ^= gf_mul(v, b[i][c])
            out[r][c] = acc
    return out


def _mat_inv(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    w = [list(row) + [int(i == r) for i in range(n)]
         for r, row in enumerate(m)]
    for c in range(n):
        piv = next(r for r in range(c, n) if w[r][c])
        w[c], w[piv] = w[piv], w[c]
        inv = gf_inv(w[c][c])
        w[c] = [gf_mul(v, inv) for v in w[c]]
        for r in range(n):
            if r != c and w[r][c]:
                f = w[r][c]
                w[r] = [v ^ gf_mul(f, u) for v, u in zip(w[r], w[c])]
    return [row[n:] for row in w]


def encode_matrix(k: int, m: int) -> list[list[int]]:
    """(k+m) x k: identity on top, parity rows below."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    return _mat_mul(vm, _mat_inv(vm[:k]))


def _mul_table(c: int) -> np.ndarray:
    t = np.zeros(256, np.uint8)
    if c:
        t[1:] = _EXP[_LOG[1:256] + _LOG[c]]
    return t


def rs_rows(matrix_rows: list[list[int]], shards: np.ndarray) -> np.ndarray:
    """rows (r x k) applied to shards (..., k, S) -> (..., r, S)."""
    out = np.zeros(shards.shape[:-2] + (len(matrix_rows), shards.shape[-1]),
                   np.uint8)
    for j, row in enumerate(matrix_rows):
        for i, c in enumerate(row):
            if c:
                out[..., j, :] ^= _mul_table(c)[shards[..., i, :]]
    return out


def shard_size(block_size: int, k: int) -> int:
    return -(-block_size // k)


def split_blocks(body: bytes, block_size: int, k: int) -> np.ndarray:
    """Body of whole blocks -> (blocks, k, S) data shards, each block
    zero-padded to k*S before the split."""
    if len(body) % block_size:
        raise ValueError("the reference handles whole blocks only")
    s = shard_size(block_size, k)
    nb = len(body) // block_size
    flat = np.zeros((nb, k * s), np.uint8)
    flat[:, :block_size] = np.frombuffer(body, np.uint8).reshape(
        nb, block_size)
    return flat.reshape(nb, k, s)


# ---------------------------------------------------------------------------
# HighwayHash-256 over N equal-length streams at once
# ---------------------------------------------------------------------------

_U = np.uint64
_INIT0 = np.array([0xdbe6d5d5fe4cce2f, 0xa4093822299f31d0,
                   0x13198a2e03707344, 0x243f6a8885a308d3], _U)
_INIT1 = np.array([0x3bd39e10cb0ef593, 0xc0acf169b5f18a8c,
                   0xbe5466cf34e90c6c, 0x452821e638d01377], _U)
_M32 = _U(0xffffffff)


def _rot32(x: np.ndarray) -> np.ndarray:
    return (x >> _U(32)) | (x << _U(32))


def _zipper(v1: np.ndarray, v0: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """ZipperMergeAndAdd's byte shuffle of one 128-bit lane pair
    (v1 high, v0 low) -> (add1, add0)."""
    u = _U
    add0 = ((((v0 & u(0xff000000)) | (v1 & u(0xff00000000))) >> u(24))
            | (((v0 & u(0xff0000000000)) | (v1 & u(0xff000000000000)))
               >> u(16))
            | (v0 & u(0xff0000)) | ((v0 & u(0xff00)) << u(32))
            | ((v1 & u(0xff00000000000000)) >> u(8)) | (v0 << u(56)))
    add1 = ((((v1 & u(0xff000000)) | (v0 & u(0xff00000000))) >> u(24))
            | (v1 & u(0xff0000)) | ((v1 & u(0xff0000000000)) >> u(16))
            | ((v1 & u(0xff00)) << u(24))
            | ((v0 & u(0xff000000000000)) >> u(8))
            | ((v1 & u(0xff)) << u(48)) | (v0 & u(0xff00000000000000)))
    return add1, add0


class _HH:
    """State of N streams: four (4, N) uint64 arrays."""

    def __init__(self, key: bytes, n: int):
        k = np.frombuffer(key, "<u8").astype(_U)
        one = np.ones((4, n), _U)
        self.mul0 = _INIT0[:, None] * one
        self.mul1 = _INIT1[:, None] * one
        self.v0 = (_INIT0 ^ k)[:, None] * one
        self.v1 = (_INIT1 ^ _rot32(k))[:, None] * one

    def update(self, lanes: np.ndarray) -> None:
        """lanes (4, N): one 32-byte packet of every stream."""
        v0, v1, mul0, mul1 = self.v0, self.v1, self.mul0, self.mul1
        v1 += mul0 + lanes
        mul0 ^= (v1 & _M32) * (v0 >> _U(32))
        v0 += mul1
        mul1 ^= (v0 & _M32) * (v1 >> _U(32))
        for dst, src in ((v0, v1), (v1, v0)):
            for lo in (0, 2):
                add1, add0 = _zipper(src[lo + 1], src[lo])
                dst[lo] += add0
                dst[lo + 1] += add1

    def remainder(self, tail: np.ndarray) -> None:
        """tail (N, n) with 0 < n < 32: the bytes after the last whole
        packet (HighwayHash's UpdateRemainder)."""
        n = tail.shape[1]
        mod4 = n & 3
        whole = n & ~3
        self.v0 += _U((n << 32) + n)
        lo = self.v1 & _M32
        hi = self.v1 >> _U(32)
        lo = ((lo << _U(n)) | (lo >> _U(32 - n))) & _M32
        hi = ((hi << _U(n)) | (hi >> _U(32 - n))) & _M32
        self.v1[...] = (hi << _U(32)) | lo
        packet = np.zeros((tail.shape[0], 32), np.uint8)
        packet[:, :whole] = tail[:, :whole]
        if n & 16:
            packet[:, 28:32] = tail[:, whole + mod4 - 4:whole + mod4]
        elif mod4:
            packet[:, 16] = tail[:, whole]
            packet[:, 17] = tail[:, whole + (mod4 >> 1)]
            packet[:, 18] = tail[:, whole + mod4 - 1]
        self.update(np.ascontiguousarray(packet).view("<u8").T.astype(_U))

    def digest256(self) -> np.ndarray:
        for _ in range(10):
            v = self.v0
            self.update(np.stack([_rot32(v[2]), _rot32(v[3]),
                                  _rot32(v[0]), _rot32(v[1])]))

        def modred(a3u, a2, a1, a0):
            a3 = a3u & _U(0x3FFFFFFFFFFFFFFF)
            m1 = a1 ^ ((a3 << _U(1)) | (a2 >> _U(63))) \
                ^ ((a3 << _U(2)) | (a2 >> _U(62)))
            m0 = a0 ^ (a2 << _U(1)) ^ (a2 << _U(2))
            return m1, m0

        s0, s1 = self.v0 + self.mul0, self.v1 + self.mul1
        h1, h0 = modred(s1[1], s1[0], s0[1], s0[0])
        h3, h2 = modred(s1[3], s1[2], s0[3], s0[2])
        out = np.stack([h0, h1, h2, h3], axis=1).astype("<u8")   # (N, 4)
        return np.ascontiguousarray(out).view(np.uint8)


def hh256_many(streams: np.ndarray, key: bytes = BITROT_KEY) -> np.ndarray:
    """streams (N, L) uint8 -> (N, 32) digests."""
    n, length = streams.shape
    h = _HH(key, n)
    whole = length // 32
    if whole:
        # (P, 4, N): packet-major so every step reads one contiguous slab
        lanes = np.ascontiguousarray(
            streams[:, :whole * 32]).view("<u8").reshape(n, whole, 4)
        lanes = np.ascontiguousarray(lanes.transpose(1, 2, 0)).astype(
            _U, copy=False)
        with np.errstate(over="ignore"):
            for p in range(whole):
                h.update(lanes[p])
    with np.errstate(over="ignore"):
        if length % 32:
            h.remainder(streams[:, whole * 32:])
        return h.digest256()


# ---------------------------------------------------------------------------
# what the drives must hold
# ---------------------------------------------------------------------------

def hash_order(key: str, n: int) -> list[int]:
    start = zlib.crc32(key.encode()) % n
    return [1 + ((start + i) % n) for i in range(1, n + 1)]


def shard_of_drive(bucket: str, key: str, n: int) -> list[int]:
    """Drive j (set order) -> 0-based shard index it holds; upstream
    hashes the joined path "bucket/object"."""
    return [s - 1 for s in hash_order(f"{bucket}/{key}", n)]


def frames(data: np.ndarray, m: int) -> np.ndarray:
    """Data shards (B, k, S) of B blocks -> (B, n, 32+S): for every block
    and every shard index, what its part file holds for that block —
    the digest, then the shard (parity rows computed here)."""
    k = data.shape[1]
    parity = rs_rows(encode_matrix(k, m)[k:], data)        # (B, m, S)
    full = np.concatenate([data, parity], axis=1)          # (B, n, S)
    nb, n, s = full.shape
    digests = hh256_many(full.reshape(nb * n, s)).reshape(nb, n, 32)
    return np.concatenate([digests, full], axis=2)         # (B, n, 32+S)


def part_files(body: bytes, k: int, m: int, block_size: int) -> list[bytes]:
    """The n part files of a single-part object, by shard index."""
    framed = frames(split_blocks(body, block_size, k), m)
    return [framed[:, i, :].tobytes() for i in range(framed.shape[1])]


def etag(body: bytes) -> str:
    return hashlib.md5(body).hexdigest()
