"""Bitrot protection algorithms and shard-file framing math.

Mirrors the reference's bitrot surface (cmd/bitrot.go): four algorithms
with the same string names, HighwayHash256 keyed by the magic pi-digest
key, and the streaming variant ("highwayhash256S") that interleaves a
32-byte digest before every shard block in the shard file
(cmd/bitrot-streaming.go framing: [hash || block]*). The default algorithm
is HighwayHash256S (reference default: cmd/xl-storage-format-v1.go:119).

Engine selection follows the fork's accelerator pattern (the reference
fork's QAT engine pick in pkg/hash/reader.go:189-206): native C++ library
when available, pure-Python fallback otherwise; the TPU batch path hashes
whole shard batches device-side (ops/ + models/).
"""

from __future__ import annotations

import enum
import hashlib
from typing import Protocol, Sequence

import numpy as np

# HH-256 of the first 100 decimals of pi (utf-8) with a zero key — verified
# reproducible by our own HighwayHash (see tests/test_bitrot.py).
MAGIC_HIGHWAYHASH_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")


class BitrotAlgorithm(enum.Enum):
    SHA256 = "sha256"
    BLAKE2B512 = "blake2b"
    HIGHWAYHASH256 = "highwayhash256"
    HIGHWAYHASH256S = "highwayhash256S"

    @property
    def streaming(self) -> bool:
        """Streaming algorithms frame a digest per shard block inside the
        shard file; whole-file algorithms store one digest in metadata."""
        return self is BitrotAlgorithm.HIGHWAYHASH256S

    @property
    def digest_size(self) -> int:
        return 64 if self is BitrotAlgorithm.BLAKE2B512 else 32

    @classmethod
    def from_string(cls, s: str) -> "BitrotAlgorithm":
        for a in cls:
            if a.value == s:
                return a
        raise ValueError(f"unsupported bitrot algorithm: {s!r}")


DEFAULT_BITROT_ALGORITHM = BitrotAlgorithm.HIGHWAYHASH256S


class Hasher(Protocol):
    def update(self, data: bytes) -> None: ...
    def digest(self) -> bytes: ...


class _NativeHH256:
    """Streaming HighwayHash-256 over the native library."""

    def __init__(self) -> None:
        from .utils import native
        self._native = native
        self._state = np.zeros(128, dtype=np.uint8)
        key = np.frombuffer(MAGIC_HIGHWAYHASH_KEY, dtype=np.uint8)
        lib = native.get_lib()
        assert lib is not None
        self._lib = lib
        lib.hh_init(native._u8p(key), native._u8p(self._state))
        self._tail = b""

    def update(self, data: bytes) -> None:
        buf = self._tail + data
        full = len(buf) & ~31
        if full:
            d = np.frombuffer(buf[:full], dtype=np.uint8)
            self._lib.hh_update_packets(
                self._native._u8p(self._state), self._native._u8p(d), full)
        self._tail = buf[full:]

    def digest(self) -> bytes:
        state = self._state.copy()
        out = np.zeros(32, dtype=np.uint8)
        rem = np.frombuffer(self._tail, dtype=np.uint8) if self._tail else \
            np.zeros(0, dtype=np.uint8)
        self._lib.hh_final256(self._native._u8p(state),
                              self._native._u8p(rem), len(self._tail),
                              self._native._u8p(out))
        return out.tobytes()


class _PyHH256:
    def __init__(self) -> None:
        from .ops.highwayhash_py import HighwayHash
        self._h = HighwayHash(MAGIC_HIGHWAYHASH_KEY)

    def update(self, data: bytes) -> None:
        self._h.update(data)

    def digest(self) -> bytes:
        return self._h.digest256()


def new_hasher(algo: BitrotAlgorithm = DEFAULT_BITROT_ALGORITHM) -> Hasher:
    if algo in (BitrotAlgorithm.HIGHWAYHASH256,
                BitrotAlgorithm.HIGHWAYHASH256S):
        from .utils import native
        if native.available():
            return _NativeHH256()
        return _PyHH256()
    if algo is BitrotAlgorithm.SHA256:
        return hashlib.sha256()
    if algo is BitrotAlgorithm.BLAKE2B512:
        return hashlib.blake2b(digest_size=64)
    raise ValueError(f"unsupported bitrot algorithm: {algo}")


def hash_shard(data: bytes | np.ndarray,
               algo: BitrotAlgorithm = DEFAULT_BITROT_ALGORITHM) -> bytes:
    h = new_hasher(algo)
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data, np.uint8).tobytes()
    h.update(data)
    return h.digest()


def hash_shards_batch(shards: np.ndarray,
                      algo: BitrotAlgorithm = DEFAULT_BITROT_ALGORITHM
                      ) -> np.ndarray:
    """Digest every row of an (n, L) shard-block matrix -> (n, digest_size).

    One native call for HighwayHash (the per-encode-step hot path);
    hashlib loop otherwise.
    """
    shards = np.ascontiguousarray(shards, np.uint8)
    if algo in (BitrotAlgorithm.HIGHWAYHASH256,
                BitrotAlgorithm.HIGHWAYHASH256S):
        from .utils import native
        if native.available():
            return native.hh256_batch(MAGIC_HIGHWAYHASH_KEY, shards)
    out = np.zeros((shards.shape[0], algo.digest_size), dtype=np.uint8)
    for i in range(shards.shape[0]):
        out[i] = np.frombuffer(hash_shard(shards[i], algo), dtype=np.uint8)
    return out


def hash_rows(rows: Sequence[np.ndarray],
              algo: BitrotAlgorithm = DEFAULT_BITROT_ALGORITHM
              ) -> np.ndarray:
    """hash_shards_batch's digests of n equal-length shards, each hashed
    where it lies -> (n, digest_size): no (n, L) array is stacked first.
    One native call for HighwayHash; a per-row loop otherwise."""
    rows = [np.ascontiguousarray(r, np.uint8).reshape(-1) for r in rows]
    if algo in (BitrotAlgorithm.HIGHWAYHASH256,
                BitrotAlgorithm.HIGHWAYHASH256S):
        from .utils import native
        if native.rows_available():
            return native.hh256_rows(MAGIC_HIGHWAYHASH_KEY, rows)
    out = np.zeros((len(rows), algo.digest_size), dtype=np.uint8)
    for i, r in enumerate(rows):
        out[i] = np.frombuffer(hash_shard(r, algo), dtype=np.uint8)
    return out


def ceil_frac(num: int, den: int) -> int:
    return -(-num // den)


def bitrot_shard_file_size(size: int, shard_size: int,
                           algo: BitrotAlgorithm) -> int:
    """On-disk size of a shard file of `size` payload bytes.

    Streaming algorithms add one digest per shard block
    (reference math: cmd/bitrot.go:140-145)."""
    if not algo.streaming:
        return size
    if size <= 0:
        return size
    return ceil_frac(size, shard_size) * algo.digest_size + size
