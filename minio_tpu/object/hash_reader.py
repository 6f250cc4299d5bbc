"""HashReader — content hashing wrapped around the PUT stream.

The analog of the reference's pkg/hash.Reader (pkg/hash/reader.go):
tees MD5 (the ETag) and optionally SHA256 over the client payload while
the engine consumes it, and verifies client expectations at EOF.

The fork's QAT pattern (pkg/hash/reader.go:189-206: pick a HW engine when
one is free, overlap the digest with encode) generalizes here to a
background hashing thread: blocks are queued to the hasher while the
erasure encode + shard writes proceed — digest latency hides behind the
device pipeline exactly like the fork's async Accel_write_data/MD5Sum
(cmd/erasure-encode.go:113-124).
"""

from __future__ import annotations

import hashlib
import os
import queue
import threading
import time
from typing import BinaryIO, Optional

from . import api_errors
from ..utils import telemetry

# Overlapping the digest with encode+write only pays when there is a
# second core to run it on; on a single-core host the queue handoff is
# pure overhead.
_DEFAULT_ASYNC = (os.cpu_count() or 1) > 1


class HashReader:
    def __init__(self, stream: BinaryIO, size: int = -1,
                 md5_hex: str = "", sha256_hex: str = "",
                 actual_size: int = -1,
                 async_hash: Optional[bool] = None):
        if async_hash is None:
            async_hash = _DEFAULT_ASYNC
        self._stream = stream
        self.size = size
        self.actual_size = actual_size if actual_size >= 0 else size
        self._want_md5 = md5_hex
        self._want_sha256 = sha256_hex
        self._md5 = hashlib.md5()
        self._sha256 = hashlib.sha256() if sha256_hex else None
        self.bytes_read = 0
        # MD5 + SHA-256 busy time of this request, as ONE span under
        # whatever span built the reader (the S3 handler's), fed from
        # whichever thread hashes and attached when the hasher drains
        self._hash_span = telemetry.accum("s3.body_hash")

        self._async = async_hash
        self._q: Optional[queue.Queue] = None
        self._worker: Optional[threading.Thread] = None
        if async_hash:
            self._q = queue.Queue(maxsize=8)
            self._worker = threading.Thread(target=self._hash_loop,
                                            daemon=True)
            self._worker.start()

    def _hash_loop(self) -> None:
        assert self._q is not None
        while True:
            chunk = self._q.get()
            if chunk is None:
                return
            self._update(chunk)

    def _update(self, chunk) -> None:
        t0 = time.perf_counter_ns()
        self._md5.update(chunk)
        if self._sha256 is not None:
            self._sha256.update(chunk)
        self._hash_span.add(t0, time.perf_counter_ns())

    def read(self, n: int = -1) -> bytes:
        if self.size >= 0:
            remaining = self.size - self.bytes_read
            if remaining <= 0:
                return b""
            if n is None or n < 0 or n > remaining:
                n = remaining
        chunk = self._stream.read(n) if n != -1 else self._stream.read()
        if chunk:
            self.bytes_read += len(chunk)
            if self._q is not None:
                self._q.put(chunk)
            else:
                self._update(chunk)
        return chunk

    def readinto_full(self, mv: memoryview) -> int:
        """Fill `mv` completely unless EOF; hashes the filled prefix.
        The zero-copy seam of the PUT hot loop: bytes land once in the
        caller's encode buffer (the fork's Accel_get_next_buff pattern,
        cmd/erasure-encode.go:104)."""
        want = len(mv)
        if self.size >= 0:
            remaining = self.size - self.bytes_read
            if remaining <= 0:
                return 0
            if want > remaining:
                mv = mv[:remaining]
                want = remaining
        stream = self._stream
        readinto = getattr(stream, "readinto", None)
        got = 0
        while got < want:
            if readinto is not None:
                n = readinto(mv[got:])
                if not n:
                    break
                got += n
            else:
                chunk = stream.read(want - got)
                if not chunk:
                    break
                mv[got:got + len(chunk)] = chunk
                got += len(chunk)
        if got:
            self.bytes_read += got
            if self._q is not None:
                # async hashing must own a stable copy — the caller
                # reuses the buffer for the next block
                self._q.put(bytes(mv[:got]))
            else:
                self._update(mv[:got])
        return got

    def _drain(self) -> None:
        if self._q is not None and self._worker is not None:
            self._q.put(None)
            self._worker.join()
            self._q = None
            self._worker = None
        self._hash_span.flush(bytes=self.bytes_read)

    def close(self) -> None:
        """Stop the background hasher — MUST be called on abandoned
        uploads or the worker thread leaks."""
        self._drain()

    def md5_current_hex(self) -> str:
        """Digest so far (reference MD5CurrentHexString) — call after the
        stream is fully consumed for the final ETag."""
        self._drain()
        return self._md5.hexdigest()

    def verify(self) -> None:
        """At EOF: enforce declared size and client-expected digests
        (reference hash.Reader EOF verification)."""
        self._drain()
        if self.size >= 0 and self.bytes_read != self.size:
            raise api_errors.IncompleteBody(
                f"read {self.bytes_read} of declared {self.size}")
        if self._want_md5 and self._md5.hexdigest() != self._want_md5:
            raise api_errors.InvalidETag(
                f"md5 mismatch: {self._md5.hexdigest()} != {self._want_md5}")
        if (self._want_sha256 and self._sha256 is not None
                and self._sha256.hexdigest() != self._want_sha256):
            raise api_errors.SignatureDoesNotMatch("content sha256 mismatch")
