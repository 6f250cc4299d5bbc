"""Bounded byte-buffer pool (reference pkg/bpool.BytePoolCap, fed to the
erasure encoder at cmd/erasure-sets.go:374).

PUT streams stage each block batch in a same-width buffer; pooling them
caps allocation churn and puts a hard bound on staging memory. get()
blocks when the pool is exhausted — that back-pressure IS the admission
control for raw block memory. The pressure is observable: `waits`
counts gets that had to block, `exhausted` counts gets that timed out
(surfaced as minio_tpu_pipeline_bpool_* metrics), so a stalled pipeline
shows up on a dashboard instead of as a silent hang.
"""

from __future__ import annotations

import queue
from typing import Optional

from ..utils import lockcheck


class BytePoolExhausted(Exception):
    """get() timed out: every buffer is checked out and none returned
    within the deadline — the pipeline is stalled or the pool is
    undersized for the live stream count."""


class BytePool:
    def __init__(self, width: int, capacity: int):
        self.width = width
        self.capacity = capacity
        self.waits = 0          # get() calls that had to block
        self.exhausted = 0      # get() calls that timed out
        self._mu = lockcheck.mutex("bpool.created")
        self._created = 0       # buffers allocated so far (<= capacity)
        self._q: "queue.Queue[bytearray]" = queue.Queue(maxsize=capacity)

    def get(self, timeout: Optional[float] = None) -> bytearray:
        """A pooled buffer; allocated lazily up to `capacity` (an idle
        pool costs nothing), then blocks (up to `timeout` seconds,
        forever when None) while all buffers are checked out. Raises
        BytePoolExhausted on timeout."""
        try:
            return self._q.get_nowait()
        except queue.Empty:
            pass
        with self._mu:
            if self._created < self.capacity:
                self._created += 1
                return bytearray(self.width)
            self.waits += 1
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            with self._mu:
                self.exhausted += 1
            raise BytePoolExhausted(
                f"no {self.width}-byte staging buffer freed within "
                f"{timeout}s (capacity {self.capacity})") from None

    def try_get(self) -> Optional[bytearray]:
        """A pooled buffer, or None when every one is checked out: for a
        caller that would rather allocate than wait."""
        try:
            return self._q.get_nowait()
        except queue.Empty:
            pass
        with self._mu:
            if self._created < self.capacity:
                self._created += 1
                return bytearray(self.width)
        return None

    def discard(self) -> None:
        """A checked-out buffer will not come back (something may still
        read it): the ring may allocate another in its place."""
        with self._mu:
            self._created = max(self._created - 1, 0)

    def put(self, buf: bytearray) -> None:
        if len(buf) != self.width:
            # a foreign-width buffer returned here would poison a later
            # get() with a wrong-geometry staging buffer — caller bug,
            # surface it
            raise ValueError(
                f"foreign buffer: width {len(buf)} != pool {self.width}")
        try:
            self._q.put_nowait(buf)
        except queue.Full:
            pass
