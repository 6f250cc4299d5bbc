"""Bitrot-framed shard I/O over StorageAPI.

Streaming algorithms (the default HighwayHash256S) interleave a digest
before every shard block inside the shard file — ``[h(block) || block]*``
— so reads verify incrementally without a separate checksum file
(reference: cmd/bitrot-streaming.go:46-58 writer, :111-150 reader).
Whole-file algorithms hash the entire shard and store the digest in
xl.meta's checksum list (cmd/bitrot-whole.go).

Writers buffer frames and flush to the drive with append_file; readers
pread frames by computed offset and verify before returning payload.
"""

from __future__ import annotations

import io
from typing import Optional

from .. import bitrot as bitrot_mod
from ..storage import errors
from ..storage.api import StorageAPI
from ..utils import telemetry

BitrotAlgorithm = bitrot_mod.BitrotAlgorithm

# syscalls / frames says whether the vectored write engages: 1 / B for
# a group of B frames on a local drive
_WRITE_SYSCALLS = telemetry.REGISTRY.counter(
    "minio_tpu_shard_write_syscalls_total",
    "Write calls issued for bitrot shard frames (write/writev "
    "syscalls on local drives, append_file calls on remote ones)")
_WRITE_FRAMES = telemetry.REGISTRY.counter(
    "minio_tpu_shard_write_frames_total",
    "Bitrot [digest][shard] frames handed to streaming shard writers")


def new_bitrot_writer(disk: StorageAPI, volume: str, path: str,
                      length: int, algo: BitrotAlgorithm,
                      shard_size: int):
    """Factory mirroring reference newBitrotWriter (cmd/bitrot.go:99)."""
    if algo.streaming:
        return StreamingBitrotWriter(disk, volume, path, shard_size, algo)
    return WholeBitrotWriter(disk, volume, path, algo)


def new_bitrot_reader(disk: StorageAPI, volume: str, path: str,
                      till_offset: int, algo: BitrotAlgorithm,
                      expected_digest: bytes, shard_size: int):
    """Factory mirroring reference newBitrotReader (cmd/bitrot.go:105)."""
    if algo.streaming:
        return StreamingBitrotReader(disk, volume, path, till_offset,
                                     algo, shard_size)
    return WholeBitrotReader(disk, volume, path, algo, expected_digest,
                             shard_size)


class StreamingBitrotWriter:
    """Writes [digest || block] frames; every write() must be exactly one
    shard block (the last may be short) — matching the encode loop's
    block cadence."""

    FLUSH_THRESHOLD = 8 << 20  # bound writer memory on huge parts

    def __init__(self, disk: StorageAPI, volume: str, path: str,
                 shard_size: int, algo: BitrotAlgorithm):
        self.disk, self.volume, self.path = disk, volume, path
        self.shard_size, self.algo = shard_size, algo
        self._buf = io.BytesIO()
        self._started = False
        self.closed = False
        # Local drives expose a persistent append handle: frames stream
        # straight into the OS file (one memcpy pass fewer than
        # buffer-then-append). Remote disks keep the buffered batches —
        # one RPC per flush, not per frame. Opened lazily so writer
        # construction never touches the drive (per-drive faults must
        # surface inside the quorum-tolerant write fan-out).
        self._file = None
        try:
            probe = getattr(disk, "has_appender", None)
            self._use_appender = bool(probe is not None and probe())
        except Exception:  # noqa: BLE001 — capability probe only
            self._use_appender = False

    def write(self, block: bytes) -> None:
        if len(block) == 0:
            return
        digest = bitrot_mod.hash_shard(block, self.algo)
        self.write_with_digest(block, digest)

    def write_with_digest(self, block, digest) -> None:
        """Frame a block whose digest was already computed (by the batched
        device/native hasher) — the accelerator handoff seam. For callers
        that have one frame; a group goes through write_frames."""
        self.write_frames((block,), (digest,))

    def write_frames(self, blocks, digests) -> tuple[int, bool]:
        """A group's B frames, [digests[0]][blocks[0]][digests[1]]...,
        in one call. A local append handle takes them in ONE vectored
        write — the handle's business how (an O_DIRECT handle stages
        them through its aligned buffer) — where a write a digest and a
        write a block crossed the interpreter lock 2 x B times. The
        blocks need not be of one length: a group that ends in an
        object's short last block goes out in the same one call (the
        vector is 2 x B buffers of whatever sizes). Remote drives keep
        the buffered append_file batches. Returns (write calls issued,
        whether the handle took the list vectored)."""
        if self._use_appender:
            frames = [buf for pair in zip(digests, blocks) for buf in pair]
            try:
                if self._file is None:
                    self._file = self.disk.open_appender(self.volume,
                                                         self.path)
                writes = self._file.writev(frames)
            except OSError as e:
                raise errors.FaultyDisk(str(e)) from e
            vectored = self._file.vectored
        else:
            writes, vectored = 0, False
            for block, digest in zip(blocks, digests):
                self._buf.write(digest)
                self._buf.write(block)
                if self._buf.tell() >= self.FLUSH_THRESHOLD:
                    self._flush()
                    writes += 1
        _WRITE_SYSCALLS.inc(writes)
        _WRITE_FRAMES.inc(len(blocks))
        return writes, vectored

    def _flush(self) -> None:
        # getbuffer(): hand the drive a view, not a copy, of the frame
        # buffer (a full extra pass over the payload per shard file)
        data = self._buf.getbuffer()
        if not data.nbytes and self._started:
            return
        self.disk.append_file(self.volume, self.path, data)
        self._started = True
        del data
        self._buf = io.BytesIO()

    def close(self) -> None:
        """Flush what is buffered and close the file; a second call
        is a no-op (`closed`)."""
        if self.closed:
            return
        if self._use_appender:
            try:
                if self._file is None:
                    # 0-byte objects still commit an (empty) shard file
                    self._file = self.disk.open_appender(self.volume,
                                                         self.path)
                self._file.close()
            except OSError as e:
                raise errors.FaultyDisk(str(e)) from e
            finally:
                self._file = None
        else:
            self._flush()
        self.closed = True

    def digest(self) -> bytes:
        return b""  # streaming: digests live in the frames


class WholeBitrotWriter:
    def __init__(self, disk: StorageAPI, volume: str, path: str,
                 algo: BitrotAlgorithm):
        self.disk, self.volume, self.path = disk, volume, path
        self.algo = algo
        self._hasher = bitrot_mod.new_hasher(algo)
        self._buf = io.BytesIO()
        self.closed = False

    def write(self, block: bytes) -> None:
        self._hasher.update(block)
        self._buf.write(block)

    def write_with_digest(self, block: bytes, digest: bytes) -> None:
        # whole-file algos hash the entire shard; a per-block digest from
        # the batched hasher can't be used — rehash into the running state
        self.write(block)

    def write_frames(self, blocks, digests) -> tuple[int, bool]:
        for block in blocks:
            self.write(block)
        return 0, False

    def close(self) -> None:
        if self.closed:
            return
        data = self._buf.getvalue()
        self.disk.create_file(self.volume, self.path, len(data),
                              io.BytesIO(data))
        self.closed = True

    def digest(self) -> bytes:
        return self._hasher.digest()


class StreamingBitrotReader:
    """Verified positional reads of shard blocks.

    read_at(offset, length): offset/length are in *payload* coordinates;
    the frame location on disk is derived from the shard size
    (cmd/bitrot-streaming.go:111-150)."""

    def __init__(self, disk: StorageAPI, volume: str, path: str,
                 till_offset: int, algo: BitrotAlgorithm, shard_size: int):
        self.disk, self.volume, self.path = disk, volume, path
        self.algo, self.shard_size = algo, shard_size
        # till_offset is in payload coords; on-disk adds digest framing
        self.till_offset = bitrot_mod.bitrot_shard_file_size(
            till_offset, shard_size, algo)
        self._stream: Optional[io.BufferedReader] = None
        self._pos = -1  # next on-disk offset the stream will yield

    def read_at(self, offset: int, length: int) -> bytes:
        """Read payload bytes [offset, offset+length) — must be
        block-aligned (offset % shard_size == 0), like the reference.
        Verifies every frame before returning."""
        out = bytearray()
        for digest, block in self.read_frames(offset, length):
            got = bitrot_mod.hash_shard(block, self.algo)
            if got != digest:
                raise errors.BitrotHashMismatch(digest.hex(), got.hex())
            out += block
        return bytes(out)

    def read_frames(self, offset: int, length: int
                    ) -> list[tuple[bytes, bytes]]:
        """Raw (expected_digest, payload) frames WITHOUT verifying — the
        deferred-verify seam for the fused device path: the engine batches
        many shards' frames into one device program that hashes and
        reconstructs together (models/pipeline.get_step), then compares
        digests host-side. Callers that don't batch must use read_at."""
        if length == 0:
            return []
        if offset % self.shard_size:
            raise errors.UnexpectedError(
                f"unaligned bitrot read at {offset}")
        block_idx = offset // self.shard_size
        disk_off = block_idx * (self.algo.digest_size + self.shard_size)
        if self._stream is None or disk_off != self._pos:
            if self._stream is not None:
                self._stream.close()
            self._stream = self.disk.read_file_stream(
                self.volume, self.path, disk_off,
                self.till_offset - disk_off)
            self._pos = disk_off

        frames: list[tuple[bytes, bytes]] = []
        remaining = length
        while remaining > 0:
            digest = self._read_exact(self.algo.digest_size)
            n = min(self.shard_size, remaining)
            block = self._read_exact(n)
            self._pos += self.algo.digest_size + n
            frames.append((digest, block))
            remaining -= n
        return frames

    def _read_exact(self, n: int) -> bytes:
        assert self._stream is not None
        buf = b""
        while len(buf) < n:
            chunk = self._stream.read(n - len(buf))
            if not chunk:
                raise errors.FileCorrupt(
                    f"{self.path}: truncated bitrot frame")
            buf += chunk
        return buf

    def close(self) -> None:
        if self._stream is not None:
            self._stream.close()
            self._stream = None


class WholeBitrotReader:
    """Reads the whole shard once, verifies the single digest, then serves
    positional reads from memory (reference wholeBitrotReader uses a
    ReadFile verifier; shard files are small enough per part)."""

    def __init__(self, disk: StorageAPI, volume: str, path: str,
                 algo: BitrotAlgorithm, expected_digest: bytes,
                 shard_size: int):
        self.disk, self.volume, self.path = disk, volume, path
        self.algo, self.expected = algo, expected_digest
        self.shard_size = shard_size
        self._data: Optional[bytes] = None

    def read_at(self, offset: int, length: int) -> bytes:
        if self._data is None:
            data = self.disk.read_all(self.volume, self.path)
            if self.expected:
                got = bitrot_mod.hash_shard(data, self.algo)
                if got != self.expected:
                    raise errors.BitrotHashMismatch(
                        self.expected.hex(), got.hex())
            self._data = data
        return self._data[offset:offset + length]

    def close(self) -> None:
        self._data = None
