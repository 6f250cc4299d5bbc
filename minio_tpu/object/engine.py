"""ErasureObjects — object CRUD on one erasure set.

The per-set object engine (reference erasureObjects, cmd/erasure-object.go
+ cmd/erasure.go): N = k+m drives, every object's shards distributed by
hashOrder, xl.meta written to all drives, quorum-checked reads/writes,
2-phase commit through .minio.sys/tmp.

TPU-first deltas vs the reference's per-block loop:
  * The PUT hot loop aggregates up to ENCODE_BATCH_BLOCKS full blocks and
    encodes + bitrot-digests them as one fused device program
    (cmd/erasure-encode.go's block loop + cmd/bitrot-streaming.go,
    batched for the MXU/VPU); the cross-request scheduler coalesces
    concurrent streams into shared dispatches.
  * Degraded GETs read GET_BATCH_BLOCKS blocks per group and
    batch verify+reconstruct every block sharing an erasure pattern in
    one fused device dispatch (cmd/erasure-decode.go:111-211 semantics
    — see _verify_and_reconstruct_group).
  * MD5/ETag runs on a background thread overlapped with encode — the
    generalized QAT async-MD5 pattern (cmd/erasure-encode.go:113-124).
"""

from __future__ import annotations

import os
import threading
import time
import uuid as _uuid
from typing import BinaryIO, Iterator, Optional

import numpy as np

from .. import bitrot as bitrot_mod
from ..storage import errors as serr
from ..utils import crashpoint, healthtrack, knobs, telemetry
from ..storage.api import StorageAPI
from ..storage.datatypes import (BLOCK_SIZE_V1, RESTORE_EXPIRY_KEY,
                                 RESTORE_KEY, TRANSITION_COMPLETE,
                                 TRANSITION_STATUS_KEY,
                                 TRANSITION_TIER_KEY,
                                 TRANSITIONED_OBJECT_KEY,
                                 TRANSITIONED_VERSION_KEY, ChecksumInfo,
                                 FileInfo, ObjectInfo, is_restored,
                                 is_transitioned, last_version_marker,
                                 new_file_info, now)
from ..storage.xl_storage import (MINIO_META_BUCKET,
                                  MINIO_META_MULTIPART_BUCKET,
                                  MINIO_META_TMP_BUCKET)
from . import api_errors, bitrot_io, metadata as meta
from .codec import Codec
from .hash_reader import HashReader
from .nslock import NSLockMap

ENCODE_BATCH_BLOCKS = knobs.get_int("MINIO_TPU_ENCODE_BATCH")
GET_BATCH_BLOCKS = knobs.get_int("MINIO_TPU_GET_BATCH")


def _sse_pkg() -> int:
    """features/crypto.PKG_SIZE without a module-level crypto import
    (crypto pulls optional deps the bare engine must not require)."""
    from ..features.crypto import PKG_SIZE
    return PKG_SIZE

# Reserved bucket names an S3 client can't touch.
RESERVED_BUCKETS = (MINIO_META_BUCKET,)


class PutOptions:
    def __init__(self, metadata: Optional[dict] = None,
                 version_id: str = "", versioned: bool = False,
                 parity: Optional[int] = None,
                 mod_time: Optional[float] = None,
                 if_none_newer: bool = False,
                 sse_spec=None):
        self.metadata = dict(metadata or {})
        self.version_id = version_id
        self.versioned = versioned
        self.parity = parity
        # features/crypto.DeviceSSE for the fused cipher+RS+digest PUT
        # path: the reader then carries PLAINTEXT and the engine
        # ciphers in-batch, appending the Poly1305 tag trailer at
        # stream end (None = any cipher ran as a reader transform)
        self.sse_spec = sse_spec
        # explicit mod time: server-side copies (rebalance pool moves)
        # preserve the object's original Last-Modified instead of
        # stamping the move time
        self.mod_time = mod_time
        # replication apply of the UNVERSIONED slot: commit only when
        # no existing null version is (mod_time, version_id)-newer —
        # evaluated INSIDE the per-key write lock, so a client write
        # racing the apply can never be clobbered by an older replica
        # (PreConditionFailed otherwise; the check-then-put a caller
        # could do itself is a TOCTOU hole)
        self.if_none_newer = if_none_newer


class GetOptions:
    def __init__(self, version_id: str = ""):
        self.version_id = version_id


# 1 - close fan-outs / commits is the share of PUTs whose writers
# closed in their last shard write
_PUT_COMMITS = telemetry.REGISTRY.counter(
    "minio_tpu_put_commits_total",
    "Single-part PUT commits begun (shards written, under the lock)")
_PUT_CLOSE_FANOUTS = telemetry.REGISTRY.counter(
    "minio_tpu_put_close_fanouts_total",
    "Single-part PUT commits that closed their shard writers in a "
    "fan-out of their own: no group was known to be the last (a 0-byte "
    "object, or a stream of unknown length that ended on a group)")

_GET_STREAMS = None


def _get_streams_counter():
    """Resolved once — the registry lookup takes the global metrics
    mutex, which the per-GET hot path must not contend on."""
    global _GET_STREAMS
    if _GET_STREAMS is None:
        _GET_STREAMS = telemetry.REGISTRY.counter(
            "minio_tpu_erasure_get_streams_total",
            "Object read streams served through the erasure "
            "shard-read/verify/decode path")
    return _GET_STREAMS


class ErasureObjects:
    """One erasure set over `disks` (k data + m parity)."""

    def __init__(self, disks: list[Optional[StorageAPI]],
                 data_shards: int, parity_shards: int,
                 block_size: int = BLOCK_SIZE_V1,
                 ns_lock: Optional[NSLockMap] = None,
                 bitrot_algo: bitrot_mod.BitrotAlgorithm =
                 bitrot_mod.DEFAULT_BITROT_ALGORITHM,
                 set_index: int = 0,
                 scheduler=None):
        assert len(disks) == data_shards + parity_shards
        self.disks = disks
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.block_size = block_size
        self.bitrot_algo = bitrot_algo
        self.ns = ns_lock or NSLockMap()
        self.set_index = set_index
        # optional cross-request batch former (parallel/scheduler.py)
        self.scheduler = scheduler
        self._codec_cache: dict[tuple[int, int], Codec] = {}
        # MRF hook: called (bucket, object) when a GET had to reconstruct
        # or hit bitrot — the sets layer queues a heal (reference
        # deepHealObject trigger, cmd/erasure-object.go:298-303)
        self.on_degraded_read = None
        # MRF hook: called (bucket, object, version_id) when a write
        # (PUT / delete / metadata) met quorum but some drives failed —
        # the degraded object regains full redundancy via the background
        # heal queue instead of waiting for the next scanner sweep
        # (reference maintainMRFList, cmd/erasure-sets.go:1641)
        self.on_degraded_write = None
        # metacache hook: called (bucket, object) after EVERY successful
        # namespace mutation (PUT / delete / delete marker / transition
        # / metadata update / multipart commit) — feeds the persisted
        # bucket index's delta journal (object/metacache.py). Must never
        # block: the receiver only appends to a bounded queue.
        self.on_namespace_change = None

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------

    def codec(self, k: int, m: int) -> Codec:
        key = (k, m)
        if key not in self._codec_cache:
            self._codec_cache[key] = Codec(k, m, self.block_size)
        return self._codec_cache[key]

    @property
    def supports_sse_device(self) -> bool:
        """Whether this layer can run the fused cipher+RS+digest PUT
        path (PutOptions.sse_spec): the package stream must tile the
        erasure blocks exactly, so full blocks carry whole ChaCha20
        packages through the batch former."""
        from ..features.crypto import PKG_SIZE
        return self.block_size % PKG_SIZE == 0

    def get_disks(self) -> list[Optional[StorageAPI]]:
        return list(self.disks)

    def _default_quorums(self, parity: Optional[int] = None
                         ) -> tuple[int, int, int, int]:
        """(data, parity, readQuorum, writeQuorum) for a fresh object
        (cmd/erasure-object.go:536-547)."""
        m = self.parity_shards if parity is None else parity
        k = len(self.disks) - m
        return k, m, k, meta.write_quorum_for(k, m)

    # ------------------------------------------------------------------
    # bucket ops (cmd/erasure-bucket.go)
    # ------------------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        if bucket in RESERVED_BUCKETS or not bucket:
            raise api_errors.BucketNameInvalid(bucket)
        _, errs = meta.for_each_disk(
            self.disks, lambda i, d: d.make_vol(bucket))
        write_quorum = len(self.disks) // 2 + 1
        exists = sum(1 for e in errs if isinstance(e, serr.VolumeExists))
        if exists >= write_quorum:
            raise api_errors.BucketExists(bucket)
        ok = sum(1 for e in errs
                 if e is None or isinstance(e, serr.VolumeExists))
        if ok < write_quorum:
            err = meta.reduce_write_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS + (serr.VolumeExists,),
                write_quorum)
            raise api_errors.to_object_err(
                err or api_errors.InsufficientWriteQuorum(), bucket)

    def bucket_exists(self, bucket: str) -> bool:
        try:
            self.get_bucket_info(bucket)
            return True
        except api_errors.BucketNotFound:
            return False

    def get_bucket_info(self, bucket: str):
        results, errs = meta.for_each_disk(
            self.disks, lambda i, d: d.stat_vol(bucket), stage="stat_vol")
        read_quorum = len(self.disks) // 2
        err = meta.reduce_read_quorum_errs(
            errs, meta.OBJECT_OP_IGNORED_ERRS, read_quorum)
        if err is not None:
            raise api_errors.to_object_err(err, bucket)
        for r in results:
            if r is not None:
                return r
        raise api_errors.BucketNotFound(bucket)

    def list_buckets(self):
        """Quorum-merged bucket listing: a bucket counts when a majority
        of drives have its volume — a stale drive that missed a
        make_bucket (or kept a deleted one) while offline can neither
        hide nor resurrect a bucket (reference merges per-disk listings,
        cmd/erasure-sets.go ListBuckets semantics)."""
        counts: dict[str, int] = {}
        infos: dict[str, object] = {}
        answered = 0
        for d in self.disks:
            if d is None:
                continue
            try:
                vols = d.list_vols()
            except serr.StorageError:
                continue
            answered += 1
            for v in vols:
                if v.name.startswith("."):
                    continue
                counts[v.name] = counts.get(v.name, 0) + 1
                prev = infos.get(v.name)
                if prev is None or v.created < prev.created:
                    infos[v.name] = v
        if answered == 0:
            return []
        # read quorum n//2 intersects the n//2+1 write quorum: a bucket
        # created under write quorum stays listed with up to half the
        # drives unreachable (review r3: n//2+1 here could hide a
        # healthy bucket when one writer drive is down)
        quorum = min(answered, max(1, len(self.disks) // 2))
        return sorted((infos[n] for n, c in counts.items()
                       if c >= quorum), key=lambda v: v.name)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        def rm(i, d):
            try:
                d.delete_vol(bucket, force)
            except serr.VolumeNotFound:
                pass

        _, errs = meta.for_each_disk(self.disks, rm)
        write_quorum = len(self.disks) // 2 + 1
        err = meta.reduce_write_quorum_errs(
            errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise api_errors.to_object_err(err, bucket)

    # ------------------------------------------------------------------
    # PUT (cmd/erasure-object.go:521-703 + cmd/erasure-encode.go)
    # ------------------------------------------------------------------

    def put_object(self, bucket: str, object_name: str, reader,
                   size: int = -1, opts: Optional[PutOptions] = None
                   ) -> ObjectInfo:
        """Write one object at write quorum. Raises `BucketNotFound`
        when the bucket is missing: nothing stats it first — each
        drive's `rename_data` refuses a volume it does not hold, and the
        commit's rename fan-out reduces those refusals at write quorum
        (the body has then been read and encoded, and the staging
        directories are removed)."""
        with telemetry.span("engine.put_object", bucket=bucket,
                            object=object_name, size=size):
            return self._put_object(bucket, object_name, reader, size,
                                    opts)

    def _put_object(self, bucket: str, object_name: str, reader,
                    size: int = -1, opts: Optional[PutOptions] = None
                    ) -> ObjectInfo:
        opts = opts or PutOptions()
        if isinstance(reader, (bytes, bytearray)):
            import io as _io
            size = len(reader)
            reader = HashReader(_io.BytesIO(reader), size)
        elif not isinstance(reader, HashReader):
            reader = HashReader(reader, size)

        k, m, _, write_quorum = self._default_quorums(opts.parity)
        fi = new_file_info(f"{bucket}/{object_name}", k, m)
        fi.erasure.block_size = self.block_size
        fi.volume, fi.name = bucket, object_name
        fi.data_dir = str(_uuid.uuid4())
        if opts.versioned:
            fi.version_id = opts.version_id or str(_uuid.uuid4())

        shuffled = meta.shuffle_disks(self.disks, fi.erasure.distribution)
        tmp_id = str(_uuid.uuid4())
        part_path = f"{tmp_id}/{fi.data_dir}/part.1"
        codec = self.codec(k, m)
        shard_size = codec.shard_size

        writers: list[Optional[object]] = []
        for d in shuffled:
            if d is None:
                writers.append(None)
                continue
            writers.append(bitrot_io.new_bitrot_writer(
                d, MINIO_META_TMP_BUCKET, part_path, -1,
                self.bitrot_algo, shard_size))

        try:
            try:
                total = self._encode_stream(reader, codec, writers,
                                            write_quorum, bucket,
                                            object_name,
                                            sse=opts.sse_spec)
                # the async hasher drains here: a wait on MD5/SHA-256
                with telemetry.span("put.hash_verify"):
                    reader.verify()
            finally:
                reader.close()  # stop the async hasher even on failure
            etag = opts.metadata.pop("etag", "") or reader.md5_current_hex()

            fi.size = total
            fi.mod_time = opts.mod_time if opts.mod_time else now()
            fi.metadata = dict(opts.metadata)
            fi.metadata["etag"] = etag
            fi.add_object_part(1, etag, total,
                               reader.actual_size
                               if reader.actual_size >= 0 else total)
            fi.erasure.checksums = [
                ChecksumInfo(1, self.bitrot_algo.value, b"")]

            # per-drive metadata then commit (2-phase: tmp -> final)
            with telemetry.span("put.commit"), self.ns.new_lock(
                    f"{bucket}/{object_name}").write_locked():
                if opts.if_none_newer:
                    self._check_none_newer(bucket, object_name, fi)
                lost = self._commit(shuffled, writers, tmp_id, fi,
                                    bucket, object_name, write_quorum)
        except Exception:
            self._cleanup_tmp(shuffled, tmp_id)
            raise
        if lost:
            # quorum met but some drives missed the write: queue an MRF
            # heal so the object converges back to full redundancy
            self._notify_degraded(bucket, object_name, fi.version_id)
        self._notify_namespace(bucket, object_name)
        return fi.to_object_info(bucket, object_name)

    def _check_none_newer(self, bucket: str, object_name: str,
                          fi: FileInfo) -> None:
        """The if_none_newer commit gate (caller holds the write
        lock): an existing version in the same slot that wins the
        deterministic (mod_time, version_id, etag) conflict rule
        aborts the commit — the replication apply's atomic
        last-writer-wins. The etag tie-break keeps two sites that
        wrote DIFFERENT bytes at the same instant convergent (a full
        tie is identical content, so either copy is fine)."""
        for cur in self._merged_versions(bucket, object_name):
            if (cur.version_id or "") != (fi.version_id or ""):
                continue
            cur_key = (cur.mod_time or 0, cur.version_id or "",
                       cur.metadata.get("etag", ""))
            new_key = (fi.mod_time or 0, fi.version_id or "",
                       fi.metadata.get("etag", ""))
            if cur_key >= new_key:
                raise api_errors.PreConditionFailed(
                    f"{bucket}/{object_name}: existing version is newer")
            return

    def _encode_stream(self, reader, codec: Codec, writers,
                       write_quorum: int, bucket: str,
                       object_name: str, sse=None) -> int:
        """The PUT hot loop: read blocks, batch-encode, batch-hash,
        fan-out framed writes. Returns total bytes.

        Two selectable forms (MINIO_TPU_PIPELINE, default on): the
        pipelined loop overlaps ingest / encode+digest / shard writes
        across a staging-buffer ring; the serial loop runs them
        strictly in sequence on this thread. Streams that fit in ONE
        encode batch stay serial even with the pipeline on — a single
        batch has nothing to overlap, so the stage hand-off would be
        pure latency.

        With `sse` (a features/crypto.DeviceSSE), the reader carries
        PLAINTEXT and the cipher fuses into the encode dispatch: full
        blocks ride the batch former as cipher+RS+digest launches, the
        Poly1305 tag trailer (computed host-side over the returned
        ciphertext) lands at stream end, and the returned total is the
        STORED size (ciphertext + trailer). Any decline or dispatch
        error drops that batch to the in-place CPU cipher — the bytes
        on disk are identical either way."""
        from ..parallel import pipeline as pl
        size = getattr(reader, "size", -1)
        if pl.ENABLED and (size < 0
                           or size > ENCODE_BATCH_BLOCKS
                           * self.block_size):
            return self._encode_stream_pipelined(reader, codec, writers,
                                                 write_quorum, sse=sse)
        return self._encode_stream_serial(reader, codec, writers,
                                          write_quorum, sse=sse)

    def _encode_stream_pipelined(self, reader, codec: Codec, writers,
                                 write_quorum: int, sse=None) -> int:
        """The PUT hot loop, overlapped (the fork's async-QAT pattern,
        cmd/erasure-encode.go:113-124, applied to the WHOLE path): a
        ring of BytePool-backed (B, k·S) staging buffers carries three
        concurrent stages —

          * this thread ingests batch N+1 straight into a pooled buffer
            (and fire-and-forgets the device dispatch for it via
            BatchScheduler.submit, so the reader never blocks on the
            device),
          * the encode stage resolves batch N's fused encode+digest
            (or runs the local CPU fallback),
          * the write stage fans batch N-1's framed shard writes out.

        Bounded stage queues + the shared buffer ring are the memory
        bound: a stalled drive backs pressure up to the reader instead
        of ballooning staging RAM. Same bytes on disk as the serial
        loop — the pad tail [block_size:k·S] of every row is re-zeroed
        on each buffer acquisition (klauspost-identical shard bytes are
        invariant by construction, not by write discipline). The stage
        threads spin up lazily on the FIRST full batch, so an
        unknown-length stream that turns out to fit one batch encodes
        and writes inline with zero pipeline overhead."""
        from ..parallel import pipeline as pl
        k, s_len = codec.k, codec.shard_size
        bs = self.block_size
        cap = ENCODE_BATCH_BLOCKS
        known_size = getattr(reader, "size", -1)
        pool = pl.staging_pool(cap * k * s_len)
        # per-stage wall seconds [ingest, encode, write]; each slot is
        # written by exactly one thread
        stage_s = [0.0, 0.0, 0.0]
        batches = 0
        t_start = time.perf_counter()

        def recycle(item) -> None:
            buf = item.get("buf")
            if buf is not None:
                item["buf"] = None
                pool.put(buf)

        def encode_stage(item):
            if item.get("sse_finish"):
                # stream end under SSE: encrypt the short tail (if any)
                # host-side, close the Poly1305 trailer, and re-chunk
                # ct_tail‖trailer into block-size erasure batches. Runs
                # on this FIFO stage so every prior batch has absorbed.
                with telemetry.timed("put.sse_finish") as t:
                    item["rows_multi"] = self._sse_finish_rows(
                        codec, sse, item["tail"], item["sse_off"])
                stage_s[1] += t.seconds
                return item
            with telemetry.timed("pipeline.encode",
                                 blocks=item["data"].shape[0]) as t:
                fut, data = item["fut"], item["data"]
                if sse is not None:
                    item["rows"] = self._sse_encode(codec, data, item,
                                                    fut, sse)
                else:
                    lengths = item["lengths"]
                    item["rows"] = self._unpack_fused(
                        codec, data,
                        self._fused_encode(codec, data, fut, lengths),
                        lengths=lengths)
            stage_s[1] += t.seconds
            return item

        def write_stage(item):
            t = telemetry.timed("pipeline.shard_write")
            try:
                with t:
                    groups = item["rows_multi"] if "rows_multi" in item \
                        else [item["rows"]]
                    for j, (rows, parity, dd, dp) in enumerate(groups):
                        self._write_shards_batch(
                            rows, parity, dd, dp, writers, write_quorum,
                            lengths=item.get("lengths"),
                            last=item["last"] and j == len(groups) - 1)
            finally:
                recycle(item)
                stage_s[2] += t.seconds

        pipe = None

        def feed(data, lengths=None, last=False) -> None:
            """Hand the CURRENT buffer (if any) plus `data` to the
            pipeline, spinning the stage threads up on first use.
            `lengths`: of the stream's last group, when it ends in a
            short block (`_lay_short_block`). `last`: no group follows,
            so the write stage closes the writers in its fan-out.
            Buffer ownership transfers to the item BEFORE submit — if
            submit raises a pending stage error, on_drop recycles the
            item's buffer and the caller's finally must not recycle it
            again (a double pool.put would hand one bytearray to two
            later streams)."""
            nonlocal batches, buf, pipe, enc_off
            reads.flush(blocks=int(data.shape[0]))
            if pipe is None:
                pipe = pl.StagePipeline([encode_stage, write_stage],
                                        depth=pl.DEPTH, name="put-pipe",
                                        on_drop=recycle)
            owned, buf = buf, None
            item = {"buf": owned, "data": data, "lengths": lengths,
                    "last": last}
            if sse is not None:
                # per-row key/nonce word arrays ride the dispatch; the
                # bucket key carries only their shape, so concurrent
                # encrypted PUTs coalesce into one launch
                kn = sse.batch_params(enc_off, data.shape[0], bs)
                item["sse_kn"], item["sse_off"] = kn, enc_off
                enc_off += data.shape[0] * bs
                fut = (self.scheduler.submit(
                    codec, data, self.bitrot_algo,
                    sse=(kn[0], kn[1], _sse_pkg()))
                    if self.scheduler is not None else None)
            else:
                fut = (self.scheduler.submit(codec, data,
                                             self.bitrot_algo,
                                             lengths=lengths)
                       if self.scheduler is not None else None)
            item["fut"] = fut
            pipe.submit(item)
            batches += 1

        def acquire():
            # back-pressure: the ring is empty while the encode and
            # write stages still hold every buffer
            with telemetry.timed("put.buffer_wait") as t:
                b = pool.get(timeout=pl.POOL_TIMEOUT_S)
            stage_s[0] += t.seconds
            a = np.frombuffer(b, dtype=np.uint8).reshape(cap, k * s_len)
            if k * s_len > bs:
                # pooled reuse: the pad tail must READ as zeros for
                # klauspost-identical shards — enforce it here rather
                # than trusting every writer of this ring forever
                a[:, bs:] = 0
            return b, a

        total = 0
        buf = None
        enc_off = 0       # plaintext stream offset of the next sse batch
        tail_pt = b""     # short last block (plaintext) under sse
        lengths = None    # of the last group, when it ends short
        sub = None        # the one block of a body under one block
        # pulling the body through the hash reader into the ring: one
        # span per group of blocks (busy time + call count), not one
        # per block
        reads = telemetry.accum("put.read_stream")
        try:
            buf, arr = acquire()
            nb = 0
            while True:
                t0 = time.perf_counter_ns()
                n = _read_full_into(reader, arr[nb][:bs])
                t1 = time.perf_counter_ns()
                stage_s[0] += (t1 - t0) / 1e9
                reads.add(t0, t1)
                if n == 0:
                    break
                total += n
                if n == bs:
                    nb += 1
                    if nb == cap:
                        at_end = 0 <= known_size == total
                        feed(arr[:nb].reshape(nb, k, s_len),
                             last=at_end and sse is None)
                        nb = 0
                        if at_end:
                            # exact batch multiple: EOF is certain, so
                            # don't block on a probe buffer the stream
                            # will never write into
                            break
                        buf, arr = acquire()
                else:
                    if sse is not None:
                        # short last block under SSE: it joins the tag
                        # trailer in the finish batches — the pending
                        # full rows flush below, then the finish runs
                        # after them in stage FIFO order
                        tail_pt = bytes(arr[nb][:n])
                        break
                    if total == n:
                        # the whole body is under one block: one block
                        # at its S rung, encoded and written below
                        sub, lengths = _lay_subblock(arr[0], n, k, s_len)
                        break
                    # short last block: it is the last row of the
                    # group of the whole blocks before it, and goes
                    # with them below
                    lengths = _lay_short_block(arr, nb, n, k, s_len)
                    nb += 1
                    break
            reads.flush(blocks=nb or int(sub is not None))
            # the read loop has ended (EOF, or a short block): without
            # SSE the group below is the stream's last; under SSE the
            # last of the finish batches is
            if sub is not None:
                self._encode_write(codec, sub, writers, write_quorum,
                                   lengths=lengths, last=True,
                                   subblock=True)
            elif nb:
                if pipe is None:
                    # a stream that fit one batch (an unknown-length
                    # one too): encode+write inline, no stage threads
                    self._encode_write(codec,
                                       arr[:nb].reshape(nb, k, s_len),
                                       writers, write_quorum,
                                       sse=sse, sse_off=enc_off,
                                       lengths=lengths, last=sse is None)
                    enc_off += nb * bs
                else:
                    feed(arr[:nb].reshape(nb, k, s_len), lengths,
                         last=sse is None)
            if sse is not None:
                if pipe is None:
                    self._write_sse_finish(codec, sse, tail_pt, enc_off,
                                           writers, write_quorum)
                else:
                    pipe.submit({"sse_finish": True, "tail": tail_pt,
                                 "sse_off": enc_off, "last": True})
            if pipe is not None:
                pipe.close()    # join; re-raises the first stage error
        except BaseException:
            if pipe is not None:
                pipe.close(abort=True)
            raise
        finally:
            if buf is not None:
                pool.put(buf)
        if pipe is not None:
            wall = time.perf_counter() - t_start
            pl.STATS.record_put(wall, sum(stage_s), batches)
        if sse is not None:
            from ..features.crypto import encrypted_size
            return encrypted_size(total)   # ciphertext + tag trailer
        return total

    def _encode_stream_serial(self, reader, codec: Codec, writers,
                              write_quorum: int, sse=None) -> int:
        """The serial PUT hot loop (MINIO_TPU_PIPELINE=off).

        Copy discipline (the fork's zero-copy QAT ingest,
        cmd/erasure-encode.go:102-124, generalized): blocks are read
        straight into a padded (B, k*S) buffer so the shard split is a
        reshape VIEW, the data shards are written from that same buffer,
        and only the parity rows are newly allocated. The old path
        copied every byte 3 extra times (concat, split, stack)."""
        total = 0
        k, s_len = codec.k, codec.shard_size
        bs = self.block_size
        cap = ENCODE_BATCH_BLOCKS
        # zero-initialized: the pad tail (k*S - block_size bytes) must
        # read as zeros for klauspost-identical shard bytes, and full
        # blocks never write into it
        buf = np.zeros((cap, k * s_len), dtype=np.uint8)
        nb = 0
        enc_off = 0
        tail_pt = b""
        lengths = None    # of the last group, when it ends short
        known_size = getattr(reader, "size", -1)
        reads = telemetry.accum("put.read_stream")

        def flush_full(n_rows: int, last: bool) -> None:
            nonlocal enc_off
            reads.flush(blocks=n_rows)
            if n_rows:
                self._encode_write(codec,
                                   buf[:n_rows].reshape(n_rows, k, s_len),
                                   writers, write_quorum,
                                   sse=sse, sse_off=enc_off,
                                   lengths=lengths,
                                   last=last and sse is None)
                enc_off += n_rows * bs

        while True:
            row = buf[nb]
            t0 = time.perf_counter_ns()
            n = _read_full_into(reader, row[:bs])
            reads.add(t0, time.perf_counter_ns())
            if n == 0:
                break
            total += n
            if n == bs:
                nb += 1
                if nb == cap:
                    at_end = 0 <= known_size == total
                    flush_full(nb, at_end)
                    nb = 0
                    if at_end:
                        break
            else:
                if sse is not None:
                    # short last block under SSE joins the tag trailer
                    # in the finish batches (after flush_full below)
                    tail_pt = bytes(row[:n])
                    break
                if total == n:
                    # the whole body is under one block: ONE block at
                    # its S rung, never a row of a full-S group
                    reads.flush(blocks=1)
                    data, lengths = _lay_subblock(row, n, k, s_len)
                    self._encode_write(codec, data, writers, write_quorum,
                                       lengths=lengths, last=True,
                                       subblock=True)
                    return total
                # short last block: the last row of the group of the
                # whole blocks before it — one encode, one fan-out
                lengths = _lay_short_block(buf, nb, n, k, s_len)
                nb += 1
                break
        flush_full(nb, True)
        if sse is not None:
            from ..features.crypto import encrypted_size
            self._write_sse_finish(codec, sse, tail_pt, enc_off, writers,
                                   write_quorum)
            return encrypted_size(total)
        return total

    def _fused_encode(self, codec: Codec, data: np.ndarray, fut=None,
                      lengths=None, subblock: bool = False):
        """(parity, digests) of one plain batch off the device — from
        its future, the shared batch former, or the codec itself when
        the engine runs without a former — or None (local CPU path).
        `lengths`: of a group that ends in a short block; `subblock`:
        the one block of a body under one block, at its S rung."""
        if fut is not None:
            # check: allow(deadline) device dispatch; scheduler close() flushes waiters
            return fut.result()
        if self.scheduler is not None:
            # the cross-request scheduler coalesces concurrent PUT
            # streams into shared dispatches
            return self.scheduler.encode_and_hash(
                codec, data, self.bitrot_algo, lengths=lengths,
                subblock=subblock)
        return codec.encode_and_hash_batch(data, self.bitrot_algo,
                                           lengths=lengths,
                                           subblock=subblock)

    def _unpack_fused(self, codec: Codec, data: np.ndarray, fused,
                      ciphertext: bool = False, lengths=None
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
        """(data_rows, parity, data_digests, parity_digests) from one
        fused encode+digest result, or the local CPU fallback when the
        batch didn't ride the device (`fused` is None). A plain result
        is (parity, digests): the data rows stay views of the caller's
        staging buffer, on the device path as on the CPU path. Under
        SSE (`ciphertext`) it is (full, digests) and the data rows are
        the ciphertext the device made. `lengths`: of a group that ends
        in a short block — the CPU fallback encodes and hashes that row
        over its own length, so the bytes `_write_shards_batch` frames
        are the same on every route."""
        if fused is not None:
            rows, digests = fused
            dd, dp = digests[:, :codec.k], digests[:, codec.k:]
            if ciphertext:
                return rows[:, :codec.k], rows[:, codec.k:], dd, dp
            return data, rows, dd, dp
        if lengths is None or lengths[-1] == data.shape[2]:
            return (data, *self._host_encode(codec, data))
        # the whole rows together, then the short row over its own
        # length; its parity lies zero-padded among the others'
        b_, s_len = data.shape[0], data.shape[2]
        parts = [self._host_encode(
            codec, np.ascontiguousarray(data[lo:hi, :, :cols]))
            for lo, hi, cols in ((0, b_ - 1, s_len),
                                 (b_ - 1, b_, int(lengths[-1])))
            if lo < hi]
        parity = np.zeros((b_, codec.m, s_len), dtype=np.uint8)
        at = 0
        for part, _dd, _dp in parts:
            parity[at:at + part.shape[0], :, :part.shape[2]] = part
            at += part.shape[0]
        return (data, parity, np.concatenate([p[1] for p in parts]),
                np.concatenate([p[2] for p in parts]))

    def _host_encode(self, codec: Codec, data: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(parity, data_digests, parity_digests) of one (B, k, S)
        batch on the local CPU path."""
        b_ = data.shape[0]
        parity = codec.encode_parity_batch(data)
        dd = bitrot_mod.hash_shards_batch(
            data.reshape(b_ * codec.k, -1), self.bitrot_algo
        ).reshape(b_, codec.k, -1)
        if codec.m:
            dp = bitrot_mod.hash_shards_batch(
                parity.reshape(b_ * codec.m, -1), self.bitrot_algo
            ).reshape(b_, codec.m, -1)
        else:
            dp = np.zeros((b_, 0, dd.shape[-1]), dtype=np.uint8)
        return parity, dd, dp

    def _encode_write(self, codec: Codec, data: np.ndarray, writers,
                      write_quorum: int, sse=None, sse_off: int = 0,
                      lengths=None, last: bool = False,
                      subblock: bool = False) -> None:
        """Encode+digest one (B, k, S) batch and fan the framed shard
        writes out — data rows go to the writers as views of `data`.
        With `sse`, the batch rows are PLAINTEXT full blocks starting
        at stream offset `sse_off` and the cipher fuses in (or falls
        back to the in-place CPU cipher). `lengths`: of a plain group
        that ends in a short block (`_lay_short_block`), or of the one
        block of a body under one block (`subblock`, laid at its S
        rung by `_lay_subblock`). `last`: the stream's last group
        (`_write_shards_batch`)."""
        attrs = {"subblock": 1, "S": data.shape[2]} if subblock else {}
        with telemetry.span("pipeline.encode", blocks=data.shape[0],
                            **attrs):
            if sse is not None:
                item = {"sse_kn": sse.batch_params(
                    sse_off, data.shape[0], self.block_size),
                    "sse_off": sse_off}
                fut = (self.scheduler.submit(
                    codec, data, self.bitrot_algo,
                    sse=(*item["sse_kn"], _sse_pkg()))
                    if self.scheduler is not None else None)
                data_rows, parity, dd, dp = self._sse_encode(
                    codec, data, item, fut, sse)
            else:
                # fused device encode+digest when routed there (one
                # program, one round-trip)
                data_rows, parity, dd, dp = self._unpack_fused(
                    codec, data,
                    self._fused_encode(codec, data, lengths=lengths,
                                       subblock=subblock),
                    lengths=lengths)
        with telemetry.span("pipeline.shard_write"):
            self._write_shards_batch(data_rows, parity, dd, dp, writers,
                                     write_quorum, lengths=lengths,
                                     last=last)

    def _sse_encode(self, codec: Codec, data: np.ndarray, item, fut,
                    sse) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                  np.ndarray]:
        """Resolve one SSE batch: fused device cipher+RS+digest result,
        or — on decline OR dispatch error — the in-place CPU cipher
        followed by the local encode path (byte-identical either way).
        Always absorbs the ciphertext into the Poly1305 tag trailer in
        stream order (the caller runs batches FIFO), so the tags are
        computed over the bytes actually committed — device output is
        re-authenticated host-side, never laundered."""
        bs = self.block_size
        b_ = data.shape[0]
        fused = None
        try:
            if fut is not None:
                # check: allow(deadline) device dispatch; scheduler close() flushes waiters
                fused = fut.result()
            else:
                keys, nonces = item["sse_kn"]
                fused = codec.encrypt_encode_and_hash_batch(
                    data, keys, nonces, _sse_pkg(), self.bitrot_algo)
        except Exception:
            fused = None    # dispatch error → CPU cipher fallback
        if fused is None:
            flat = data.reshape(b_, -1)
            sse.cpu_encrypt_rows(flat[:, :bs], item["sse_off"])
        rows = self._unpack_fused(codec, data, fused, ciphertext=True)
        ct = rows[0]        # (B, k, S): device output or encrypted buf
        for i in range(b_):
            sse.absorb(ct[i].reshape(-1)[:bs])
        return rows

    def _sse_finish_rows(self, codec: Codec, sse, tail_pt: bytes,
                         off: int) -> list:
        """Close an SSE stream: encrypt the short plaintext tail (CPU —
        partial blocks never ride the device), absorb it, close the tag
        trailer, and chunk ct_tail‖trailer into block-size erasure
        batches ready for _write_shards_batch. The trailer can exceed
        one block for huge objects, hence a list."""
        if tail_pt:
            arr = np.frombuffer(bytearray(tail_pt), dtype=np.uint8)
            sse.cpu_encrypt_tail(arr, off)
            sse.absorb(arr)
            stream = arr.tobytes() + sse.trailer()
        else:
            stream = sse.trailer()
        out = []
        bs = self.block_size
        for at in range(0, len(stream), bs):
            data = codec.split(stream[at:at + bs])[None, ...]
            out.append(self._unpack_fused(codec, data, None))
        return out

    def _write_sse_finish(self, codec: Codec, sse, tail_pt: bytes,
                          off: int, writers, write_quorum: int) -> None:
        """Write an SSE stream's finish batches in order; the last is
        the stream's last group."""
        groups = self._sse_finish_rows(codec, sse, tail_pt, off)
        for j, rows in enumerate(groups):
            self._write_shards_batch(*rows, writers, write_quorum,
                                     last=j == len(groups) - 1)

    def _write_shards_batch(self, data: np.ndarray, parity: np.ndarray,
                            dd: np.ndarray, dp: np.ndarray,
                            writers, write_quorum: int,
                            lengths=None, last: bool = False) -> None:
        """parallelWriter.Write, batched: writer i gets ALL B of its
        [digest‖block] frames in one call (cmd/erasure-encode.go:38-72's
        per-disk goroutine — but fanned out once per encode batch, not
        once per block: B× fewer pool tasks, and the frames are handed
        over as rows of the encode output, which a local drive takes in
        one vectored write, copy-free down to the kernel). Data and
        parity arrive as separate arrays so the data rows stay views of
        the read buffer. `lengths`: each block's own shard length, of a
        group that ends in a short block — its frame is
        [digest][lengths[b] bytes], in the same write as the others.
        `last`: the stream's last group — each drive's task closes its
        writer after the frames (the flush, and the fsync where it is
        on), so this fan-out's quorum is the commit's barrier: write
        quorum of drives hold every shard, closed, before any drive is
        told to rename. A close error is that drive's write error."""
        B, k = data.shape[0], data.shape[1]
        ends = [data.shape[2]] * B if lengths is None \
            else [int(n) for n in lengths]

        def write(i: int, w) -> None:
            rows, digs, j = (data, dd, i) if i < k else \
                (parity, dp, i - k)
            with telemetry.timed("disk.shard_write", disk=i,
                                 blocks=B) as t:
                # a row of the ring or of the fetched parity is
                # C-contiguous as it is; one that is not is copied
                # alone, never the group
                writes, vectored = w.write_frames(
                    [np.ascontiguousarray(rows[bi, j, :ends[bi]])
                     for bi in range(B)],
                    [np.ascontiguousarray(digs[bi, j]) for bi in range(B)])
                if last:
                    w.close()
                t.annotate(writes=writes, vectored=int(vectored),
                           closed=int(last))
            healthtrack.observe_disk(w.disk, "write", t.seconds)

        # quorum-ack lane: once write-quorum writers are durable, a
        # laggard past the stall grace is dropped from the fan-out
        # (and from every later batch, and from the rename, via
        # writers[i] = None below) — its missing shard heals through
        # MRF instead of setting p99
        _, errs = meta.for_each_disk_quorum(
            list(writers),  # type: ignore[arg-type]
            write, write_quorum, stall_s=healthtrack.write_stall_s(),
            stage="shard_write")
        for i, e in enumerate(errs):
            if e is not None:
                writers[i] = None
        live = sum(1 for w in writers if w is not None)
        if live < write_quorum:
            raise api_errors.InsufficientWriteQuorum(
                f"{live} live writers < quorum {write_quorum}")

    def _commit(self, shuffled, writers, tmp_id: str, fi: FileInfo,
                bucket: str, object_name: str, write_quorum: int) -> int:
        """Commit in ONE quorum fan-out, rename: each drive is handed
        its own version (no staged journal). Returns how many drives
        MISSED the commit (offline slot, dropped writer, or failed
        rename) — the MRF degraded-write signal. The barrier before it
        is the last shard-write fan-out, whose tasks closed the
        writers: below write quorum there, no drive was told to rename.
        Where no group was known to be the last (0 bytes, or a stream
        of unknown length that ended on a group), a fan-out of closes
        is that barrier."""
        # the whole commit window rides the quorum-ack lane: a drive
        # stalling at close/rename must not hold the client ack once
        # quorum is durable — it is counted into `lost` below and the
        # object converges back through MRF
        stall = healthtrack.write_stall_s()
        commit_span = telemetry.current_span()
        _PUT_COMMITS.inc()

        def fan_out(phase: str, disks, fn, **kw):
            with telemetry.span("put." + phase):
                _, errs = meta.for_each_disk_quorum(
                    disks, fn, write_quorum, stall_s=stall, stage=phase,
                    **kw)
            if commit_span is not None:
                commit_span.attrs["fanouts"] = \
                    commit_span.attrs.get("fanouts", 0) + 1
            return errs

        def live():
            return [d if writers[i] is not None else None
                    for i, d in enumerate(shuffled)]

        if any(w is not None and not w.closed for w in writers):
            _PUT_CLOSE_FANOUTS.inc()
            # flushes remaining frames (an empty file for 0 bytes)
            errs = fan_out("close", live(), lambda i, d: writers[i].close())
            for i, e in enumerate(errs):
                if e is not None:
                    writers[i] = None
            err = meta.reduce_write_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
            if err is not None:
                raise err
        # closed shards in tmp, no metadata anywhere new — a crash here
        # must leave the previous version untouched and only tmp
        # garbage for fsck to reclaim
        crashpoint.hit("put.shards.before_meta")
        metas = [fi.light_copy() for _ in range(len(shuffled))]
        for i, w in enumerate(writers):
            if w is None:
                continue
            metas[i].erasure.index = i + 1
            if not self.bitrot_algo.streaming:
                # whole-file digests are per-drive (each shard differs)
                for c in metas[i].erasure.checksums:
                    c.hash = w.digest()
        staged = live()
        # every version made, nothing committed: the rename fan-out is
        # the point of no return
        crashpoint.hit("put.meta.before_rename")

        def rename(i, d):
            # one hit per drive: arm :<nth> to die with n-1 drives
            # committed (torn below/at write quorum)
            crashpoint.hit("put.rename.partial", disk=i)
            # the drive is handed its version: the staging directory
            # holds the data dir alone, and no journal is read or made
            # there
            d.rename_data(MINIO_META_TMP_BUCKET, tmp_id, fi.data_dir,
                          bucket, object_name, fi=metas[i])

        def renamed_late(_i: int) -> None:
            # an abandoned rename that eventually LANDS may have laid
            # an OLDER version over a commit that happened after this
            # PUT acked — re-queue the MRF check now that it settled,
            # so the drive is healed against current quorum state
            self._notify_degraded(bucket, object_name, fi.version_id)

        errs = fan_out("rename", staged, rename, on_settle=renamed_late)
        err = meta.reduce_write_quorum_errs(
            errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
        if err is not None:
            raise api_errors.to_object_err(err, bucket, object_name)
        return sum(1 for i in range(len(shuffled))
                   if staged[i] is None or errs[i] is not None)

    def _cleanup_tmp(self, disks, tmp_id: str) -> None:
        def rm(i, d):
            try:
                d.delete_file(MINIO_META_TMP_BUCKET, tmp_id, recursive=True)
            except serr.StorageError:
                pass
        meta.for_each_disk(disks, rm)

    # ------------------------------------------------------------------
    # GET (cmd/erasure-object.go:124-323 + cmd/erasure-decode.go)
    # ------------------------------------------------------------------

    def _object_file_info(self, bucket: str, object_name: str,
                          version_id: str = ""
                          ) -> tuple[FileInfo, list[Optional[FileInfo]],
                                     list[Optional[StorageAPI]]]:
        metas, errs = meta.read_all_file_info(self.disks, bucket,
                                              object_name, version_id)
        try:
            read_quorum, _ = meta.object_quorum_from_meta(
                metas, errs, self.parity_shards)
        except (api_errors.InsufficientReadQuorum, serr.StorageError):
            err = meta.reduce_read_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS,
                len(self.disks) - self.parity_shards)
            raise api_errors.to_object_err(
                err or api_errors.InsufficientReadQuorum(),
                bucket, object_name) from None
        err = meta.reduce_read_quorum_errs(errs, meta.OBJECT_OP_IGNORED_ERRS,
                                           read_quorum)
        if err is not None:
            raise api_errors.to_object_err(err, bucket, object_name)
        fi = meta.pick_valid_file_info(metas, read_quorum)
        online, _ = meta.list_online_disks(self.disks, metas, errs)
        return fi, metas, online

    def has_object_versions(self, bucket: str, object_name: str) -> bool:
        """True when ANY version (including a delete marker) exists —
        the zone-affinity probe (reference getZoneIdx's delete-marker
        cases, cmd/erasure-server-sets.go:195-220)."""
        try:
            self._object_file_info(bucket, object_name)
            return True
        except api_errors.ObjectApiError:
            return False

    def latest_file_info(self, bucket: str, object_name: str) -> FileInfo:
        """Latest version's FileInfo INCLUDING delete markers — the
        multi-pool newest-wins read probe (get_object_info hides
        markers behind ObjectNotFound, which would let an older data
        copy in another pool shadow a newer marker here)."""
        fi, _, _ = self._object_file_info(bucket, object_name)
        return fi

    def update_object_metadata(self, bucket: str, object_name: str,
                               metadata: dict, version_id: str = ""
                               ) -> ObjectInfo:
        """Metadata-only update of an existing version in place (tags,
        user metadata) — no data rewrite, no new version (reference
        updates xl.meta via WriteMetadata on the same version id)."""
        with self.ns.new_lock(f"{bucket}/{object_name}").write_locked():
            fi, metas, online = self._object_file_info(
                bucket, object_name, version_id)
            if fi.deleted:
                raise api_errors.MethodNotAllowed(
                    f"{bucket}/{object_name} is a delete marker")
            new_meta = dict(metadata)
            new_meta["etag"] = fi.metadata.get("etag", "")

            def upd(i, d):
                m = metas[i]
                if m is None:
                    raise serr.FileNotFound(object_name)
                m.metadata = dict(new_meta)
                d.write_metadata(bucket, object_name, m)

            _, errs = meta.for_each_disk(online, upd)
            _, write_quorum = meta.object_quorum_from_meta(
                metas, [None] * len(metas), self.parity_shards)
            err = meta.reduce_write_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
            if err is not None:
                raise api_errors.to_object_err(err, bucket, object_name)
            fi.metadata = new_meta
        if any(e is not None for e in errs):
            self._notify_degraded(bucket, object_name, version_id)
        self._notify_namespace(bucket, object_name)
        return fi.to_object_info(bucket, object_name)

    def transition_object(self, bucket: str, object_name: str,
                          version_id: str = "", tier: str = "",
                          remote_object: str = "",
                          remote_version: str = "",
                          expect_etag: str = "",
                          expect_mod_time: Optional[float] = None
                          ) -> ObjectInfo:
        """Rewrite one version's xl.meta into a zero-data stub carrying
        the tier name + remote key, then free the local shards — the
        reference's TransitionObject commit (cmd/erasure-object.go):
        the caller has ALREADY verified the remote copy; local data is
        deleted only after the stub landed at write quorum, so a crash
        anywhere earlier leaves the object fully readable locally.

        Also the restore-expiry reclaim path: re-stubbing a restored
        copy passes the SAME tier/remote key back in (no re-upload) and
        this rewrite drops the x-amz-restore state.

        expect_etag/expect_mod_time pin the version's IDENTITY inside
        the write lock: for unversioned objects nothing else ties the
        uploaded remote bytes to the version being stubbed — a client
        overwrite racing the worker's remote upload must abort the
        commit (PreConditionFailed), not stub the NEW data over the OLD
        remote copy."""
        with self.ns.new_lock(f"{bucket}/{object_name}").write_locked():
            fi, metas, online = self._object_file_info(
                bucket, object_name, version_id)
            if fi.deleted:
                raise api_errors.MethodNotAllowed(
                    f"{bucket}/{object_name} is a delete marker")
            if (expect_etag
                    and fi.metadata.get("etag", "") != expect_etag) or \
                    (expect_mod_time is not None
                     and fi.mod_time != expect_mod_time):
                raise api_errors.PreConditionFailed(
                    f"{bucket}/{object_name} changed since the remote "
                    "copy was written")
            data_dir = fi.data_dir
            new_meta = dict(fi.metadata)
            new_meta[TRANSITION_STATUS_KEY] = TRANSITION_COMPLETE
            new_meta[TRANSITION_TIER_KEY] = tier
            new_meta[TRANSITIONED_OBJECT_KEY] = remote_object
            if remote_version:
                new_meta[TRANSITIONED_VERSION_KEY] = remote_version
            else:
                new_meta.pop(TRANSITIONED_VERSION_KEY, None)
            new_meta.pop(RESTORE_KEY, None)
            new_meta.pop(RESTORE_EXPIRY_KEY, None)

            def upd(i, d):
                m = metas[i]
                if m is None:
                    raise serr.FileNotFound(object_name)
                m.metadata = dict(new_meta)
                m.data_dir = ""        # zero-data stub
                d.write_metadata(bucket, object_name, m)

            _, errs = meta.for_each_disk(online, upd)
            _, write_quorum = meta.object_quorum_from_meta(
                metas, [None] * len(metas), self.parity_shards)
            err = meta.reduce_write_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
            if err is not None:
                raise api_errors.to_object_err(err, bucket, object_name)
            # the stub is durable at quorum: NOW the local shards go
            # (every drive, not just online — stale copies must not
            # resurrect the data dir)
            if data_dir:
                def rm(i, d):
                    try:
                        d.delete_file(bucket,
                                      f"{object_name}/{data_dir}",
                                      recursive=True)
                    except serr.FileNotFound:
                        pass

                meta.for_each_disk(self.disks, rm)
            fi.metadata = new_meta
            fi.data_dir = ""
        if any(e is not None for e in errs):
            self._notify_degraded(bucket, object_name, fi.version_id)
        self._notify_namespace(bucket, object_name)
        return fi.to_object_info(bucket, object_name)

    def put_stub_version(self, bucket: str, object_name: str,
                         info: ObjectInfo,
                         if_none_newer: bool = False) -> ObjectInfo:
        """Write a transitioned ZERO-DATA stub version from its
        API-facing ObjectInfo — the rebalance copy path for tiered
        objects (there are no local shards to move; only the xl.meta
        pointer travels). Identity (version id, mod time, etag, parts,
        metadata incl. the tier/remote-key pointers) is preserved; the
        erasure geometry is re-minted for THIS set, since the stored
        geometry gates read quorum and the source pool's k may not even
        fit this pool's drive count."""
        md = dict(info.user_defined or {})
        if not (md.get(TRANSITION_STATUS_KEY) == TRANSITION_COMPLETE):
            raise api_errors.InvalidObjectState(
                f"{bucket}/{object_name} is not a transitioned stub")
        k, m, _, write_quorum = self._default_quorums()
        fi = new_file_info(f"{bucket}/{object_name}", k, m)
        fi.erasure.block_size = self.block_size
        fi.volume, fi.name = bucket, object_name
        fi.data_dir = ""
        fi.version_id = info.version_id or ""
        fi.size = info.size
        fi.mod_time = info.mod_time
        md["etag"] = info.etag
        if info.content_type:
            md["content-type"] = info.content_type
        if info.content_encoding:
            md["content-encoding"] = info.content_encoding
        fi.metadata = md
        for p in (info.parts or []):
            fi.add_object_part(p.number, p.etag, p.size, p.actual_size)
        if not fi.parts:
            fi.add_object_part(1, info.etag, info.size, info.size)
        with self.ns.new_lock(f"{bucket}/{object_name}").write_locked():
            if if_none_newer:
                # the replication apply's unversioned conflict gate —
                # an older stub replica must not shadow a newer write
                self._check_none_newer(bucket, object_name, fi)
            metas = [fi.light_copy() for _ in range(len(self.disks))]
            online = meta.write_unique_file_info(
                self.disks, bucket, object_name, metas, write_quorum)
        if any(d is None for d in online):
            # quorum met but some drive missed the stub: regain full
            # redundancy through MRF like every other write verb
            self._notify_degraded(bucket, object_name, fi.version_id)
        self._notify_namespace(bucket, object_name)
        return fi.to_object_info(bucket, object_name)

    def get_object_info(self, bucket: str, object_name: str,
                        opts: Optional[GetOptions] = None) -> ObjectInfo:
        opts = opts or GetOptions()
        with self.ns.new_lock(f"{bucket}/{object_name}").read_locked():
            fi, _, _ = self._object_file_info(bucket, object_name,
                                              opts.version_id)
        if fi.deleted:
            if opts.version_id:
                return fi.to_object_info(bucket, object_name)
            raise api_errors.ObjectNotFound(bucket, object_name)
        return fi.to_object_info(bucket, object_name)

    def get_object(self, bucket: str, object_name: str,
                   offset: int = 0, length: int = -1,
                   opts: Optional[GetOptions] = None
                   ) -> tuple[ObjectInfo, Iterator[bytes]]:
        """Returns (info, chunk iterator). Reads are verified (streaming
        bitrot) and reconstructed on the fly when shards are missing."""
        opts = opts or GetOptions()
        lock = self.ns.new_lock(f"{bucket}/{object_name}")
        # read lock + the quorum metadata read, before the stream (the
        # body's spans hang under engine.get_object, a traced_iter)
        with telemetry.span("get.open"):
            if not lock.get_rlock(30.0):
                raise api_errors.ObjectApiError("read lock timeout")
            try:
                fi, metas, online = self._object_file_info(
                    bucket, object_name, opts.version_id)
            except Exception:
                lock.unlock()
                raise
        try:
            if fi.deleted:
                # latest is a delete marker: plain GET -> NotFound;
                # explicit version GET -> MethodNotAllowed (S3 semantics,
                # matching get_object_info)
                if opts.version_id:
                    raise api_errors.MethodNotAllowed(
                        f"{bucket}/{object_name} is a delete marker")
                raise api_errors.ObjectNotFound(bucket, object_name)
            if is_transitioned(fi.metadata) \
                    and not is_restored(fi.metadata):
                # the data lives in a remote tier and no restored local
                # copy exists: S3 InvalidObjectState until RestoreObject
                raise api_errors.InvalidObjectState(
                    f"{bucket}/{object_name} is archived in tier "
                    f"{fi.metadata.get(TRANSITION_TIER_KEY, '?')!r}; "
                    "restore it first")
            oi = fi.to_object_info(bucket, object_name)
            if length < 0:
                length = fi.size - offset
            if offset < 0 or length < 0 or offset + length > fi.size:
                if not (fi.size == 0 and offset == 0 and length <= 0):
                    raise api_errors.InvalidRange(offset, length, fi.size)
        except Exception:
            lock.unlock()
            raise

        # a drive that is present but lacks the latest copy needs heal
        # even when no shard read will fail (its shard may be parity)
        flagged = False
        if self.on_degraded_read is not None and any(
                online[i] is None and self.disks[i] is not None
                for i in range(len(online))):
            flagged = True
            try:
                self.on_degraded_read(bucket, object_name)
            except Exception:  # noqa: BLE001 — heal queueing is best-effort
                pass

        # idempotent release: the generator's finally AND the wrapper's
        # close() both funnel here — whichever runs first wins
        released = [False]

        def release() -> None:
            if not released[0]:
                released[0] = True
                lock.unlock()

        def gen() -> Iterator[bytes]:
            try:
                if fi.size == 0 or length == 0:
                    return
                # traced_iter (NOT a plain span): the span must only be
                # current while the read code runs, never across a
                # yield into the consumer — see telemetry.traced_iter
                yield from telemetry.traced_iter(
                    "engine.get_object",
                    self._read_object_stream(
                        bucket, object_name, fi, metas, online, offset,
                        length, suppress_heal_flag=flagged),
                    bucket=bucket, object=object_name, length=length)
            finally:
                release()

        return oi, _UnlockOnClose(gen(), release)

    def _read_object_stream(self, bucket, object_name, fi: FileInfo,
                            metas, online, offset: int, length: int,
                            suppress_heal_flag: bool = False
                            ) -> Iterator[bytes]:
        """Per-part block loop (getObjectWithFileInfo,
        cmd/erasure-object.go:217-323), with CROSS-PART lookahead: the
        one-group prefetcher no longer stops at a part boundary — while
        part N's last group runs fused verify+decode, part N+1's FIRST
        group is already reading on the prefetch pool (its readers are
        independent streams, so no io_lock is shared across parts)."""
        from ..parallel import pipeline as pl
        # every erasure read stream counts here — the hot-object read
        # cache's "hit serves WITHOUT erasure decode" proof is a flat
        # delta on this counter across a cached GET
        _get_streams_counter().inc()
        shuffled_disks = meta.shuffle_disks(online, fi.erasure.distribution)
        shuffled_meta = meta.shuffle_parts_metadata(metas,
                                                    fi.erasure.distribution)
        k = fi.erasure.data_blocks
        codec = self.codec(k, fi.erasure.parity_blocks)

        part_idx, part_off = fi.object_to_part_offset(offset)
        remaining = length
        plans: list[_PartReadPlan] = []
        for pi in range(part_idx, len(fi.parts)):
            if remaining <= 0:
                break
            part = fi.parts[pi]
            part_read_off = part_off if pi == part_idx else 0
            part_read_len = min(remaining, part.size - part_read_off)
            if part_read_len > 0:
                plans.append(_PartReadPlan(
                    self, bucket, object_name, fi, shuffled_disks,
                    shuffled_meta, codec, part, part_read_off,
                    part_read_len, suppress_heal_flag))
            remaining -= part_read_len
        try:
            for i, plan in enumerate(plans):
                nxt = plans[i + 1] if pl.ENABLED \
                    and i + 1 < len(plans) else None
                yield from plan.stream(next_plan=nxt)
        finally:
            for plan in plans:
                plan.close()

    def _read_part(self, bucket, object_name, fi: FileInfo, disks, smeta,
                   codec: Codec, part, offset: int, length: int,
                   suppress_heal_flag: bool = False) -> Iterator[bytes]:
        """Single-part convenience (kept for callers outside the main
        GET loop): one plan, no cross-part prefetch."""
        plan = _PartReadPlan(self, bucket, object_name, fi, disks, smeta,
                             codec, part, offset, length,
                             suppress_heal_flag)
        try:
            yield from plan.stream()
        finally:
            plan.close()

    def _read_block_shards(self, readers, codec: Codec, block_num: int,
                           shard_size: int, shard_len: int, k: int, n: int
                           ) -> tuple[list, bool]:
        """Single-block convenience (healing path): raw read +
        reconstruct-in-place."""
        shards, _digests, had_errors = self._read_block_shards_raw(
            readers, block_num, shard_size, shard_len, k, n)
        if any(shards[i] is None for i in range(k)):
            shards = codec.reconstruct(shards, data_only=True)
        return shards, had_errors

    def _verify_and_reconstruct_group(self, codec: Codec, group, k: int,
                                      n: int, readers, shard_size: int,
                                      algo: bitrot_mod.BitrotAlgorithm,
                                      io_lock: Optional[threading.Lock]
                                      = None,
                                      reader_gen: Optional[tuple]
                                      = None,
                                      benign_missing: frozenset
                                      = frozenset()) -> bool:
        """Verify deferred frame digests AND reconstruct the degraded
        blocks of a read group. Degraded blocks sharing one
        (present-mask, shard-length) pattern go through a single fused
        verify+decode device dispatch (models/pipeline.get_step); shards
        the fused program didn't cover batch-verify in one host call. A
        digest mismatch (rare) drops the corrupt shard's reader and
        re-reads the affected block with inline verification. Group
        entries are [b, off, blen, shard_len, shards, digests] lists,
        mutated in place. Returns True when any block needed
        reconstruction or had bitrot."""
        from ..ops import rs_matrix
        heal = False
        corrupt: set[int] = set()
        if io_lock is None:
            io_lock = threading.Lock()   # uncontended when no prefetch

        def drop_reader(u: int) -> None:
            """Condemn the reader a corrupt frame came from — unless a
            concurrent lookahead rebuilt the readers list since this
            group was read, in which case index u names a FRESH reader
            that never served the corrupt frame."""
            with io_lock:
                if reader_gen is None or \
                        reader_gen[0][0] == reader_gen[1]:
                    readers[u] = None

        # 1) degraded buckets: fused verify+decode on device, or
        #    missing-rows-only matmul on host
        buckets: dict[tuple[int, int], list[int]] = {}
        for gi, entry in enumerate(group):
            shards = entry[4]
            if all(shards[i] is not None for i in range(k)):
                continue
            mask = sum(1 << i for i in range(n)
                       if shards[i] is not None)
            buckets.setdefault((mask, entry[3]), []).append(gi)
        # submit EVERY bucket's fused dispatch before resolving any:
        # each bucket's grace window then overlaps CONCURRENT requests'
        # same-pattern buckets (same former key -> one fused launch)
        # instead of opening only after the previous bucket resolved
        staged: list[tuple] = []
        for (mask, shard_len), idxs in buckets.items():
            # a reconstruct forced by the READ PLAN (quarantine skip /
            # latency-hedge loser) is not damage: the shards are on
            # disk, nothing needs healing — only a miss the plan can't
            # account for flags the degraded-read heal
            if not {i for i in range(k)
                    if not (mask >> i) & 1} <= benign_missing:
                heal = True
            _dm, used, _missing = rs_matrix.missing_data_matrix(
                k, codec.m, mask)
            stacked = np.stack([
                np.stack([group[gi][4][u] for u in used])
                for gi in idxs])                       # (G', k, S)
            # fuse hashing only when digests were actually deferred;
            # inline-verified shards need just the decode matmul
            want_fused = any(group[gi][5][u] is not None
                             for gi in idxs for u in used)
            fut = None
            if want_fused and self.scheduler is not None:
                fut = self.scheduler.submit_decode(
                    codec, stacked, mask, shard_len, algo)
            staged.append((mask, shard_len, idxs, used, stacked,
                           want_fused, fut))
        for mask, shard_len, idxs, used, stacked, want_fused, fut \
                in staged:
            if fut is not None:
                try:
                    with telemetry.span("get.decode_wait"):
                        # check: allow(deadline) device dispatch; scheduler close() flushes waiters
                        fused = fut.result()
                except Exception:  # noqa: BLE001 — a shared-dispatch
                    # failure must not kill a GET the host can still
                    # serve: fall back to the local decode + step-2
                    # host verification of the deferred digests
                    fused = None
            elif want_fused:
                fused = codec.verify_and_decode_batch(
                    stacked, mask, shard_len, algo)
            else:
                fused = None
            if fused is not None:
                out, missing_idx, sdig = fused
                for row, gi in enumerate(idxs):
                    shards, digests = group[gi][4], group[gi][5]
                    bad = False
                    for col, u in enumerate(used):
                        exp = digests[u]
                        if exp is None:
                            continue
                        if sdig[row, col].tobytes() != exp:
                            shards[u] = None
                            drop_reader(u)
                            bad = True
                        else:
                            digests[u] = None  # verified on device
                    if bad:
                        corrupt.add(gi)
                    else:
                        for r_i, mi in enumerate(missing_idx):
                            shards[mi] = out[row][r_i]
            else:
                out, idxs_rows = codec.recover_stacked(
                    stacked, mask, set(range(k)))
                for row, gi in enumerate(idxs):
                    shards = group[gi][4]
                    for r_i, mi in enumerate(idxs_rows):
                        shards[mi] = out[row][r_i]

        # 2) batch-verify every shard the fused program didn't cover
        #    (healthy blocks, hedged extras, CPU-routed buckets)
        pend: dict[int, list[tuple[int, int]]] = {}
        for gi, entry in enumerate(group):
            if gi in corrupt:
                continue
            shards, digests = entry[4], entry[5]
            for i in range(n):
                if digests[i] is not None and shards[i] is not None:
                    pend.setdefault(len(shards[i]), []).append((gi, i))
        if pend:
            with telemetry.span(
                    "get.host_verify",
                    shards=sum(len(it) for it in pend.values()),
                    bytes=sum(sl * len(it) for sl, it in pend.items())):
                for _sl, items in pend.items():
                    stacked = np.stack([group[gi][4][i] for gi, i in items])
                    got = bitrot_mod.hash_shards_batch(stacked, algo)
                    for row, (gi, i) in enumerate(items):
                        if got[row].tobytes() != group[gi][5][i]:
                            group[gi][4][i] = None
                            drop_reader(i)
                            corrupt.add(gi)
                        else:
                            group[gi][5][i] = None

        # 3) corrupt blocks (bitrot found after deferral): re-read with
        #    inline verification and host reconstruct — the corrupt
        #    reader is dead, so hedged extras replace it
        for gi in sorted(corrupt):
            heal = True
            b, _off, _blen, shard_len, _shards, _dg = group[gi]
            with io_lock:   # a GET lookahead may hold the readers
                new_shards, _digests, _he = self._read_block_shards_raw(
                    readers, b, shard_size, shard_len, k, n)
            if any(new_shards[i] is None for i in range(k)):
                new_shards = codec.reconstruct(new_shards, data_only=True)
            group[gi][4] = new_shards
            group[gi][5] = [None] * n
        return heal

    def _read_group_shards_raw(self, readers, blocks: list,
                               shard_size: int, shard_lens: list,
                               k: int, n: int,
                               collect_digests: bool = False,
                               avoid: frozenset = frozenset(),
                               benign_sink: Optional[set] = None) -> list:
        """Group form of _read_block_shards_raw: ONE pool task per
        reader streams every block of the group sequentially (the
        frames are adjacent on disk), instead of a k-way fan-out per
        block — GET_BATCH_BLOCKS× fewer pool tasks, and each shard
        file is read in order. Returns [(shards, digests, had_errors)]
        per block.

        This is THE hedged-read state machine (the "Tail at Scale"
        fix): k primaries launch, and a spare shard read races any
        primary that either FAILS (error hedge, the original behavior)
        or outlives the adaptive latency deadline from the health
        tracker (healthy p95 × K, clamped) — a drive doing 500 ms
        I/Os no longer holds the whole GET. First k wins; losers are
        condemned (their stateful streams must never serve a later
        group) and closed when their abandoned read settles.

        `avoid` holds reader indices the plan deprioritizes (slow-drive
        quarantine): they sort behind every healthy candidate and are
        touched only when nothing else can reach k. `benign_sink`
        collects indices whose shards are missing for PLAN reasons
        (avoided, or hedge-raced on latency) rather than damage — the
        verify step must not flag a heal for those."""
        from concurrent.futures import FIRST_COMPLETED
        from concurrent.futures import wait as _fwait
        nb = len(blocks)
        per_reader: list = [None] * n          # i -> [(data, dg)]*nb
        had_errors = False
        errored: set = set()

        def read_one(i: int, r) -> list:
            out = []
            with telemetry.timed("disk.shard_read", disk=i,
                                 blocks=nb) as t:
                for b, sl in zip(blocks, shard_lens):
                    off = b * shard_size
                    if collect_digests and isinstance(
                            r, bitrot_io.StreamingBitrotReader):
                        frames = r.read_frames(off, sl)
                        out.append((frames[0][1] if frames else b"",
                                    frames[0][0] if frames else None))
                    else:
                        out.append((r.read_at(off, sl), None))
            healthtrack.observe_disk(r.disk, "read", t.seconds)
            return out

        # candidate order: data rows first (their shards join without
        # a decode), parity next, avoided (suspect/probation) drives
        # last — the capacity-permitting rule by construction: they
        # re-enter only when nothing healthier can reach k
        candidates = [i for i in range(n) if readers[i] is not None]
        candidates.sort(key=lambda i: (i in avoid, 0 if i < k else 1, i))
        spares = candidates[k:]
        inflight: dict = {}

        def launch(i: int) -> None:
            inflight[meta.submit_disk_task(read_one, i, readers[i],
                                           stage="shard_read")] = i

        for i in candidates[:k]:
            launch(i)
        hedge_s = healthtrack.read_hedge_s()
        deadline = None if hedge_s is None \
            else time.monotonic() + hedge_s

        while inflight:
            got = sum(1 for p in per_reader if p is not None)
            if got >= k:
                break
            timeout = None
            if deadline is not None and spares:
                timeout = max(deadline - time.monotonic(), 0.0)
            done, _ = _fwait(set(inflight), timeout=timeout,
                             return_when=FIRST_COMPLETED)
            if not done:
                # latency hedge: every still-missing slot gets a spare
                # racing it; the deadline re-arms so a second level of
                # stalls hedges again (spares permitting)
                need = k - sum(1 for p in per_reader if p is not None)
                fresh, spares = spares[:need], spares[need:]
                for i in fresh:
                    launch(i)
                    healthtrack.note_hedge("latency")
                deadline = time.monotonic() + (hedge_s or 0.0)
                continue
            for f in done:
                i = inflight.pop(f)
                try:
                    per_reader[i] = f.result(timeout=0)
                except Exception:  # noqa: BLE001 — reader condemned
                    readers[i] = None
                    errored.add(i)
                    had_errors = True
                    if spares:
                        j = spares.pop(0)
                        launch(j)
                        healthtrack.note_hedge("error")

        got = sum(1 for p in per_reader if p is not None)
        if got >= k and inflight:
            # first-k wins: condemn the losers so no later group reads
            # their (stateful) streams, and close each one when its
            # abandoned task settles on the pool
            for f, i in inflight.items():
                loser = readers[i]
                readers[i] = None

                def _close(_f, r=loser):
                    try:
                        r.close()
                    except Exception:  # noqa: BLE001 — abandoned
                        pass
                f.add_done_callback(_close)
        if got < k:
            raise api_errors.InsufficientReadQuorum(
                f"{got} readable shards < k={k}")
        # shards missing because the PLAN skipped or out-raced their
        # reader (not because the reader failed) are benign: decode
        # reconstructs them, but nothing on disk needs healing. The
        # caller may PRE-SEED benign_sink with prior groups' benign
        # misses (a latency-condemned reader stays out for the whole
        # part) — those carry forward into this group's verdict too.
        benign = {i for i in candidates
                  if per_reader[i] is None and i not in errored}
        if benign_sink is not None:
            benign_sink.update(benign)
            # a reader that REALLY errored this group loses any benign
            # standing it carried in (avoided earlier, then pressed
            # into service and failed): that miss is damage
            benign_sink.difference_update(errored)
            benign = set(benign_sink)
        missing_data = {i for i in range(k) if per_reader[i] is None}
        if missing_data and not missing_data <= benign:
            had_errors = True

        out = []
        for bi in range(nb):
            shards: list = [None] * n
            digests: list = [None] * n
            for i in range(n):
                if per_reader[i] is not None:
                    shards[i] = np.frombuffer(per_reader[i][bi][0],
                                              dtype=np.uint8)
                    digests[i] = per_reader[i][bi][1]
            out.append((shards, digests, had_errors))
        return out

    def _read_block_shards_raw(self, readers, block_num: int,
                               shard_size: int, shard_len: int, k: int,
                               n: int, collect_digests: bool = False,
                               avoid: frozenset = frozenset(),
                               benign_sink: Optional[set] = None
                               ) -> tuple[list, list, bool]:
        """k-of-n shard reads with hedged extras on failure OR stall
        (parallelReader, cmd/erasure-decode.go:102-184). Returns
        (shards, expected_digests, had_errors): raw shards (missing
        entries None — at least k present) without reconstructing.

        With collect_digests, streaming readers skip per-frame host
        verification and return each frame's stored digest instead
        (digests[i] is None when the shard was verified at read time) —
        the deferred-verify feed for the fused device program.

        One hedged-read state machine: this is the single-block form of
        _read_group_shards_raw, so the heal/rebalance readers that call
        it ride the same adaptive hedging the GET plan does."""
        return self._read_group_shards_raw(
            readers, [block_num], shard_size, [shard_len], k, n,
            collect_digests=collect_digests, avoid=avoid,
            benign_sink=benign_sink)[0]

    # ------------------------------------------------------------------
    # DELETE (cmd/erasure-object.go:727-820)
    # ------------------------------------------------------------------

    def delete_object(self, bucket: str, object_name: str,
                      version_id: str = "", versioned: bool = False
                      ) -> ObjectInfo:
        k, m, _, write_quorum = self._default_quorums()
        with self.ns.new_lock(f"{bucket}/{object_name}").write_locked():
            if versioned and not version_id:
                # versioned delete without a version: write a delete marker
                fi = FileInfo(volume=bucket, name=object_name,
                              version_id=str(_uuid.uuid4()), deleted=True,
                              mod_time=now())
                _, errs = meta.for_each_disk(
                    self.disks,
                    lambda i, d: d.write_metadata(bucket, object_name, fi))
                err = meta.reduce_write_quorum_errs(
                    errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
                if err is not None:
                    raise api_errors.to_object_err(err, bucket, object_name)
                oi = fi.to_object_info(bucket, object_name)
                self._flag_degraded_delete(bucket, object_name,
                                           fi.version_id, errs)
                self._notify_namespace(bucket, object_name)
                return oi

            fi = FileInfo(volume=bucket, name=object_name,
                          version_id=version_id)

            def rm(i, d):
                d.delete_version(bucket, object_name, fi)

            _, errs = meta.for_each_disk(self.disks, rm)
            # not-found is counted (not ignored) so a missing object maps
            # to ObjectNotFound rather than a quorum failure
            err = meta.reduce_write_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
            if err is not None:
                raise api_errors.to_object_err(err, bucket, object_name)
        self._flag_degraded_delete(bucket, object_name, version_id, errs)
        self._notify_namespace(bucket, object_name)
        return ObjectInfo(bucket=bucket, name=object_name,
                          version_id=version_id)

    def put_delete_marker(self, bucket: str, object_name: str,
                          version_id: str = "",
                          mod_time: Optional[float] = None,
                          metadata: Optional[dict] = None) -> ObjectInfo:
        """Replicate a delete marker with an EXPLICIT version id and mod
        time — the rebalance/replication copy path (delete_object always
        mints fresh ids, which would break version-history fidelity when
        a marker moves between pools). `metadata` carries replication
        markers (the replica-origin key) on the marker version itself."""
        _k, _m, _, write_quorum = self._default_quorums()
        fi = FileInfo(volume=bucket, name=object_name,
                      version_id=version_id or str(_uuid.uuid4()),
                      deleted=True, mod_time=mod_time or now(),
                      metadata=dict(metadata or {}))
        with self.ns.new_lock(f"{bucket}/{object_name}").write_locked():
            _, errs = meta.for_each_disk(
                self.disks,
                lambda i, d: d.write_metadata(bucket, object_name, fi))
            err = meta.reduce_write_quorum_errs(
                errs, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
            if err is not None:
                raise api_errors.to_object_err(err, bucket, object_name)
        self._flag_degraded_delete(bucket, object_name, fi.version_id,
                                   errs)
        self._notify_namespace(bucket, object_name)
        return fi.to_object_info(bucket, object_name)

    def _notify_degraded(self, bucket: str, object_name: str,
                         version_id: str) -> None:
        """Best-effort on_degraded_write invocation — the single home of
        the guard+swallow all degraded write paths share."""
        if self.on_degraded_write is None:
            return
        try:
            self.on_degraded_write(bucket, object_name, version_id)
        except Exception:  # noqa: BLE001 — heal queueing is best-effort
            pass

    def _notify_namespace(self, bucket: str, object_name: str) -> None:
        """Best-effort on_namespace_change invocation (the
        _notify_degraded pattern): every successful namespace mutation
        reports (bucket, object) so the persisted bucket metacache can
        journal the delta. Hidden meta buckets never feed the index —
        the index's own segment writes land there."""
        if self.on_namespace_change is None or bucket.startswith("."):
            return
        try:
            self.on_namespace_change(bucket, object_name)
        except Exception:  # noqa: BLE001 — indexing is best-effort
            pass

    def _flag_degraded_delete(self, bucket: str, object_name: str,
                              version_id: str, errs) -> None:
        """Queue an MRF heal when a quorum-successful delete/marker write
        left stale state on some drive (drive gone or write failed). A
        drive answering FileNotFound is already converged — absence is
        the goal state of a delete."""
        if any(e is not None
               and not isinstance(e, serr.OBJECT_NOT_FOUND_ERRS)
               for e in errs):
            self._notify_degraded(bucket, object_name, version_id)

    def delete_objects(self, bucket: str, objects: list[str]
                       ) -> list[Optional[Exception]]:
        """Bulk delete: ONE storage call per drive for the whole batch
        (reference DeleteObjects, cmd/erasure-object.go:772 — not a loop
        of single deletes), with per-key quorum evaluation."""
        if not objects:
            return []
        import copy
        _k, _m, _, write_quorum = self._default_quorums()
        fis = [FileInfo(volume=bucket, name=o) for o in objects]
        with self.ns.new_lock(
                *[f"{bucket}/{o}" for o in objects]).write_locked():
            def bulk(i, d):
                return d.delete_versions(bucket,
                                         [copy.deepcopy(f) for f in fis])

            results, disk_errs = meta.for_each_disk(self.disks, bulk)

        out: list[Optional[Exception]] = []
        for j, o in enumerate(objects):
            per_disk: list[Optional[Exception]] = []
            for res, derr in zip(results, disk_errs):
                if derr is not None:
                    per_disk.append(derr)      # whole drive failed
                elif res is not None and j < len(res):
                    per_disk.append(res[j])
                else:
                    per_disk.append(serr.DiskNotFound("no result"))
            err = meta.reduce_write_quorum_errs(
                per_disk, meta.OBJECT_OP_IGNORED_ERRS, write_quorum)
            out.append(None if err is None
                       else api_errors.to_object_err(err, bucket, o))
            if err is None:
                # quorum-successful delete that left stale state on
                # some drive still needs the MRF pass, exactly like
                # the single-key delete path
                self._flag_degraded_delete(bucket, o, "", per_disk)
                self._notify_namespace(bucket, o)
        return out

    # ------------------------------------------------------------------
    # LIST (merge-walk across drives; cmd/erasure-sets.go:888-1081)
    # ------------------------------------------------------------------

    def list_objects(self, bucket: str, prefix: str = "", marker: str = "",
                     delimiter: str = "", max_keys: int = 1000
                     ) -> tuple[list[ObjectInfo], list[str], bool]:
        """Returns (objects, common_prefixes, is_truncated)."""
        self.get_bucket_info(bucket)  # existence + quorum check

        def read_latest(name: str):
            try:
                fi = self._read_one(bucket, name)
            except api_errors.ObjectApiError:
                return None
            if fi.deleted:
                return None
            return fi.to_object_info(bucket, name)

        return paginate_objects(self._merged_names(bucket, prefix, marker),
                                read_latest, prefix, marker, delimiter,
                                max_keys)

    def list_object_versions(self, bucket: str, prefix: str = "",
                             marker: str = "", max_keys: int = 1000,
                             version_marker: str = "",
                             delimiter: str = ""
                             ) -> tuple[list[ObjectInfo], list[str],
                                        str, str, bool]:
        """One page of the bucket's version history: (versions,
        common_prefixes, next_key_marker, next_version_id_marker,
        is_truncated) — the page shape lives in paginate_versions, the
        SAME loop the metacache index serve runs.

        `version_marker` resumes AFTER that version of `marker` (S3
        version-id-marker semantics); an unknown version id falls back
        to the key's whole version list, which can only over-return,
        never skip. A delimiter rolls keys up into CommonPrefixes like
        the reference's ListObjectVersions."""
        self.get_bucket_info(bucket)
        names = self._merged_names(bucket, prefix, marker,
                                   inclusive=bool(version_marker))
        return paginate_versions(
            names, lambda n: self.object_versions(bucket, n),
            prefix, marker, version_marker, delimiter, max_keys)

    def object_versions(self, bucket: str, name: str) -> list[ObjectInfo]:
        """Quorum-merged versions of ONE object as API ObjectInfos,
        newest first — the per-name unit of list_object_versions, the
        metacache refresh read, and the pool-local read the rebalance
        feed path uses."""
        return [fi.to_object_info(bucket, name)
                for fi in self._merged_versions(bucket, name)]

    def _merged_versions(self, bucket: str, name: str) -> list[FileInfo]:
        """Quorum-merge the per-drive xl.meta version journals of one
        object: a version counts only when >= read-quorum drives agree
        on it (version id + mod time + kind) — a stale drive that missed
        writes (or kept deleted versions) while offline cannot distort
        the history. The reference merges per-drive FileInfo under
        quorum the same way (readAllFileInfo + pickValidFileInfo,
        cmd/erasure-metadata-utils.go:118). Versions sort newest-first
        like the reference journal order."""
        results, _errs = meta.for_each_disk(
            self.disks, lambda i, d: d.read_versions(bucket, name))
        counts: dict[tuple, int] = {}
        picks: dict[tuple, FileInfo] = {}
        for vers in results:
            if vers is None:
                continue
            for fi in vers:
                key = (fi.version_id, fi.mod_time, fi.deleted)
                counts[key] = counts.get(key, 0) + 1
                picks.setdefault(key, fi)
        read_quorum = self.data_shards
        merged = [picks[key] for key, c in counts.items()
                  if c >= read_quorum]
        # deterministic newest-first order: mod time, then version id —
        # the active-active conflict rule. Two sites that hold the same
        # version SET (concurrent writers replicated both ways) must
        # list them identically, including mod-time ties, or the
        # convergence contract of the replication plane breaks.
        merged.sort(key=lambda fi: (fi.mod_time or 0, fi.version_id or ""),
                    reverse=True)
        return merged

    def _merged_names(self, bucket: str, prefix: str,
                      marker: str = "",
                      inclusive: bool = False) -> Iterator[str]:
        """Lazy lexical merge-walk of object names across drives (the
        reference's startMergeWalks/lexicallySortedEntry,
        cmd/erasure-sets.go:888-1081): each drive streams its own sorted
        walk, a heap merge dedupes, and nothing is materialized — a
        100k-key bucket costs one page, not one set.

        Yields names > marker (>= marker when `inclusive` — the
        version-marker resume re-enters the marker key itself) matching
        prefix, in order, until the caller stops."""
        import heapq

        # narrow the walk to the deepest directory of the prefix
        dir_part = prefix.rsplit("/", 1)[0] if "/" in prefix else ""
        # drive walks yield strictly > their marker; shortening the
        # marker by one char re-admits the marker name itself (plus a
        # few predecessors the caller filters out)
        walk_marker = marker[:-1] if (inclusive and marker) else marker

        def drive_names(d) -> Iterator[str]:
            try:
                for fi in d.walk(bucket, dir_part, walk_marker):
                    yield fi.name
            except serr.StorageError:
                return              # drive died mid-walk: its names drop

        iters = []
        live = 0
        for d in self.disks:
            if d is None:
                continue
            iters.append(drive_names(d))
            live += 1
            if live >= 3:  # reference asks 3 disks per set
                break
        last = None
        for name in heapq.merge(*iters):
            if name == last:
                continue
            last = name
            if name.startswith(prefix):
                yield name
            elif name > prefix:
                return              # sorted: nothing later can match

    def _read_one(self, bucket: str, object_name: str) -> FileInfo:
        fi, _, _ = self._object_file_info(bucket, object_name)
        return fi


def paginate_objects(names, read_latest, prefix: str, marker: str,
                     delimiter: str, max_keys: int
                     ) -> tuple[list[ObjectInfo], list[str], bool]:
    """The single home of the object-listing page shape: delimiter
    grouping, marker skips, and max_keys truncation over a sorted
    prefix-matching name stream. Both the merge-walk path
    (ErasureObjects.list_objects) and the metacache index serve run
    THIS loop, so index-served pages are shape-identical to the oracle
    by construction.

    `read_latest(name)` returns the listable ObjectInfo or None (no
    quorum, or the latest version is a delete marker — either way the
    name does not count toward the page)."""
    objects: list[ObjectInfo] = []
    prefixes: list[str] = []
    seen_prefix: set[str] = set()
    truncated = False
    for name in names:
        if marker and name <= marker:
            continue
        if delimiter:
            rest = name[len(prefix):]
            di = rest.find(delimiter)
            if di >= 0:
                p = prefix + rest[:di + len(delimiter)]
                if marker and p <= marker:
                    continue  # prefix page already returned
                if p not in seen_prefix:
                    seen_prefix.add(p)
                    prefixes.append(p)
                    if len(objects) + len(prefixes) >= max_keys + 1:
                        truncated = True
                        prefixes = prefixes[:max_keys - len(objects)]
                        break
                continue
        oi = read_latest(name)
        if oi is None:
            continue
        objects.append(oi)
        if len(objects) + len(prefixes) >= max_keys + 1:
            truncated = True
            objects = objects[:max_keys - len(prefixes)]
            break
    return objects, prefixes, truncated


def paginate_versions(names, versions_of, prefix: str, marker: str,
                      version_marker: str, delimiter: str, max_keys: int
                      ) -> tuple[list[ObjectInfo], list[str], str, str,
                                 bool]:
    """The single home of the versions-listing page shape: delimiter
    grouping (CommonPrefixes, like the reference's ListObjectVersions),
    key+version-id marker resume, and max_keys truncation over a sorted
    prefix-matching name stream. Both the merge-walk path
    (ErasureObjects.list_object_versions) and the metacache index serve
    run THIS loop, so index-served pages are shape-identical to the
    oracle by construction.

    Returns (versions, common_prefixes, next_key_marker,
    next_version_id_marker, is_truncated). Versions and prefixes each
    count one entry toward max_keys (S3 semantics). A page boundary may
    fall INSIDE one key's version list — the markers make the cut
    explicit and resumable; a cut at a rolled-up prefix sets
    next_key_marker to the prefix itself (keys under it sort after it,
    and the `p <= marker` skip on resume collapses them straight back
    into the already-returned prefix entry). `versions_of(name)`
    returns the key's quorum-merged versions, newest first."""
    out: list[ObjectInfo] = []
    prefixes: list[str] = []
    seen_prefix: set[str] = set()
    if max_keys <= 0:
        return [], [], "", "", False
    for name in names:
        if marker:
            if name < marker or (not version_marker and name == marker):
                continue
        if delimiter:
            rest = name[len(prefix):]
            di = rest.find(delimiter)
            if di >= 0:
                p = prefix + rest[:di + len(delimiter)]
                if marker and p <= marker:
                    continue  # prefix page already returned
                if p not in seen_prefix:
                    seen_prefix.add(p)
                    if len(out) + len(prefixes) >= max_keys:
                        # overflow entry actually seen: provably
                        # truncated, the cut falls BEFORE this prefix
                        nkm, nvm = _last_marker(out, prefixes)
                        return out, prefixes, nkm, nvm, True
                    prefixes.append(p)
                continue
        vers = versions_of(name)
        if version_marker and name == marker:
            # "null" is the wire form of the empty (pre-versioning)
            # version id (xmlgen emits it, clients echo it back)
            vm = "" if version_marker == "null" else version_marker
            idx = next((i for i, v in enumerate(vers)
                        if v.version_id == vm), None)
            if idx is not None:
                vers = vers[idx + 1:]
        for oi in vers:
            if len(out) + len(prefixes) >= max_keys:
                # A null version id rides as the "null" sentinel — an
                # empty marker would read as NO marker on resume and
                # skip the key's remaining versions
                nkm, nvm = _last_marker(out, prefixes)
                return out, prefixes, nkm, nvm, True
            out.append(oi)
    return out, prefixes, "", "", False


# the single home of the page-cut marker rule (shared with
# sets.merge_version_listings and the FS/gateway single_version_page)
_last_marker = last_version_marker


class _UnlockOnClose:
    """GET stream wrapper whose close() releases the namespace read
    lock even when the stream was NEVER started — closing (or dropping)
    an unstarted generator skips its ``finally``, so a consumer that
    errors before reading the first chunk (a failed tier upload, an
    aborted proxy) would otherwise leak the read lock and wedge every
    later write-locked op on the object."""

    def __init__(self, gen, release):
        self._gen = gen
        self._release = release

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self) -> None:
        try:
            self._gen.close()
        finally:
            self._release()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass


class _PartReadPlan:
    """One part's GET read state: stateful bitrot readers, the
    precomputed group walk, and the one-group lookahead — factored out
    of the per-part loop so the prefetcher can cross PART boundaries
    (engine._read_object_stream primes part N+1's first group while
    part N's last group verifies/decodes).

    Every reader I/O (group reads, hedged re-reads, the corrupt-block
    re-reads inside verify) serializes on the per-part ``io_lock``: the
    bitrot readers are stateful streams shared with the lookahead
    thread. ``reader_gen`` counts in-place rebuilds of the readers list
    so a verify verdict formed against the OLD readers can't condemn a
    fresh one by index. Parts never share readers, so cross-part
    prefetch needs no cross-part locking."""

    def __init__(self, eng: "ErasureObjects", bucket: str,
                 object_name: str, fi: FileInfo, disks, smeta,
                 codec: Codec, part, offset: int, length: int,
                 suppress_heal_flag: bool = False):
        self.eng = eng
        self.bucket, self.object_name = bucket, object_name
        self.fi, self.disks, self.smeta = fi, disks, smeta
        self.codec, self.part = codec, part
        self.offset, self.length = offset, length
        self.suppress_heal_flag = suppress_heal_flag
        self.n = len(disks)
        self.k = fi.erasure.data_blocks
        self.shard_size = fi.erasure.shard_size()
        self.till = fi.erasure.shard_file_offset(offset, length,
                                                 part.size)
        self.path = f"{object_name}/{fi.data_dir}/part.{part.number}"
        self.readers: Optional[list] = None
        self.part_algo = None
        self.defer_verify = False
        self.avoid: frozenset = frozenset()
        # indices whose shards went missing for PLAN reasons in ANY
        # earlier group (quarantine skip / latency-hedge loser): a
        # condemned-for-latency reader stays out for the whole part,
        # and later groups must keep treating its absence as benign —
        # not as damage to heal (cleared on a quorum-loss rebuild,
        # which mints fresh readers)
        self.benign_hist: set = set()
        self.io_lock = threading.Lock()
        self.reader_gen = [0]
        self.heal_required = False
        self._pending = None           # live lookahead future
        self._primed = False           # _pending holds OUR group 0

        # blocks are read in groups so a degraded part reconstructs many
        # blocks per device call instead of one matmul per block; the
        # group walk is precomputed so the one-group-lookahead
        # prefetcher can issue group N+1's reads while group N runs
        # fused verify+decode and is joined/yielded
        self.specs: list[tuple[list, list]] = []
        bn = offset // fi.erasure.block_size
        end_block = (offset + length - 1) // fi.erasure.block_size
        while bn <= end_block:
            group_end = min(bn + GET_BATCH_BLOCKS - 1, end_block)
            blocks = list(range(bn, group_end + 1))
            geoms = []
            for b in blocks:
                block_off = b * fi.erasure.block_size
                block_len = min(fi.erasure.block_size,
                                part.size - block_off)
                geoms.append((b, block_off, block_len,
                              -(-block_len // self.k)))
            self.specs.append((blocks, geoms))
            bn = group_end + 1

    def _make_readers(self) -> list:
        out: list[Optional[object]] = [None] * self.n
        for i, d in enumerate(self.disks):
            if d is None or self.smeta[i] is None:
                continue
            csum = self.smeta[i].erasure.get_checksum_info(
                self.part.number)
            algo = (bitrot_mod.BitrotAlgorithm.from_string(
                csum.algorithm) if csum else self.eng.bitrot_algo)
            out[i] = bitrot_io.new_bitrot_reader(
                d, self.bucket, self.path, self.till, algo,
                csum.hash if csum else b"", self.shard_size)
        return out

    def _ensure_readers(self) -> None:
        if self.readers is not None:
            return
        self.readers = self._make_readers()
        # slow-drive quarantine: suspect/probation drives fall to the
        # BACK of the candidate order (excluded from primaries and
        # hedge targets) — but only capacity-permitting: with fewer
        # than k healthy readers the plan keeps everyone in play
        if healthtrack.quarantine_enabled():
            sus = {i for i, r in enumerate(self.readers)
                   if r is not None
                   and healthtrack.is_suspect_disk(r.disk)}
            if sus and sum(1 for r in self.readers
                           if r is not None) - len(sus) >= self.k:
                self.avoid = frozenset(sus)
        # device-routed groups defer per-frame bitrot verification into
        # the fused verify+decode program (one dispatch hashes AND
        # reconstructs — cmd/erasure-decode.go:111-150's inseparable
        # verify-then-decode, device form); small/CPU groups verify
        # inline at read time as before. The digest comparison must use
        # the algorithm the frames were WRITTEN with (per-shard
        # csum.algorithm — it may differ from the server's current
        # bitrot config), so deferral needs every reader on one
        # streaming device-kernel algorithm.
        algos = {r.algo for r in self.readers if r is not None}
        self.part_algo = algos.pop() if len(algos) == 1 else None
        self.defer_verify = (
            self.part_algo is not None and self.part_algo.streaming
            and self.codec._device_hash_kernel(self.part_algo)
            is not None
            and self.codec._route(GET_BATCH_BLOCKS * self.k
                                  * self.shard_size) == "device")

    def read_group(self, blocks: list, geoms: list
                   ) -> tuple[list, bool, float, frozenset]:
        """One group's raw shard reads, with the quorum-loss →
        per-block-hedged-read degradation unchanged; returns
        (per-block reads, degraded, read seconds, benign-missing
        reader indices — plan-caused misses the verify step must not
        flag a heal for)."""
        t0 = time.perf_counter()
        degraded = False
        # pre-seeded with earlier groups' plan-caused misses: a reader
        # condemned by a latency hedge in group 1 stays benign-missing
        # for every later group of this part
        benign: set = set(self.benign_hist)
        with self.io_lock, telemetry.span("pipeline.read_group",
                                          blocks=len(blocks)):
            readers = self.readers
            try:
                reads = self.eng._read_group_shards_raw(
                    readers, blocks, self.shard_size,
                    [g[3] for g in geoms], self.k, self.n,
                    collect_digests=self.defer_verify,
                    avoid=self.avoid, benign_sink=benign)
                self.benign_hist = set(benign)
            except api_errors.InsufficientReadQuorum:
                # group-granular hedging can lose quorum where
                # block-granular recovery still succeeds (distinct
                # readers corrupted at distinct blocks): rebuild
                # the readers the group attempt burned and degrade
                # to per-block hedged reads
                for r in readers:
                    if r is not None:
                        r.close()
                readers[:] = self._make_readers()
                self.reader_gen[0] += 1
                degraded = True
                benign.clear()      # recovery mode: flag everything
                self.benign_hist = set()
                reads = [self.eng._read_block_shards_raw(
                    readers, g[0], self.shard_size, g[3], self.k,
                    self.n, collect_digests=self.defer_verify)
                    for g in geoms]
        return reads, degraded, time.perf_counter() - t0, \
            frozenset(benign)

    def _submit(self, spec) -> object:
        """Queue one group's reads on the prefetch pool, carrying the
        caller's span context so the reads attach to the request tree."""
        from ..parallel import pipeline as pl
        return pl.prefetch(self.read_group, *spec)

    def prime(self) -> None:
        """Issue this part's FIRST group read on the prefetch pool —
        called by the PREVIOUS part when it reaches its last group, so
        the drive I/O of part N+1 overlaps part N's verify+decode."""
        if self._pending is not None or self._primed or not self.specs:
            return
        self._ensure_readers()
        self._pending = self._submit(self.specs[0])
        self._primed = True

    def stream(self, next_plan: Optional["_PartReadPlan"] = None
               ) -> Iterator[bytes]:
        from ..parallel import pipeline as pl
        self._ensure_readers()
        readers = self.readers
        k, n = self.k, self.n
        offset, length = self.offset, self.length
        for si, (blocks, geoms) in enumerate(self.specs):
            group = []
            with telemetry.span("get.read_shards"):
                lookahead = self._pending
                self._pending = None
                if lookahead is not None and pl.cancel_prefetch(lookahead):
                    # still queued behind other streams' prefetch
                    # tasks: reading inline is strictly faster than
                    # waiting for a task that hasn't started
                    lookahead = None
                    self._primed = False
                if lookahead is not None:
                    t0 = time.perf_counter()
                    # the task runs read_group: its shard reads ride
                    # the hedged state machine, so the deadline lives
                    # inside the read itself
                    # check: allow(deadline) task body IS the hedged reader
                    reads, degraded, read_s, benign = lookahead.result()
                    pl.STATS.record_get_group(
                        True, time.perf_counter() - t0, read_s)
                else:
                    reads, degraded, _, benign = self.read_group(blocks,
                                                                 geoms)
                    pl.STATS.record_get_group(False)
            # readers-list generation THIS group's frames came from
            # (the N+1 lookahead may rebuild the list mid-verify)
            gen_at_read = self.reader_gen[0]
            self.heal_required = self.heal_required or degraded
            # issue the NEXT group's reads on the drive pool before
            # this group's verify+decode — decode overlaps drive
            # I/O, bounded to ONE group of lookahead staging; at the
            # LAST group the lookahead crosses into the next part
            if pl.ENABLED and si + 1 < len(self.specs):
                self._pending = self._submit(self.specs[si + 1])
            elif si + 1 == len(self.specs) and next_plan is not None:
                next_plan.prime()
            for (b, block_off, block_len, shard_len), \
                    (shards, digests, had_errors) in zip(geoms, reads):
                self.heal_required = self.heal_required or had_errors
                group.append([b, block_off, block_len, shard_len,
                              shards, digests])
            with telemetry.span("pipeline.verify_decode",
                                blocks=len(blocks)):
                if self.eng._verify_and_reconstruct_group(
                        self.codec, group, k, n, readers,
                        self.shard_size,
                        self.part_algo or self.eng.bitrot_algo,
                        io_lock=self.io_lock,
                        reader_gen=(self.reader_gen, gen_at_read),
                        benign_missing=benign):
                    self.heal_required = True
            with telemetry.span("get.join"):
                out = []
                for b, block_off, block_len, shard_len, shards, _dg \
                        in group:
                    data = np.concatenate([s[:shard_len]
                                           for s in shards[:k]])
                    begin = max(offset - block_off, 0)
                    end = min(offset + length - block_off, block_len)
                    # slice the view FIRST: tobytes on the full block
                    # then slicing again was two payload copies
                    out.append(data[begin:end].tobytes())
            yield from out
        if self.heal_required and not self.suppress_heal_flag \
                and self.eng.on_degraded_read is not None:
            try:
                self.eng.on_degraded_read(self.bucket, self.object_name)
            except Exception:  # noqa: BLE001 — heal is best-effort
                pass

    def close(self) -> None:
        """Settle any in-flight lookahead, then close the readers (an
        abandoned generator must not leave a pool thread racing closed
        streams)."""
        from ..parallel import pipeline as pl
        if self._pending is not None \
                and not pl.cancel_prefetch(self._pending):
            try:
                # check: allow(deadline) task body IS the hedged reader
                self._pending.result()
            except BaseException:  # noqa: BLE001 — abandoned read
                pass
        self._pending = None
        if self.readers is not None:
            for r in self.readers:
                if r is not None:
                    r.close()
            self.readers = None


def _read_full(reader, n: int) -> bytes:
    """io.ReadFull semantics: exactly n bytes unless EOF."""
    buf = b""
    while len(buf) < n:
        chunk = reader.read(n - len(buf))
        if not chunk:
            break
        buf += chunk
    return buf


def _split_into(shards: np.ndarray, block: np.ndarray, s_t: int) -> None:
    """`block` laid into the zeroed (k, S) `shards` as `Codec.split`
    lays it: shard i = bytes [i*S_t, (i+1)*S_t) of the block in the
    first S_t columns of row i, zero everywhere else."""
    whole, rest = divmod(len(block), s_t)
    shards[:whole, :s_t] = block[:whole * s_t].reshape(whole, s_t)
    if rest:
        shards[whole, :rest] = block[whole * s_t:]


def _lay_short_block(buf: np.ndarray, row: int, n: int, k: int,
                     s_len: int) -> np.ndarray:
    """The short last block of a stream, read into the first n bytes of
    buf[row] (a (k * s_len,) row of a staging buffer), laid out in
    place (`_split_into`, S_t = ceil(n / k)) in the (k, s_len) view of
    the row, so that it is one more block of its group at the full
    shard length. -> the group's (row + 1,) shard lengths: s_len for
    the whole blocks, S_t last."""
    s_t = -(-n // k)
    block = buf[row, :n].copy()
    buf[row] = 0
    _split_into(buf[row].reshape(k, s_len), block, s_t)
    lengths = np.full(row + 1, s_len, np.int32)
    lengths[row] = s_t
    return lengths


def _lay_subblock(row: np.ndarray, n: int, k: int,
                  s_len: int) -> tuple[np.ndarray, np.ndarray]:
    """A body of n bytes, under one block, read into row[:n]: its one
    block laid (`_split_into`, S_t = ceil(n / k)) at its S rung S_r
    (parallel/ladder.s_rung) instead of the full `s_len`. -> (data
    (1, k, S_r), lengths [S_t]). The frames written are those of the
    full-S layout: 32 + S_t bytes a shard."""
    from ..parallel import ladder
    s_t = -(-n // k)
    data = np.zeros((1, k, ladder.s_rung(s_len, s_t)), np.uint8)
    _split_into(data[0], row[:n], s_t)
    return data, np.array([s_t], np.int32)


def _read_full_into(reader, view: np.ndarray) -> int:
    """io.ReadFull into a caller buffer: fills `view` (a uint8 array
    slice) unless EOF; returns bytes read. Uses the reader's zero-copy
    readinto_full when it has one (HashReader), else falls back to
    read()+copy (chunked-signature readers, plain streams)."""
    fn = getattr(reader, "readinto_full", None)
    if fn is not None:
        return fn(memoryview(view))  # type: ignore[arg-type]
    n = len(view)
    got = 0
    while got < n:
        chunk = reader.read(n - got)
        if not chunk:
            break
        ln = len(chunk)
        view[got:got + ln] = np.frombuffer(chunk, dtype=np.uint8)
        got += ln
    return got
