"""engine._commit: a PUT commits in ONE quorum fan-out over its drives,
rename — each drive's shard writer closes in the last shard-write task,
which is the barrier — and a drive lost in either still leaves a quorum
commit that MRF converges; below quorum at the close nothing was told to
rename. Where no group was known to be the last (a 0-byte object, a
stream of unknown length that ended on a group) one fan-out of closes
runs before the rename."""

from __future__ import annotations

import io
import os
import threading
import time

import numpy as np
import pytest

from minio_tpu.features import crypto as sse
from minio_tpu.object import ErasureSetObjects, PutOptions, api_errors
from minio_tpu.object import bitrot_io
from minio_tpu.object import metadata as meta
from minio_tpu.object.engine import ENCODE_BATCH_BLOCKS
from minio_tpu.object.sets import ErasureSets
from minio_tpu.storage import XLStorage, errors as serr, new_format_erasure_v3
from minio_tpu.storage.naughty import NaughtyDisk
from minio_tpu.utils import healthtrack

K, M = 4, 2
NDISKS = K + M
BLOCK = 1 << 16
GROUP = ENCODE_BATCH_BLOCKS * BLOCK
TMP_VOL = ".minio.sys/tmp"

# what fails on the one bad drive, by where in the commit it fails: a
# NaughtyDisk has no append handle, so a small object's frames reach
# the drive when its writer is closed
FAIL_AT = {"close": "append_file", "rename": "rename_data"}


def _drives(tmp_path, naughty: int):
    fmts = new_format_erasure_v3(1, NDISKS)
    out = []
    for j in range(NDISKS):
        d = XLStorage(str(tmp_path / f"d{j}"))
        d.write_format(fmts[0][j])
        out.append(NaughtyDisk(d) if j < naughty else d)
    return out


def _engine(tmp_path, naughty: int = 0):
    e = ErasureSetObjects(_drives(tmp_path, naughty), K, M,
                          block_size=BLOCK)
    e.make_bucket("b")
    return e


def _bucket_tree(eng):
    out = {}
    for j, d in enumerate(eng.disks):
        root = getattr(d, "inner", d).root
        for dirpath, _, files in os.walk(os.path.join(root, "b")):
            for f in files:
                fp = os.path.join(dirpath, f)
                with open(fp, "rb") as fh:
                    out[(j, os.path.relpath(fp, root))] = fh.read()
    return out


def _body(size: int) -> bytes:
    return np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _put(eng, name: str, body: bytes, known: bool = True, opts=None):
    if known:
        return eng.put_object("b", name, body, opts=opts)
    return eng.put_object("b", name, io.BytesIO(body), size=-1, opts=opts)


def _spy_fanouts(monkeypatch):
    """[(stage, closed)] of every quorum fan-out: `closed`, for a
    shard-write fan-out, is how many of its live writers were closed
    when it returned."""
    seen = []
    real = meta.for_each_disk_quorum

    def spy(disks, fn, quorum, stall_s=None, stage="write", **kw):
        out = real(disks, fn, quorum, stall_s=stall_s, stage=stage, **kw)
        closed = sum(1 for w in disks if w is not None and w.closed) \
            if stage == "shard_write" else None
        seen.append((stage, closed))
        return out

    monkeypatch.setattr(meta, "for_each_disk_quorum", spy)
    return seen


# (size, known length, the commit's fan-outs)
CASES = {
    "small": (1000, True, ["rename"]),
    "three_blocks_short_tail": (3 * BLOCK + 17, True, ["rename"]),
    "one_whole_group": (GROUP, True, ["rename"]),
    "two_whole_groups": (2 * GROUP, True, ["rename"]),
    "pipelined_short_tail": (2 * GROUP + 4 * BLOCK + 999, True, ["rename"]),
    "unknown_length_short_tail": (3 * BLOCK + 17, False, ["rename"]),
    "zero_bytes": (0, True, ["close", "rename"]),
    "unknown_length_on_a_group": (GROUP, False, ["close", "rename"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_put_commits_in_one_quorum_fanout(tmp_path, monkeypatch, case):
    size, known, want = CASES[case]
    eng = _engine(tmp_path)
    seen = _spy_fanouts(monkeypatch)
    body = _body(size)
    _put(eng, "o", body, known)
    writes = [c for s, c in seen if s == "shard_write"]
    assert [s for s, _ in seen if s != "shard_write"] == want
    if want == ["rename"]:
        # the last shard write closed every writer; none closed before
        assert writes[-1] == NDISKS and not any(writes[:-1])
    else:
        assert not any(writes)
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == body
    for d in eng.disks:                     # nothing left staged
        assert d.list_dir(TMP_VOL, "") == []


def test_multi_group_put_closes_each_writer_in_its_last_write_task(
        tmp_path, monkeypatch):
    """Three groups through the pipelined loop: each writer is closed
    once, by a drive task, after its third group's frames."""
    eng = _engine(tmp_path)
    events = []
    mu = threading.Lock()
    real_frames = bitrot_io.StreamingBitrotWriter.write_frames
    real_close = bitrot_io.StreamingBitrotWriter.close
    ran = []
    real_pipe = ErasureSetObjects._encode_stream_pipelined

    def write_frames(self, blocks, digests):
        with mu:
            events.append((id(self), "frames"))
        return real_frames(self, blocks, digests)

    def close(self):
        with mu:
            events.append((id(self), "close",
                           threading.current_thread() is
                           threading.main_thread()))
        return real_close(self)

    def pipelined(self, *a, **kw):
        ran.append(1)
        return real_pipe(self, *a, **kw)

    monkeypatch.setattr(bitrot_io.StreamingBitrotWriter, "write_frames",
                        write_frames)
    monkeypatch.setattr(bitrot_io.StreamingBitrotWriter, "close", close)
    monkeypatch.setattr(ErasureSetObjects, "_encode_stream_pipelined",
                        pipelined)
    body = _body(2 * GROUP + 4 * BLOCK + 999)
    eng.put_object("b", "o", body)
    assert ran == [1]
    per = {}
    for ev in events:
        per.setdefault(ev[0], []).append(ev[1:])
    assert len(per) == NDISKS
    for evs in per.values():
        assert evs == [("frames",)] * 3 + [("close", False)], evs
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == body


@pytest.mark.parametrize("known", [True, False], ids=["serial", "pipelined"])
def test_sse_put_commits_in_one_fanout(tmp_path, monkeypatch, known):
    """Under SSE the last of the finish batches (the ciphertext tail
    and the tag trailer) is the last group: its task closes."""
    eng = _engine(tmp_path)
    seen = _spy_fanouts(monkeypatch)
    oek, base = bytes(range(32)), bytes(range(100, 112))
    pt = _body(3 * BLOCK + 17 if known else 2 * GROUP + 5)
    _put(eng, "o", pt, known,
         opts=PutOptions(sse_spec=sse.DeviceSSE(oek, base)))
    writes = [c for s, c in seen if s == "shard_write"]
    assert [s for s, _ in seen if s != "shard_write"] == ["rename"]
    assert writes[-1] == NDISKS and not any(writes[:-1])
    _, it = eng.get_object("b", "o")
    assert len(b"".join(it)) == sse.encrypted_size(len(pt))


@pytest.mark.parametrize("size", [0, 1000, 2 * GROUP + 4 * BLOCK + 999])
def test_no_staging_directory_holds_a_journal_at_rename(
        tmp_path, monkeypatch, size):
    """At the moment each drive is told to rename, its staging directory
    holds the data dir and nothing else: no staged xl.meta exists."""
    eng = _engine(tmp_path)
    held = []
    real = XLStorage.rename_data

    def spy(self, sv, sp, dd, dv, dp, version_id="", fi=None):
        held.append(sorted(os.listdir(self._file_path(sv, sp))))
        assert fi is not None
        return real(self, sv, sp, dd, dv, dp, version_id, fi)

    monkeypatch.setattr(XLStorage, "rename_data", spy)
    oi = eng.put_object("b", "o", _body(size))
    data_dir = eng.disks[0].read_version("b", "o").data_dir
    assert held == [[data_dir]] * NDISKS, held
    assert oi.size == size


def test_writer_dropped_from_the_last_write_is_not_renamed(
        tmp_path, monkeypatch):
    """A drive that stalls in the last shard-write task (its close: the
    frames reach a NaughtyDisk there) is dropped by the quorum-ack lane
    once quorum is closed: it is never told to rename, and the commit
    counts it lost, so MRF is fed."""
    monkeypatch.setenv("MINIO_TPU_WRITE_STALL_FLOOR_S", "0.1")
    monkeypatch.setenv("MINIO_TPU_WRITE_STALL_CEIL_S", "0.2")
    healthtrack.TRACKER.reset()
    eng = _engine(tmp_path, naughty=1)
    nd = eng.disks[0]
    lost, degraded = [], []
    real_commit = ErasureSetObjects._commit

    def commit(self, *a, **kw):
        lost.append(real_commit(self, *a, **kw))
        return lost[-1]

    monkeypatch.setattr(ErasureSetObjects, "_commit", commit)
    monkeypatch.setattr(eng, "_notify_degraded",
                        lambda *a: degraded.append(a))
    nd.stall_verbs = {"append_file": 0.8}
    try:
        body = _body(2 * BLOCK + 5)
        eng.put_object("b", "o", body)
    finally:
        nd.stall_verbs = {}
    try:
        assert nd.stats.stalls == 1
        assert nd.stats.calls.get("rename_data", 0) == 0
        assert lost == [1]
        assert degraded and degraded[0][:2] == ("b", "o")
        _, it = eng.get_object("b", "o")
        assert b"".join(it) == body
    finally:
        time.sleep(0.8)          # the abandoned task settles
        healthtrack.TRACKER.reset()


@pytest.mark.parametrize("where", sorted(FAIL_AT))
def test_one_drive_lost_in_the_commit_still_commits_and_converges(
        tmp_path, where):
    """One drive fails its close or its rename: the PUT commits at
    quorum, the drive is counted lost (the MRF feed), and the
    background heal gives it the shard back."""
    drives = _drives(tmp_path, naughty=1)
    nd = drives[0]
    sets = ErasureSets.from_storage(
        drives, set_count=1, set_drive_count=NDISKS, parity=M,
        block_size=BLOCK,
        mrf_options=dict(max_retries=10, backoff_base=0.02,
                         backoff_max=0.2))
    try:
        sets.make_bucket("b")
        body = os.urandom(2 * BLOCK + 5)
        nd.fail_verbs[FAIL_AT[where]] = serr.FaultyDisk("boom")
        sets.put_object("b", "o", body)
        assert nd.stats.calls.get("rename_data", 0) == \
            (1 if where == "rename" else 0)  # not closed: not renamed
        assert sets.mrf_stats()["queued"] >= 1
        _, it = sets.get_object("b", "o")
        assert b"".join(it) == body
        del nd.fail_verbs[FAIL_AT[where]]   # the drive recovers
        assert sets.drain_mrf(15.0)
        stats = sets.mrf_stats()
        assert stats["pending"] == 0 and stats["healed"] >= 1
        eng = sets.sets[0]
        fi = eng.disks[0].read_version("b", "o")
        eng.disks[0].check_parts("b", "o", fi)
        eng.disks[0].verify_file("b", "o", fi)
    finally:
        sets.close()


# (new body, known length): where the close that fails below quorum runs
CLOSE_AT = {
    "one_group": (b"new" * 700, True),
    "last_of_three_groups": (_body(2 * GROUP + 4 * BLOCK + 999), True),
    "fallback_zero_bytes": (b"", True),
    "fallback_unknown_length_on_a_group": (_body(GROUP), False),
}


@pytest.mark.parametrize("where", sorted(CLOSE_AT))
def test_below_quorum_at_the_close_aborts_with_the_previous_version(
        tmp_path, where):
    """M + 1 drives fail their close — in the last shard-write task, or
    in the fallback close fan-out: the PUT fails, no drive was told to
    rename, the previous version reads back and the bucket is byte for
    byte what it was; what is left is in tmp."""
    body, known = CLOSE_AT[where]
    eng = _engine(tmp_path, naughty=NDISKS)
    eng.put_object("b", "o", b"old" * 500)
    before = _bucket_tree(eng)
    for d in eng.disks[:M + 1]:
        d.fail_verbs[FAIL_AT["close"]] = serr.FaultyDisk("boom")
    with pytest.raises(api_errors.InsufficientWriteQuorum):
        _put(eng, "o", body, known)
    for d in eng.disks:
        assert d.stats.calls.get("rename_data", 0) == 1   # the old PUT's
        d.fail_verbs.clear()
    assert _bucket_tree(eng) == before
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == b"old" * 500


def test_versioned_put_stages_each_drives_own_index(tmp_path):
    """The FileInfo a drive is handed at rename is its own copy: shard
    index i + 1 on the drive that holds shard i, one version id on
    all."""
    eng = _engine(tmp_path)
    oi = eng.put_object("b", "v", b"z" * 5000,
                        opts=PutOptions(versioned=True))
    eng.put_object("b", "v", b"y" * 100, opts=PutOptions(versioned=True))
    fis = [d.read_version("b", "v", oi.version_id) for d in eng.disks]
    dist = fis[0].erasure.distribution
    assert [f.erasure.index for f in fis] == dist
    assert {f.version_id for f in fis} == {oi.version_id}
    assert len(eng.disks[0].read_versions("b", "v")) == 2
