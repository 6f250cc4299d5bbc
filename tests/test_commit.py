"""engine._commit: a PUT commits in two quorum fan-outs over its drives
— stage (close the shard writer, write the staged journal), rename —
and a drive lost in either still leaves a quorum commit that MRF
converges; below quorum at stage nothing was told to rename."""

from __future__ import annotations

import os

import pytest

from minio_tpu.object import ErasureSetObjects, PutOptions, api_errors
from minio_tpu.object import metadata as meta
from minio_tpu.object.sets import ErasureSets
from minio_tpu.storage import XLStorage, errors as serr, new_format_erasure_v3
from minio_tpu.storage.naughty import NaughtyDisk

K, M = 4, 2
NDISKS = K + M
BLOCK = 1 << 16
TMP_VOL = ".minio.sys/tmp"

# what fails on the one bad drive, by where in the commit it fails: a
# NaughtyDisk has no append handle, so a small object's frames reach
# the drive when its writer is closed
FAIL_AT = {"close": "append_file", "stage": "write_metadata",
           "rename": "rename_data"}


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


@pytest.mark.parametrize("size", [0, 1000, 3 * BLOCK + 17])
def test_put_commits_in_two_quorum_fanouts(tmp_path, monkeypatch, size):
    eng = _engine(tmp_path)
    stages = []
    real = meta.for_each_disk_quorum

    def spy(disks, fn, quorum, stall_s=None, stage="write", **kw):
        stages.append(stage)
        return real(disks, fn, quorum, stall_s=stall_s, stage=stage, **kw)

    monkeypatch.setattr(meta, "for_each_disk_quorum", spy)
    eng.put_object("b", "o", b"s" * size)
    assert [s for s in stages if s != "shard_write"] == ["stage", "rename"]
    _, it = eng.get_object("b", "o")
    assert b"".join(it) == b"s" * size
    for d in eng.disks:                     # nothing left staged
        assert d.list_dir(TMP_VOL, "") == []


@pytest.mark.parametrize("where", sorted(FAIL_AT))
def test_one_drive_lost_in_the_commit_still_commits_and_converges(
        tmp_path, where):
    """One drive fails its close, its staged write or its rename: the
    PUT commits at quorum, the drive is counted lost (the MRF feed),
    and the background heal gives it the shard back."""
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
            (1 if where == "rename" else 0)  # not staged: not renamed
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


@pytest.mark.parametrize("where", ["close", "stage"])
def test_below_quorum_at_stage_aborts_with_the_previous_version(
        tmp_path, where):
    """M + 1 drives fail in the stage fan-out: the PUT fails, no drive
    was told to rename, the previous version reads back and the bucket
    is byte for byte what it was; what is left is in tmp."""
    eng = _engine(tmp_path, naughty=M + 1)
    eng.put_object("b", "o", b"old" * 500)
    before = _bucket_tree(eng)
    for d in eng.disks[:M + 1]:
        d.fail_verbs[FAIL_AT[where]] = serr.FaultyDisk("boom")
    with pytest.raises(api_errors.InsufficientWriteQuorum):
        eng.put_object("b", "o", b"new" * 700)
    for d in eng.disks[:M + 1]:
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
