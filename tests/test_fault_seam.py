"""The seam the benchmark's controls stand on. `benchmark/benchlib/
faults.py` plants its encode faults by wrapping `Codec.
encode_and_hash_batch` and touching the parity and digests its result
carries (a `.parity` attribute, a bare (B, m, S) array, or the rows
past k of a join). Whatever shape the program gives that result — and
at whatever ladder rung the launch ran, its pad blocks cut off — a
planted fault must still reach the drives
through an engine with a batch former — else the PUT cells' controls go
blind and `correct` stops meaning anything. The plain reference
(`benchlib/reference.py`) says what a sound PUT leaves on the drives."""

from __future__ import annotations

import glob
import io
import os
import sys

import numpy as np
import pytest

from minio_tpu.object import ErasureSetObjects
from minio_tpu.object import codec as codec_mod
from minio_tpu.parallel.scheduler import BatchScheduler
from minio_tpu.storage import XLStorage, new_format_erasure_v3

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from benchlib import faults, reference  # noqa: E402

K, M = 4, 2
N = K + M
BLOCK = 1 << 16
SHARD = BLOCK // K
FRAME = reference.DIGEST_BYTES + SHARD


@pytest.fixture
def plant(monkeypatch):
    """Plant a fault on an XLA-CPU-forced codec; the class attribute
    faults.plant() replaces goes back when the test ends."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    # monkeypatch records the original here and restores it at teardown
    monkeypatch.setattr(codec_mod.Codec, "encode_and_hash_batch",
                        codec_mod.Codec.encode_and_hash_batch)
    return faults.plant


def _put(tmp_path, sched, blocks: float):
    """PUT one object of `blocks` blocks (2.5: two whole blocks and a
    short one) -> (body, part file by shard index as the drives hold
    it)."""
    fmts = new_format_erasure_v3(1, N)
    disks = []
    for j in range(N):
        d = XLStorage(str(tmp_path / f"d{j}"))
        d.write_format(fmts[0][j])
        disks.append(d)
    eng = ErasureSetObjects(disks, K, M, block_size=BLOCK, scheduler=sched)
    eng.make_bucket("b")
    body = np.random.default_rng(int(blocks)).integers(
        0, 256, int(blocks * BLOCK), dtype=np.uint8).tobytes()
    eng.put_object("b", "obj", io.BytesIO(body), len(body))
    files: list = [None] * N
    for j, shard in enumerate(reference.shard_of_drive("b", "obj", N)):
        (path,) = glob.glob(str(tmp_path / f"d{j}" / "b" / "obj" / "*"
                                / "part.1"))
        with open(path, "rb") as f:
            files[shard] = np.frombuffer(f.read(), dtype=np.uint8)
    return body, files


# 3 blocks ride the engine's inline path (one group, one launch); 20 the
# pipelined one (groups of 8, 8 and 4 through the former)
@pytest.mark.parametrize("blocks", [3, 20])
@pytest.mark.parametrize("fault", ["", "encode-parity-altered",
                                   "encode-digest-skipped"])
def test_planted_encode_fault_reaches_the_drives(tmp_path, plant, fault,
                                                 blocks):
    if fault:
        plant(fault)
    sched = BatchScheduler(max_wait=0.01)
    try:
        body, files = _put(tmp_path, sched, blocks)
        st = sched.stats()
    finally:
        sched.close()
    launches = st["verbs"]["encode"]["batches"]
    assert launches >= 1 and st["errors"]["encode"] == 0
    want = [np.frombuffer(f, dtype=np.uint8)
            for f in reference.part_files(body, K, M, BLOCK)]
    assert [len(f) for f in files] == [len(w) for w in want]
    wrong = [np.flatnonzero(f != w) for f, w in zip(files, want)]
    for i in range(K):
        assert wrong[i].size == 0           # data rows: never touched
    if not fault:
        assert all(w.size == 0 for w in wrong)
    elif fault == "encode-parity-altered":
        # one bit of the first parity row, first byte of a launch's
        # first block: the digest beside it still covers the true byte
        assert all(w.size == 0 for w in wrong[K + 1:])
        at = wrong[K]
        assert at.size == launches
        assert ((at % FRAME) == reference.DIGEST_BYTES).all()
        assert ((files[K][at] ^ want[K][at]) == 1).all()
    else:
        # every parity frame: shard right, digest left zero
        for i in range(K, N):
            framed = files[i].reshape(blocks, FRAME)
            assert not framed[:, :reference.DIGEST_BYTES].any()
            assert (wrong[i] % FRAME < reference.DIGEST_BYTES).all()


@pytest.mark.parametrize("blocks", [3, 20])
def test_device_put_writes_data_rows_out_of_the_streams_buffer(
        tmp_path, plant, monkeypatch, blocks):
    """No fault planted: what the former hands a stream has m rows, and
    the data rows that go to the drives are the very array the stream
    submitted — a view of its own buffer, not a copy made by a join."""
    seen = []
    unpack = ErasureSetObjects._unpack_fused

    def spy(self, codec, data, fused, **kw):
        out = unpack(self, codec, data, fused, **kw)
        seen.append((data, fused, out))
        return out
    monkeypatch.setattr(ErasureSetObjects, "_unpack_fused", spy)
    sched = BatchScheduler(max_wait=0.01)
    try:
        _put(tmp_path, sched, blocks)
    finally:
        sched.close()
    assert sum(d.shape[0] for d, _f, _o in seen) == blocks
    for data, fused, out in seen:
        assert fused is not None and fused[0].shape[1:] == (M, SHARD)
        assert out[0] is data and not data.flags.owndata


@pytest.mark.parametrize("fault", ["", "encode-parity-altered",
                                   "encode-digest-skipped"])
def test_planted_encode_fault_reaches_the_drives_through_a_ragged_launch(
        tmp_path, plant, fault):
    """The seam holds for the ragged row: an object of 2.5 blocks is
    ONE group whose launch enters through `Codec.encode_and_hash_batch`
    - the method the faults wrap - with `lengths` among its keywords;
    the clean run equals the reference, a planted fault reaches the
    drives in the short block's frames as in the whole ones."""
    if fault:
        plant(fault)
    sched = BatchScheduler(max_wait=0.01)
    try:
        body, files = _put(tmp_path, sched, 2.5)
        st = sched.stats()
    finally:
        sched.close()
    enc = st["verbs"]["encode"]
    assert (enc["groups"], enc["batches"], enc["ragged_batches"],
            enc["short_blocks"]) == (1, 1, 1, 1)
    assert st["errors"]["encode"] == 0
    want = [np.frombuffer(f, dtype=np.uint8)
            for f in reference.part_files(body, K, M, BLOCK)]
    assert [len(f) for f in files] == [len(w) for w in want] \
        == [2 * FRAME + reference.DIGEST_BYTES + SHARD // 2] * N
    wrong = [np.flatnonzero(f != w) for f, w in zip(files, want)]
    for i in range(K):
        assert wrong[i].size == 0           # data rows: never touched
    if not fault:
        assert all(w.size == 0 for w in wrong)
    elif fault == "encode-parity-altered":
        # one bit, first byte of the launch's first parity row
        assert all(w.size == 0 for w in wrong[K + 1:])
        assert wrong[K].tolist() == [reference.DIGEST_BYTES]
        assert files[K][wrong[K][0]] ^ want[K][wrong[K][0]] == 1
    else:
        # every parity frame, the short block's too: digest left zero
        starts = (0, FRAME, 2 * FRAME)
        for i in range(K, N):
            for at in starts:
                assert not files[i][at:at + reference.DIGEST_BYTES].any()
            assert all(any(at <= w < at + reference.DIGEST_BYTES
                           for at in starts) for w in wrong[i])
            assert wrong[i].size > 2 * reference.DIGEST_BYTES
