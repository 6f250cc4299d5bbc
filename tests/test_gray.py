"""Gray-failure chaos: drives (and peers) that are SLOW while still
answering. A NaughtyDisk stall (the drive answers after 0.5+ s) drives
the three behaviors of the gray-failure plane:

  * adaptive hedged reads bound GET latency under a mid-GET stall,
  * quorum-ack writes bound PUT / multipart-commit latency under a
    mid-PUT stall, with zero acked-write loss once MRF drains,
  * the DiskMonitor quarantine walks the slow drive through
    suspect → probation → heal-verified re-admission, excluding it
    from read plans while convicted.

These are the fast seeded cases (tier-1); timing asserts use wide
margins (bounded-by < stall) so a loaded CI box cannot flake them.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from minio_tpu.object.background import DiskMonitor
from minio_tpu.object.sets import ErasureSets
from minio_tpu.storage import XLStorage
from minio_tpu.storage.naughty import FaultSchedule, NaughtyDisk
from minio_tpu.utils import healthtrack

pytestmark = pytest.mark.chaos

K, M = 4, 2
NDISKS = K + M
BLOCK = 1 << 16
STALL = 0.6

READ_STALLS = ("read_file_stream", "read_file", "read_all")
WRITE_STALLS = ("append_file", "create_file", "write_all",
                "write_metadata", "rename_data", "rename_file")

MRF_TEST_OPTIONS = dict(max_retries=10, backoff_base=0.02,
                        backoff_max=0.25)


@pytest.fixture(autouse=True)
def _gray_env(monkeypatch):
    """Tight adaptive deadlines so the plane bites at test scale, and
    a clean tracker so one test's convictions never leak into the
    next."""
    monkeypatch.setenv("MINIO_TPU_HEDGE_FLOOR_S", "0.05")
    monkeypatch.setenv("MINIO_TPU_HEDGE_CEIL_S", "0.1")
    monkeypatch.setenv("MINIO_TPU_WRITE_STALL_FLOOR_S", "0.1")
    monkeypatch.setenv("MINIO_TPU_WRITE_STALL_CEIL_S", "0.2")
    healthtrack.TRACKER.reset()
    yield
    healthtrack.TRACKER.reset()


def payload(size: int, seed: int = 11) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def make_sets(tmp_path, n: int = NDISKS, parity: int = M
              ) -> tuple[ErasureSets, NaughtyDisk]:
    """1 set x n drives, drive 0 wrapped in a (disarmed) NaughtyDisk."""
    drives: list = [XLStorage(str(tmp_path / f"d{j}"))
                    for j in range(n)]
    nd = NaughtyDisk(drives[0], enabled=False)
    drives[0] = nd
    sets = ErasureSets.from_storage(
        drives, set_count=1, set_drive_count=n, parity=parity,
        block_size=BLOCK, mrf_options=dict(MRF_TEST_OPTIONS))
    sets.make_bucket("b")
    return sets, nd


def stall_on(nd: NaughtyDisk, verbs, dur: float = STALL) -> None:
    nd.stall_verbs = {v: dur for v in verbs}
    nd.arm()


def assert_converged(sets: ErasureSets, datas: dict) -> None:
    """Every acked write reads back byte-identical and every shard is
    whole on every drive (the no-acked-write-loss bar)."""
    assert sets.drain_mrf(30.0)
    assert sets.mrf_stats()["pending"] == 0
    for name, data in datas.items():
        _, it = sets.get_object("b", name)
        assert b"".join(it) == data, name
        for d in sets.sets[0].disks:
            fi = d.read_version("b", name)
            d.check_parts("b", name, fi)


def test_stall_mid_get_bounded(tmp_path):
    """A drive stalling every read answers the GET anyway — the hedged
    reader races a spare shard read at the adaptive deadline and the
    client never waits out the stall."""
    sets, nd = make_sets(tmp_path)
    data = payload(3 * BLOCK + 123)
    sets.put_object("b", "o", data)
    from minio_tpu.utils.telemetry import REGISTRY
    hedged = REGISTRY.counter("minio_tpu_hedged_reads_total")
    before = hedged.value(trigger="latency")
    stall_on(nd, READ_STALLS)
    try:
        t0 = time.perf_counter()
        _, it = sets.get_object("b", "o")
        got = b"".join(it)
        dt = time.perf_counter() - t0
    finally:
        nd.disarm()
        nd.stall_verbs = {}
    assert got == data
    assert dt < STALL * 0.75, f"GET took {dt:.3f}s against {STALL}s stall"
    assert nd.stats.stalls >= 1          # the stall really fired
    assert hedged.value(trigger="latency") > before
    # a latency hedge is NOT damage: nothing was queued for heal
    assert sets.mrf_stats()["pending"] == 0


def test_hedge_loser_stays_benign_across_groups(tmp_path):
    """A reader condemned by a latency hedge in an early read group
    stays benign-missing for every LATER group of the same stream: a
    multi-group GET against a gray drive must not flag a degraded-read
    heal for shards that are perfectly intact on disk (review
    regression — the single-group case can't catch it)."""
    from minio_tpu.object.engine import GET_BATCH_BLOCKS
    sets, nd = make_sets(tmp_path)
    # 3 read groups' worth of blocks
    data = payload(3 * GET_BATCH_BLOCKS * BLOCK + 31, seed=15)
    sets.put_object("b", "o", data)
    stall_on(nd, READ_STALLS)
    try:
        _, it = sets.get_object("b", "o")
        got = b"".join(it)
    finally:
        nd.disarm()
        nd.stall_verbs = {}
    assert got == data
    assert nd.stats.stalls >= 1
    # plan-caused misses across EVERY group: nothing queued for heal
    assert sets.mrf_stats()["pending"] == 0
    assert sets.mrf_stats()["queued"] == 0
    sets.close()


def test_stall_mid_put_quorum_ack(tmp_path):
    """A drive stalling every write: the PUT acks once quorum is
    durable, the laggard is abandoned to the background lane, and MRF
    converges the object back to full redundancy — zero acked-write
    loss."""
    sets, nd = make_sets(tmp_path)
    data = payload(2 * BLOCK + 77, seed=12)
    stall_on(nd, WRITE_STALLS)
    try:
        t0 = time.perf_counter()
        sets.put_object("b", "o", data)
        dt = time.perf_counter() - t0
    finally:
        nd.disarm()
        nd.stall_verbs = {}
    # without quorum-ack this path pays >= 2 stalls (append flush +
    # meta/rename); with it the ack is bounded by the stall grace
    assert dt < STALL * 1.5, f"PUT took {dt:.3f}s"
    assert nd.stats.stalls >= 1
    assert_converged(sets, {"o": data})
    sets.close()


def test_pool_queueing_is_not_a_stalled_drive(monkeypatch):
    """A fused device batch hands every coalesced stream its shards at
    once, so shard-write fan-outs QUEUE on the drive-io pool. A task
    that waited its turn and then ran well inside the grace is a
    healthy drive, and so is one that ran slow while all its peers
    did: the grace runs from the task's own start and scales with the
    fan-out's own median (on the chip the absolute rule left acked
    12+4 objects with 12 shards under an 8-stream PUT wave)."""
    from concurrent.futures import ThreadPoolExecutor
    from minio_tpu.object import metadata as meta
    from minio_tpu.storage import errors as serr

    task_s, grace_s, n, quorum = 0.4, 0.6, 16, 12
    pool = ThreadPoolExecutor(max_workers=quorum)
    monkeypatch.setattr(meta, "_POOL", pool)
    try:
        # 12 run at once, 4 wait ~task_s for a worker and finish at
        # ~2·task_s: past the grace counted from fan-out start, well
        # inside it counted from their own start
        _, errs = meta.for_each_disk_quorum(
            [object()] * n, lambda i, d: time.sleep(task_s), quorum,
            stall_s=grace_s)
        assert errs == [None] * n, errs
        # a saturated host: EVERY write runs slow, the last one a bit
        # slower — within K x its peers' median it is no laggard
        _, errs = meta.for_each_disk_quorum(
            [object()] * quorum,
            lambda i, d: time.sleep(0.9 if i == 0 else 0.3),
            quorum - 1, stall_s=0.2)
        assert errs == [None] * quorum, errs
        # a drive that outlives the grace where its peers are fast is
        # still abandoned
        _, errs = meta.for_each_disk_quorum(
            [object()] * quorum,
            lambda i, d: time.sleep(3 * grace_s if i == 0 else 0.01),
            quorum - 1, stall_s=grace_s)
        assert isinstance(errs[0], serr.StorageStalled)
        assert errs[1:] == [None] * (quorum - 1)
    finally:
        pool.shutdown(wait=True)


def test_stall_mid_multipart_commit(tmp_path):
    """CompleteMultipartUpload's rename fan-out acks at quorum under a
    stalled drive, and the commit converges through MRF."""
    sets, nd = make_sets(tmp_path)
    data = payload(3 * BLOCK + 17, seed=13)
    uid = sets.new_multipart_upload("b", "mp")
    sets.put_object_part("b", "mp", uid, 1, data)
    from minio_tpu.object.multipart import CompletePart
    pi = sets.list_object_parts("b", "mp", uid)[0]
    stall_on(nd, WRITE_STALLS)
    try:
        t0 = time.perf_counter()
        sets.complete_multipart_upload(
            "b", "mp", uid, [CompletePart(1, pi.etag)])
        dt = time.perf_counter() - t0
    finally:
        nd.disarm()
        nd.stall_verbs = {}
    assert dt < STALL * 1.5, f"complete took {dt:.3f}s"
    assert nd.stats.stalls >= 1
    assert_converged(sets, {"mp": data})
    sets.close()


def test_slow_peer_behind_storage_rpc(tmp_path):
    """A slow REMOTE drive: the stall is injected on the server side
    of storage_rpc, so the whole gray-read crosses the wire — the
    hedged reader must race a stalled PEER exactly like a stalled
    local drive."""
    from minio_tpu.distributed.storage_rpc import (RemoteStorage,
                                                   StorageRPCServer)
    from minio_tpu.distributed.transport import RPCServer

    ak, sk = "graykey", "graysecret1234"
    serving: dict = {}
    naughty = None
    for j in range(NDISKS):
        d = XLStorage(str(tmp_path / f"d{j}"))
        if j == 0:
            naughty = NaughtyDisk(d, enabled=False)
            serving[f"/d{j}"] = naughty
        else:
            serving[f"/d{j}"] = d
    rpc_srv = StorageRPCServer(serving, ak, sk)
    host = RPCServer().start()
    host.mount(rpc_srv.handler)
    remotes = [RemoteStorage("127.0.0.1", host.port, f"/d{j}", ak, sk)
               for j in range(NDISKS)]
    sets = ErasureSets.from_storage(
        remotes, set_count=1, set_drive_count=NDISKS, parity=M,
        block_size=BLOCK, sources=list(remotes),
        mrf_options=dict(MRF_TEST_OPTIONS))
    sets.make_bucket("b")
    try:
        data = payload(2 * BLOCK + 5, seed=14)
        sets.put_object("b", "o", data)
        stall_on(naughty, READ_STALLS)
        t0 = time.perf_counter()
        _, it = sets.get_object("b", "o")
        got = b"".join(it)
        dt = time.perf_counter() - t0
        naughty.disarm()
        naughty.stall_verbs = {}
        assert got == data
        assert dt < STALL * 0.75, f"remote GET took {dt:.3f}s"
        assert naughty.stats.stalls >= 1
    finally:
        sets.close()
        host.stop()


def test_quarantine_probation_readmission_roundtrip(tmp_path,
                                                    monkeypatch):
    """The full state machine: slow traffic convicts the drive
    (suspect), read plans then exclude it entirely, probation probes
    fail while it still stalls and pass once it recovers, and
    re-admission is heal-verified + kicks MRF."""
    monkeypatch.setenv("MINIO_TPU_QUAR_LATENCY_S", "0.2")
    monkeypatch.setenv("MINIO_TPU_QUAR_MIN_SAMPLES", "4")
    monkeypatch.setenv("MINIO_TPU_QUAR_PROBATION_S", "0")
    monkeypatch.setenv("MINIO_TPU_QUAR_PROBES", "2")
    sets, nd = make_sets(tmp_path)
    key = healthtrack.disk_key(nd)
    datas = {}
    for i in range(4):
        datas[f"o{i}"] = payload(BLOCK + i, seed=20 + i)
        sets.put_object("b", f"o{i}", datas[f"o{i}"])
    mon = DiskMonitor(sets, interval=3600)   # manual scans only
    stall_on(nd, READ_STALLS + ("disk_info",))
    for i in range(4):                       # slow traffic = evidence
        _, it = sets.get_object("b", f"o{i}")
        b"".join(it)
    mon.scan_once()
    assert healthtrack.TRACKER.state_of("drive", key) == \
        healthtrack.STATE_SUSPECT

    # convicted: reads exclude the drive entirely AND stay fast
    calls0 = dict(nd.stats.calls)
    t0 = time.perf_counter()
    _, it = sets.get_object("b", "o1")
    got = b"".join(it)
    dt = time.perf_counter() - t0
    assert got == datas["o1"]
    assert dt < 0.3, f"quarantined GET took {dt:.3f}s"
    for v in READ_STALLS:
        assert nd.stats.calls.get(v, 0) == calls0.get(v, 0), v

    # still stalling: the probation probe re-convicts
    mon.scan_once()
    assert healthtrack.TRACKER.state_of("drive", key) in (
        healthtrack.STATE_SUSPECT, healthtrack.STATE_PROBATION)

    # recovery: probes pass, re-admission is heal-verified + MRF kicks
    nd.disarm()
    nd.stall_verbs = {}
    for _ in range(4):
        mon.scan_once()
        if healthtrack.TRACKER.state_of("drive", key) == \
                healthtrack.STATE_OK:
            break
    assert healthtrack.TRACKER.state_of("drive", key) == \
        healthtrack.STATE_OK
    events = [e for _k, e in mon.quarantine_events]
    assert events[:1] == ["suspect"] and events[-1] == "readmit"
    assert "probation" in events
    # re-admission cleared the pre-recovery evidence: the very next
    # scans must NOT re-convict off stale slow samples (the perpetual
    # flap + full-sweep loop a review round caught)
    mon.scan_once()
    mon.scan_once()
    assert healthtrack.TRACKER.state_of("drive", key) == \
        healthtrack.STATE_OK
    assert events.count("suspect") == 1
    assert_converged(sets, datas)
    sets.close()


def test_quarantine_capacity_rule(tmp_path):
    """With fewer than k healthy readers the plan keeps the suspect
    drive in play — quarantine must never turn a readable object
    unreadable."""
    sets, nd = make_sets(tmp_path)
    data = payload(BLOCK + 9, seed=30)
    sets.put_object("b", "o", data)
    key = healthtrack.disk_key(nd)
    healthtrack.TRACKER.set_state("drive", key,
                                  healthtrack.STATE_SUSPECT)
    # kill parity-count OTHER drives: only k drives remain, one of
    # them the suspect — it must still serve
    eng = sets.sets[0]
    killed = 0
    for j in range(len(eng.disks) - 1, 0, -1):
        if killed == M:
            break
        eng.disks[j] = None
        killed += 1
    _, it = sets.get_object("b", "o")
    assert b"".join(it) == data
    sets.close()


def test_schedule_stalls_deterministic():
    """Seeded stall schedule: same seed, same decisions; heavy tail
    capped at stall_max_s; op-count windows stall unconditionally."""
    s1 = FaultSchedule(seed=42, stall_rate=0.3, stall_s=0.2,
                       stall_pareto=1.0, stall_max_s=1.5)
    s2 = FaultSchedule(seed=42, stall_rate=0.3, stall_s=0.2,
                       stall_pareto=1.0, stall_max_s=1.5)
    seq1 = [s1.stall_for("read_file", n, 0) for n in range(200)]
    assert seq1 == [s2.stall_for("read_file", n, 0)
                    for n in range(200)]
    fired = [d for d in seq1 if d > 0]
    assert fired and all(d <= 1.5 for d in fired)
    assert any(d > 0.2 for d in fired)      # the tail is heavy
    win = FaultSchedule(seed=1, stall_s=0.3,
                        stall_windows=((10, 20),))
    assert win.stall_for("read_file", 1, 15) == pytest.approx(0.3)
    assert win.stall_for("read_file", 1, 25) == 0.0


def test_naughty_counts_stalls(tmp_path):
    d = XLStorage(str(tmp_path / "d0"))
    nd = NaughtyDisk(d, enabled=True)
    nd.verb_stalls = {"make_vol": {1: 0.05}}
    t0 = time.perf_counter()
    nd.make_vol("v1")
    assert time.perf_counter() - t0 >= 0.05
    nd.make_vol("v2")                        # one-shot: second is fast
    assert nd.stats.stalls == 1
    assert nd.stats.stall_s == pytest.approx(0.05)
