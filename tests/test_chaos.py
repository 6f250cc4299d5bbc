"""Chaos tests: seeded NaughtyDisk schedules drive PUT/GET/heal/MRF
through drive faults (errors, bitrot flips, truncated streams, short
writes, offline windows) on <= parity drives.

Invariants (the acceptance bar of the failure-plane PR):
  * every op against the quorum-healthy set succeeds,
  * every object reads back byte-identical,
  * after MRF drain + a deep-scan heal pass, every shard verifies clean
    on every drive and the MRF queue is empty.

Every test prints its fault-schedule seed; a failing run reproduces
exactly via MINIO_TPU_CHAOS_SEED=<seed>. The cheap seeded subset runs
in tier-1; the long randomized schedules are additionally marked slow.
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

from minio_tpu.object.sets import ErasureSets
from minio_tpu.storage import XLStorage, errors as serr
from minio_tpu.storage.naughty import FaultSchedule, NaughtyDisk

pytestmark = pytest.mark.chaos

K, M = 4, 2
NDISKS = K + M
BLOCK = 1 << 16

# fast-converging MRF for tests: tight backoff, generous retries
MRF_TEST_OPTIONS = dict(max_retries=10, backoff_base=0.02,
                        backoff_max=0.25)


def chaos_seed(default: int) -> int:
    return int(os.environ.get("MINIO_TPU_CHAOS_SEED", "0") or 0) or default


def announce(seed: int) -> None:
    # pytest shows captured stdout on failure: the seed reproduces the
    # exact fault schedule (MINIO_TPU_CHAOS_SEED=<seed>)
    print(f"fault-schedule seed={seed} "
          f"(MINIO_TPU_CHAOS_SEED={seed} reproduces)")


def payload(size: int, seed: int = 7) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def make_chaos_sets(tmp_path, schedules: dict,
                    n: int = NDISKS, parity: int = M,
                    wrapper=NaughtyDisk
                    ) -> tuple[ErasureSets, list[NaughtyDisk]]:
    """1 set x n drives; drives named in `schedules` get a (disarmed)
    NaughtyDisk wrapper — arm after the fixture is built."""
    drives: list = []
    naughty: list[NaughtyDisk] = []
    for j in range(n):
        d = XLStorage(str(tmp_path / f"d{j}"))
        sched = schedules.get(j)
        if sched is not None:
            nd = wrapper(d, schedule=sched, enabled=False)
            naughty.append(nd)
            drives.append(nd)
        else:
            drives.append(d)
    sets = ErasureSets.from_storage(
        drives, set_count=1, set_drive_count=n, parity=parity,
        block_size=BLOCK, mrf_options=dict(MRF_TEST_OPTIONS))
    sets.make_bucket("b")
    return sets, naughty


def run_workload(sets: ErasureSets, n_threads: int = 3,
                 n_objects: int = 4, seed: int = 0) -> dict[str, bytes]:
    """Concurrent PUT + immediate GET verify; returns {name: data}."""
    datas: dict[str, bytes] = {}
    failures: list = []

    def worker(t: int) -> None:
        for i in range(n_objects):
            name = f"o-{t}-{i}"
            size = (i % 3) * BLOCK + 1000 * (t + 1) + i * 37
            data = payload(size, seed=seed * 1000 + t * 100 + i)
            try:
                sets.put_object("b", name, data)
                _, it = sets.get_object("b", name)
                got = b"".join(it)
                if got != data:
                    failures.append((name, "byte mismatch"))
                datas[name] = data
            except Exception as e:  # noqa: BLE001 — collected for assert
                failures.append((name, repr(e)))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not failures, failures
    return datas


def assert_converged(sets: ErasureSets, datas: dict[str, bytes],
                     drain_timeout: float = 30.0) -> None:
    """MRF drain + deep-scan heal, then: queue empty, bytes identical,
    every shard verifies clean on every drive."""
    assert sets.drain_mrf(drain_timeout)
    for name in datas:
        sets.heal_object("b", name, deep_scan=True)
    assert sets.drain_mrf(drain_timeout)
    assert sets.mrf_stats()["pending"] == 0
    eng = sets.sets[0]
    for name, data in datas.items():
        _, it = sets.get_object("b", name)
        assert b"".join(it) == data, name
        for j, d in enumerate(eng.disks):
            fi = d.read_version("b", name)
            d.check_parts("b", name, fi)
            d.verify_file("b", name, fi)


# ---------------------------------------------------------------------------
# cheap seeded subset (tier-1)
# ---------------------------------------------------------------------------

def test_chaos_flaky_verbs_converge(tmp_path):
    """Random verb errors + read-bitrot on <= parity drives: no op may
    fail, bytes stay identical, MRF + heal converge every shard."""
    seed = chaos_seed(1101)
    announce(seed)
    sched = {j: FaultSchedule(seed=seed + j, error_rate=0.2,
                              bitrot_rate=0.15)
             for j in range(M)}
    sets, naughty = make_chaos_sets(tmp_path, sched)
    try:
        for nd in naughty:
            nd.arm()
        datas = run_workload(sets, seed=seed)
        for nd in naughty:
            nd.disarm()
        assert_converged(sets, datas)
    finally:
        sets.close()


def test_chaos_pipelined_put_writer_death_mid_batch(tmp_path,
                                                    monkeypatch):
    """Pipelined PUT with a drive dying MID-BATCH (an append_file frame
    write fails while later batches are still being ingested/encoded):
    write-quorum semantics hold — the PUT succeeds with <= parity
    writers lost, the 2-phase commit counts the dead drive, MRF is fed
    and heals the object back to full redundancy, bytes identical."""
    from minio_tpu.object import bitrot_io, engine as engine_mod
    from minio_tpu.parallel import pipeline as pl
    assert pl.ENABLED        # the default; the test targets this path
    # small batches + per-frame flushes so the failure lands inside
    # the write stage of a mid-stream batch, not at writer close
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    monkeypatch.setattr(bitrot_io.StreamingBitrotWriter,
                        "FLUSH_THRESHOLD", 1)
    seed = chaos_seed(2201)
    announce(seed)
    sets, naughty = make_chaos_sets(tmp_path,
                                    {0: FaultSchedule(seed=seed)})
    try:
        nd = naughty[0]
        nd.arm()
        # the 5th frame append on drive 0 fails: mid-stream, mid-batch
        nd.verb_errors["append_file"] = {5: serr.FaultyDisk("mid-batch")}
        data = payload(10 * BLOCK + 1234, seed=seed)
        sets.put_object("b", "o", data)
        assert nd.stats.calls.get("append_file", 0) >= 5
        stats = sets.mrf_stats()
        assert stats["queued"] >= 1        # degraded write fed MRF
        assert_converged(sets, {"o": data})
    finally:
        sets.close()


class LocalNaughtyDisk(NaughtyDisk):
    """A NaughtyDisk that hands out the inner drive's append handle, as
    a local drive does (a plain NaughtyDisk has none, so its writers
    take the remote append_file path): every vectored write counts as
    verb `writev`, and a programmed error lets the kernel take the
    first buffer before it raises — a drive filling up mid-group."""

    def has_appender(self) -> bool:
        return True

    def open_appender(self, volume, path):
        self._begin("open_appender")
        return _NaughtyAppender(self, self.inner.open_appender(volume,
                                                               path))


class _NaughtyAppender:
    def __init__(self, disk, handle):
        self._disk, self._handle = disk, handle
        self.vectored = handle.vectored

    def writev(self, buffers) -> int:
        try:
            self._disk._begin("writev")
        except serr.StorageError as e:
            self._handle.writev(buffers[:1])
            raise OSError(28, f"No space left on device ({e})") from e
        return self._handle.writev(buffers)

    def close(self) -> None:
        self._handle.close()


def test_chaos_pipelined_put_vectored_write_dies_mid_stream(tmp_path,
                                                            monkeypatch):
    """The fan-out's semantics over the vectored write: a LOCAL drive
    whose third group write fails part-way (a prefix taken, then
    ENOSPC) costs the PUT nothing at quorum, is dropped from every
    later group, is counted by the commit, feeds MRF and heals back to
    byte-identical shards."""
    from minio_tpu.object import engine as engine_mod
    from minio_tpu.parallel import pipeline as pl
    assert pl.ENABLED
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", 2)
    seed = chaos_seed(2203)
    announce(seed)
    sets, naughty = make_chaos_sets(
        tmp_path, {0: FaultSchedule(seed=seed), 1: FaultSchedule(seed=seed)},
        wrapper=LocalNaughtyDisk)
    try:
        bad, good = naughty
        bad.arm()
        good.arm()
        bad.verb_errors["writev"] = {3: serr.FaultyDisk("mid-stream")}
        data = payload(10 * BLOCK + 1234, seed=seed)
        sets.put_object("b", "o", data)
        groups = good.stats.calls["writev"]
        assert groups >= 5                  # 5 groups of 2 and the tail
        assert bad.stats.calls["writev"] == 3   # none after its failure
        assert sets.mrf_stats()["queued"] >= 1
        _, it = sets.get_object("b", "o")
        assert b"".join(it) == data
        assert_converged(sets, {"o": data})
    finally:
        sets.close()


def test_chaos_truncated_streams_and_short_writes(tmp_path):
    """Truncated read streams (mid-stream disconnects) on one drive and
    silent short writes on another stay invisible to clients and heal
    clean."""
    seed = chaos_seed(2202)
    announce(seed)
    sched = {0: FaultSchedule(seed=seed, truncate_rate=0.4),
             1: FaultSchedule(seed=seed + 1, truncate_rate=0.3,
                              bitrot_rate=0.2)}
    sets, naughty = make_chaos_sets(tmp_path, sched)
    try:
        for nd in naughty:
            nd.arm()
        datas = run_workload(sets, seed=seed)
        for nd in naughty:
            nd.disarm()
        assert_converged(sets, datas)
    finally:
        sets.close()


def test_chaos_offline_window_comes_back(tmp_path):
    """A drive that goes offline mid-workload and comes back: writes
    succeed at quorum during the outage; the drive converges after."""
    seed = chaos_seed(3303)
    announce(seed)
    sched = {2: FaultSchedule(seed=seed, offline_windows=((5, 60),))}
    sets, naughty = make_chaos_sets(tmp_path, sched)
    try:
        for nd in naughty:
            nd.arm()
        datas = run_workload(sets, seed=seed)
        assert naughty[0].stats.offline_hits > 0
        for nd in naughty:
            nd.disarm()
        assert_converged(sets, datas)
    finally:
        sets.close()


def test_chaos_schedule_is_deterministic():
    """Identical seeds replay identical fault decisions; a different
    seed diverges — the reproduce-from-printed-seed guarantee."""
    a = FaultSchedule(seed=42, error_rate=0.3, bitrot_rate=0.3,
                      truncate_rate=0.3, latency_rate=0.3)
    b = FaultSchedule(seed=42, error_rate=0.3, bitrot_rate=0.3,
                      truncate_rate=0.3, latency_rate=0.3)
    c = FaultSchedule(seed=43, error_rate=0.3, bitrot_rate=0.3,
                      truncate_rate=0.3, latency_rate=0.3)

    def trace(s):
        return [(s.error_for("read_file", n) is not None,
                 s.corrupts("read_file", n), s.truncates("read_file", n),
                 s.latency_for("append_file", n) > 0)
                for n in range(200)]

    assert trace(a) == trace(b)
    assert trace(a) != trace(c)
    # the fault mix is actually exercised at these rates
    hits = trace(a)
    assert any(h[0] for h in hits) and any(h[1] for h in hits)
    assert any(h[2] for h in hits) and any(h[3] for h in hits)


# ---------------------------------------------------------------------------
# topology plane: rebalance under faults
# ---------------------------------------------------------------------------

def test_chaos_rebalance_pool_death_and_bitrot(tmp_path):
    """Pool drain under chaos: the whole TARGET pool dies mid-drain
    (every move fails at write quorum) and the source serves reads
    with bitrot on <= parity drives. Invariants: no write-quorum
    object is lost (everything stays readable from the source), failed
    moves land in the source MRF queue and count in
    minio_tpu_rebalance_failed_total; after the target recovers the
    drain converges and the source pool is empty."""
    from minio_tpu.object.rebalance import Rebalancer
    from minio_tpu.object.server_sets import ErasureServerSets
    from minio_tpu.object.topology import POOL_DRAINING
    from minio_tpu.utils import telemetry

    seed = chaos_seed(4404)
    announce(seed)
    # source: bitrot on read for <= parity drives (moves reconstruct)
    src_sched = {j: FaultSchedule(seed=seed + j, bitrot_rate=0.2,
                                  fault_verbs=("read_file",
                                               "read_file_stream"))
                 for j in range(M)}
    src, src_naughty = make_chaos_sets(tmp_path / "src", src_sched)
    # target: plain wrappers we can kill wholesale ("pool death")
    dst_drives = []
    dst_naughty = []
    for j in range(NDISKS):
        nd = NaughtyDisk(XLStorage(str(tmp_path / "dst" / f"d{j}")),
                         schedule=FaultSchedule(seed=seed + 100 + j),
                         enabled=False)
        dst_naughty.append(nd)
        dst_drives.append(nd)
    dst = ErasureSets.from_storage(dst_drives, 1, NDISKS, M, block_size=BLOCK,
                                   mrf_options=dict(MRF_TEST_OPTIONS))
    dst.make_bucket("b")
    zz = ErasureServerSets([src, dst])
    try:
        datas = {}
        for i in range(6):
            name = f"chaos-{i}"
            data = payload(BLOCK + 211 * i, seed=seed + i)
            src.put_object("b", name, data)
            datas[name] = data
        zz.set_pool_state(0, POOL_DRAINING)

        def failed_total():
            snap = telemetry.REGISTRY.snapshot(
                "minio_tpu_rebalance_failed_total")
            return snap.get("minio_tpu_rebalance_failed_total",
                            {}).get("pool=0", 0)

        failed_before = failed_total()
        # pool death: > parity target drives offline -> every move
        # fails its target write quorum
        for nd in dst_naughty[:M + 2]:
            nd.offline = True
        for nd in src_naughty:
            nd.arm()
        reb = Rebalancer(zz, 0, busy_fn=lambda: False)
        moved, failed, remaining = reb.run_pass()
        assert moved == 0 and failed == len(datas)
        assert remaining == len(datas)
        assert failed_total() - failed_before >= len(datas)
        # failed moves fed the source MRF queue
        assert src.mrf_stats()["queued"] >= 1
        # nothing lost: every object still reads byte-identical
        for name, data in datas.items():
            _, it = zz.get_object("b", name)
            assert b"".join(it) == data, name

        # target pool recovers: the drain converges
        for nd in dst_naughty:
            nd.offline = False
        src.drain_mrf(30.0)
        moved2, failed2, remaining2 = reb.run_pass(restart=True)
        assert failed2 == 0 and remaining2 == 0
        assert moved2 == len(datas)
        for nd in src_naughty:
            nd.disarm()
        assert src.list_object_versions("b", max_keys=20)[0] == []
        for name, data in datas.items():
            _, it = zz.get_object("b", name)
            assert b"".join(it) == data, name
            assert dst.has_object_versions("b", name)
    finally:
        zz.close()


# ---------------------------------------------------------------------------
# RemoteStorage drives: faults injected on the SERVER side of the RPC
# ---------------------------------------------------------------------------

def test_chaos_remote_storage_faults_over_rpc(tmp_path):
    """A NaughtyDisk schedule BEHIND storage_rpc: verb errors, bitrot
    and truncated streams are injected server-side, so every fault
    crosses the wire through the RPC error-mapping path (wire fault ->
    serr.* reconstruction, mid-stream truncation -> NetworkStorageError)
    instead of a local wrapper shortcut. Quorum ops succeed, bytes stay
    identical, MRF + heal converge every shard."""
    from minio_tpu.distributed.storage_rpc import (RemoteStorage,
                                                   StorageRPCServer)
    from minio_tpu.distributed.transport import RPCServer

    seed = chaos_seed(5505)
    announce(seed)
    ak, sk = "chaoskey", "chaossecret1234"
    naughty: list[NaughtyDisk] = []
    serving: dict[str, object] = {}
    for j in range(NDISKS):
        d = XLStorage(str(tmp_path / f"d{j}"))
        if j < M:
            nd = NaughtyDisk(d, schedule=FaultSchedule(
                seed=seed + j, error_rate=0.15, bitrot_rate=0.15,
                truncate_rate=0.15), enabled=False)
            naughty.append(nd)
            serving[f"/d{j}"] = nd
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
        for nd in naughty:
            nd.arm()
        datas = run_workload(sets, seed=seed)
        for nd in naughty:
            nd.disarm()
        # the schedule really fired behind the RPC server
        injected = sum(nd.stats.errors + nd.stats.bitrot
                       + nd.stats.truncated for nd in naughty)
        assert injected > 0
        assert sets.drain_mrf(30.0)
        for name in datas:
            sets.heal_object("b", name, deep_scan=True)
        assert sets.drain_mrf(30.0)
        assert sets.mrf_stats()["pending"] == 0
        for name, data in datas.items():
            _, it = sets.get_object("b", name)
            assert b"".join(it) == data, name
            for d in sets.sets[0].disks:
                fi = d.read_version("b", name)
                d.check_parts("b", name, fi)
                d.verify_file("b", name, fi)
    finally:
        sets.close()
        for r in remotes:
            r.close()
        host.stop()


# ---------------------------------------------------------------------------
# long randomized schedules (nightly)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("base_seed", [101, 202, 303])
def test_chaos_randomized_full_mix(tmp_path, base_seed):
    """Everything at once on parity-many drives: verb errors, latency,
    bitrot, truncation, and an offline window — larger workload, full
    convergence."""
    seed = chaos_seed(base_seed)
    announce(seed)
    sched = {
        0: FaultSchedule(seed=seed, error_rate=0.25, latency_rate=0.1,
                         latency=0.001, bitrot_rate=0.2,
                         truncate_rate=0.15),
        1: FaultSchedule(seed=seed + 7, error_rate=0.15,
                         bitrot_rate=0.15, truncate_rate=0.1,
                         offline_windows=((30, 120), (220, 260))),
    }
    sets, naughty = make_chaos_sets(tmp_path, sched)
    try:
        for nd in naughty:
            nd.arm()
        datas = run_workload(sets, n_threads=4, n_objects=8, seed=seed)
        for nd in naughty:
            nd.disarm()
        assert_converged(sets, datas, drain_timeout=60.0)
    finally:
        sets.close()


# ---------------------------------------------------------------------------
# tier-plane chaos: NaughtyTierClient faults through transition/restore
# ---------------------------------------------------------------------------

def _tier_env(tmp_path, **worker_kw):
    from minio_tpu.tier.client import FSTierClient, NaughtyTierClient
    from minio_tpu.tier.config import TierConfig, TierManager
    from minio_tpu.tier.transition import TransitionWorker
    sets = ErasureSets.from_drives(
        [str(tmp_path / f"d{i}") for i in range(NDISKS)], 1, NDISKS, M,
        block_size=BLOCK, mrf_options=MRF_TEST_OPTIONS)
    sets.make_bucket("b")
    tiers = TierManager(sets)
    tiers.add(TierConfig("cold", "fs", {"path": str(tmp_path / "tier")}))
    naughty = NaughtyTierClient(FSTierClient(str(tmp_path / "tier")))
    tiers.set_client("cold", naughty)
    worker = TransitionWorker(sets, tiers, busy_fn=lambda: False,
                              **worker_kw)
    return sets, tiers, naughty, worker


def test_chaos_failed_transition_lands_in_mrf_and_retries(tmp_path):
    """A tier that 5xxes the upload: the transition fails, the object
    stays fully readable locally, the failure lands in the MRF queue,
    and a retry after the tier recovers succeeds."""
    from minio_tpu.tier.client import TierClientError
    sets, tiers, naughty, worker = _tier_env(tmp_path)
    worker.start()
    body = payload(150_000)
    info = sets.put_object("b", "obj", body)

    naughty.fail_verbs["put"] = TierClientError("upstream 503")
    worker.enqueue("b", "obj", "", "cold", etag=info.etag)
    assert worker.drain(30), worker.stats()
    assert worker.stats()["failed"] == 1
    # failure fed the MRF queue (heal-first), and the object is intact
    assert sets.mrf.queued >= 1
    assert sets.drain_mrf(10)
    _, stream = sets.get_object("b", "obj")
    assert b"".join(stream) == body

    # tier recovers: the retry (next crawler pass re-finds it) succeeds
    naughty.clear_faults()
    worker.enqueue("b", "obj", "", "cold", etag=info.etag)
    assert worker.drain(30)
    assert worker.stats()["moved"] == 1
    from minio_tpu.object import api_errors
    with pytest.raises(api_errors.InvalidObjectState):
        sets.get_object("b", "obj")
    worker.close()
    sets.close()


def test_chaos_mid_transition_crash_leaves_object_readable(tmp_path):
    """A 'crash' between the remote upload and the stub rewrite (the
    verify head fails, so the commit never happens): the object stays
    fully readable locally and the orphaned remote copy was freed."""
    from minio_tpu.tier.client import TierClientError
    sets, tiers, naughty, worker = _tier_env(tmp_path)
    worker.start()
    body = payload(120_000, seed=11)
    info = sets.put_object("b", "crash", body)

    # upload succeeds, then the worker dies before the stub rewrite
    # (head raising models the process losing the tier mid-commit)
    naughty.fail_verbs["head"] = TierClientError("conn reset")
    worker.enqueue("b", "crash", "", "cold", etag=info.etag)
    assert worker.drain(30)
    assert worker.stats()["failed"] == 1
    assert naughty.calls["put"] == 1        # the upload DID happen
    _, stream = sets.get_object("b", "crash")
    assert b"".join(stream) == body          # fully readable locally
    # no orphaned metadata: the version is still a plain local object
    from minio_tpu.storage import datatypes as dt
    assert not dt.is_transitioned(
        sets.get_object_info("b", "crash").user_defined)
    worker.close()
    sets.close()


def test_chaos_short_read_on_restore_keeps_stub(tmp_path):
    """A tier stream that truncates mid-restore: the local put aborts
    (no short copy committed over the stub), the object still answers
    InvalidObjectState, and a clean retry restores the full bytes."""
    from minio_tpu.object import api_errors
    from minio_tpu.tier.client import TierClientError
    from minio_tpu.tier.transition import restore_object
    sets, tiers, naughty, worker = _tier_env(tmp_path)
    worker.start()
    body = payload(200_000, seed=23)
    info = sets.put_object("b", "trunc", body)
    worker.enqueue("b", "trunc", "", "cold", etag=info.etag)
    assert worker.drain(30)
    assert worker.stats()["moved"] == 1

    naughty.short_read_verbs = ("get",)
    with pytest.raises(TierClientError):
        restore_object(sets, tiers, "b", "trunc")
    assert naughty.stats["short_reads"] >= 1
    # the stub survived the failed restore
    with pytest.raises(api_errors.InvalidObjectState):
        sets.get_object("b", "trunc")

    naughty.clear_faults()
    restore_object(sets, tiers, "b", "trunc")
    oi, stream = sets.get_object("b", "trunc")
    assert b"".join(stream) == body
    assert oi.etag == info.etag
    worker.close()
    sets.close()


def test_chaos_transition_with_naughty_source_drives(tmp_path):
    """Faulted SOURCE drives (<= parity) under the transition read: the
    engine's reconstructing GET feeds the tier the correct bytes, and
    the restored object round-trips byte-identical."""
    from minio_tpu.tier.client import FSTierClient
    from minio_tpu.tier.config import TierConfig, TierManager
    from minio_tpu.tier.transition import TransitionWorker, restore_object
    from minio_tpu.object import api_errors
    seed = chaos_seed(4242)
    announce(seed)
    sets, naughties = make_chaos_sets(
        tmp_path, {0: FaultSchedule(seed=seed, error_rate=0.15),
                   1: FaultSchedule(seed=seed + 1, bitrot_rate=0.05)})
    body = payload(180_000, seed=seed & 0xFF)
    info = sets.put_object("b", "faulty", body)
    for nd in naughties:
        nd.arm()
    tiers = TierManager(sets)
    tiers.add(TierConfig("cold", "fs", {"path": str(tmp_path / "tier")}))
    worker = TransitionWorker(sets, tiers, busy_fn=lambda: False).start()
    worker.enqueue("b", "faulty", "", "cold", etag=info.etag)
    assert worker.drain(60), worker.stats()
    assert worker.stats()["moved"] == 1
    with pytest.raises(api_errors.InvalidObjectState):
        sets.get_object("b", "faulty")
    restore_object(sets, tiers, "b", "faulty")
    _, stream = sets.get_object("b", "faulty")
    assert b"".join(stream) == body
    for nd in naughties:
        nd.disarm()
    worker.close()
    sets.close()


# ---------------------------------------------------------------------------
# encrypted shards under bitrot: reconstruct or clean auth error — NEVER
# silently corrupted plaintext
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("device", [False, True])
def test_chaos_bitrot_on_encrypted_shards(tmp_path, monkeypatch, device):
    """NaughtyDisk bitrot under an encrypted object has exactly two
    legal outcomes: the shard digests catch the flip and the erasure
    layer reconstructs (plaintext byte-identical), or — when too many
    drives rot to reconstruct — the read fails with a clean error
    (erasure quorum or Poly1305 auth). A success that returns WRONG
    plaintext is the one forbidden outcome, on both cipher paths
    (device-fused PUT and the CPU fallback)."""
    from minio_tpu.features import crypto as sse
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object import engine as engine_mod

    if device:
        monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
        monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
        monkeypatch.setenv("MINIO_TPU_SSE_DEVICE_MIN_BYTES", "0")
    seed = chaos_seed(7801)
    announce(seed)
    oek, base = bytes(range(32)), bytes(range(50, 62))

    def decrypt_back(sets, name, n):
        """Full read path: erasure GET feeds the verify-then-decrypt
        seam exactly as the S3 handler does."""
        def fetch(off, ln):
            _, it = sets.get_object("b", name, off, ln)
            return it
        return b"".join(sse.chacha_decrypt_ranged(
            fetch, sse.encrypted_size(n), oek, base, 0, n))[:n]

    # phase 1: bitrot on <= parity drives -> reconstruct, byte-identical
    sched = {j: FaultSchedule(seed=seed + j, bitrot_rate=0.35,
                              fault_verbs=("read_file",
                                           "read_file_stream"))
             for j in range(M)}
    sets, naughty = make_chaos_sets(tmp_path / "lo", sched)
    datas = {}
    for i, n in enumerate((1000, BLOCK + 17, 2 * BLOCK + 999)):
        data = payload(n, seed=seed + i)
        sets.put_object("b", f"e{i}", data,
                        opts=engine_mod.PutOptions(
                            sse_spec=sse.DeviceSSE(oek, base)))
        datas[f"e{i}"] = data
    for nd in naughty:
        nd.arm()
    for name, data in datas.items():
        assert decrypt_back(sets, name, len(data)) == data, name
    for nd in naughty:
        nd.disarm()
    sets.close()

    # phase 2: bitrot past parity -> clean failure or correct bytes,
    # never a silent wrong-plaintext success
    sched = {j: FaultSchedule(seed=seed + 100 + j, bitrot_rate=1.0,
                              fault_verbs=("read_file",
                                           "read_file_stream"))
             for j in range(M + 1)}
    sets, naughty = make_chaos_sets(tmp_path / "hi", sched)
    n = BLOCK + 4321
    data = payload(n, seed=seed + 9)
    sets.put_object("b", "hot", data,
                    opts=engine_mod.PutOptions(
                        sse_spec=sse.DeviceSSE(oek, base)))
    for nd in naughty:
        nd.arm()
    try:
        got = decrypt_back(sets, "hot", n)
    except Exception as exc:  # noqa: BLE001 — ANY clean error is legal
        # quorum/bitrot error from the erasure layer, or the Poly1305
        # trailer refusing the corrupt ciphertext: both are clean
        # failures; the test only forbids garbled plaintext below
        print(f"clean failure (ok): {type(exc).__name__}: {exc}")
    else:
        assert got == data, "silent plaintext corruption leaked through"
    for nd in naughty:
        nd.disarm()
    sets.close()
