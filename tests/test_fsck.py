"""In-process crash-consistency tests: the fsck auditor per finding
class, torn-write injection through the crashpoint hook, torn
checkpoint/registry tolerance (satellite bugfix sweep), commit-window
abort semantics (previous version stays readable), and the metacache
persist-crash fallback — the tier-1 half of the crash plane (the
subprocess SIGKILL matrix lives in tests/test_crash.py, slow)."""

from __future__ import annotations

import json
import os
import time

import pytest

from minio_tpu.object import api_errors
from minio_tpu.object.background import MRFHealer
from minio_tpu.object.engine import PutOptions
from minio_tpu.object.fsck import run_fsck
from minio_tpu.object.metacache import MetacacheManager, manifest_key, \
    mc_prefix
from minio_tpu.object.rebalance import Rebalancer
from minio_tpu.object.rebalance import _checkpoint_object as reb_ckpt
from minio_tpu.object.server_sets import ErasureServerSets
from minio_tpu.object.sets import ErasureSets
from minio_tpu.object.topology import TopologyStore
from minio_tpu.replicate.resync import Resyncer
from minio_tpu.storage.xl_storage import MINIO_META_BUCKET
from minio_tpu.utils import atomicfile, crashpoint

K, M, NDISKS = 4, 2, 6
BLOCK = 1 << 16
ORIGIN_KEY = "X-Minio-Internal-replication-origin"


def make_zones(tmp_path, pools=1, tag="p"):
    zz = ErasureServerSets(
        [ErasureSets.from_drives(
            [str(tmp_path / f"{tag}{p}d{j}") for j in range(NDISKS)],
            1, NDISKS, M, block_size=BLOCK, enable_mrf=False)
         for p in range(pools)],
        load_topology=False)
    zz.make_bucket("b")
    return zz


@pytest.fixture()
def zz(tmp_path):
    z = make_zones(tmp_path)
    yield z
    z.close()


@pytest.fixture(autouse=True)
def _disarm():
    crashpoint.disarm()
    yield
    crashpoint.disarm()


def eng_of(zz, pool=0):
    return zz.server_sets[pool].sets[0]


def get_bytes(zz, bucket, name):
    _info, stream = zz.get_object(bucket, name)
    try:
        return b"".join(stream)
    finally:
        close = getattr(stream, "close", None)
        if close:
            close()


# ---------------------------------------------------------------------------
# fsck per finding class
# ---------------------------------------------------------------------------

def test_fsck_clean_tree(zz):
    zz.put_object("b", "ok", b"x" * 1000)
    rep = run_fsck(zz, tmp_age_s=0)
    assert rep.clean and rep.supported
    assert rep.objects_scanned >= 1


def test_fsck_orphan_data_dir(zz):
    zz.put_object("b", "obj", b"x" * 1000)
    d0 = eng_of(zz).disks[0]
    orphan = os.path.join(d0.root, "b", "obj", "11111111-dead")
    os.makedirs(orphan)
    with open(os.path.join(orphan, "part.1"), "wb") as f:
        f.write(b"junk")
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"orphan_data": 1}
    assert rep.repaired_counts() == {"orphan_data": 1}
    assert not os.path.exists(orphan)
    # the committed copy is untouched
    assert get_bytes(zz, "b", "obj") == b"x" * 1000
    assert run_fsck(zz, tmp_age_s=0).clean


def test_fsck_tmp_age_gate(zz):
    d0 = eng_of(zz).disks[0]
    stale = os.path.join(d0.root, ".minio.sys", "tmp", "stale-uuid")
    os.makedirs(stale)
    with open(os.path.join(stale, "f"), "wb") as f:
        f.write(b"junk")
    # a FRESH staged dir is NOT reaped under the default age gate (it
    # could be an in-flight PUT)…
    assert run_fsck(zz).counts() == {}
    # …but the explicit quiesced mode reaps it
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"stale_tmp": 1}
    assert not os.path.exists(stale)


def test_fsck_meta_missing_heals(zz):
    zz.put_object("b", "deg", b"y" * 800)
    eng = eng_of(zz)
    os.unlink(os.path.join(eng.disks[1].root, "b", "deg", "xl.meta"))
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"meta_missing": 1}
    assert rep.repaired_counts() == {"meta_missing": 1}
    assert run_fsck(zz, tmp_age_s=0).clean
    assert os.path.exists(
        os.path.join(eng.disks[1].root, "b", "deg", "xl.meta"))


def test_fsck_missing_shards_heal_and_lost(zz):
    import shutil
    zz.put_object("b", "sh", b"z" * 4000)
    eng = eng_of(zz)
    fi = eng.disks[0].read_versions("b", "sh")[0]
    # drop the data dir on ONE drive (≤ parity): repairable
    shutil.rmtree(os.path.join(eng.disks[2].root, "b", "sh",
                               fi.data_dir))
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"missing_shards": 1}
    assert run_fsck(zz, tmp_age_s=0).clean
    # drop it below the decode quorum: LOST, reported, not repairable
    for j in range(NDISKS - K + 1):
        p = os.path.join(eng.disks[j].root, "b", "sh", fi.data_dir)
        if os.path.isdir(p):
            shutil.rmtree(p)
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert "lost_data" in rep.counts()
    lost = [f for f in rep.findings if f.cls == "lost_data"]
    assert lost and not lost[0].repairable


def test_fsck_origin_divergence_repairs(zz):
    zz.put_object("b", "repl", b"r" * 600,
                  opts=PutOptions(versioned=True))
    eng = eng_of(zz)
    for j, site in ((0, "site-A"), (1, "site-B")):
        fi = eng.disks[j].read_versions("b", "repl")[0]
        fi.metadata[ORIGIN_KEY] = site
        eng.disks[j].write_metadata("b", "repl", fi)
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"origin_divergence": 1}
    assert rep.repaired_counts() == {"origin_divergence": 1}
    assert run_fsck(zz, tmp_age_s=0).clean
    origins = {d.read_versions("b", "repl")[0].metadata.get(ORIGIN_KEY)
               for d in eng.disks}
    assert len(origins) == 1


def test_fsck_stale_multipart(zz):
    eng = eng_of(zz)
    # session dir with NO readable session meta on any drive (a torn
    # new_multipart_upload)
    for d in eng.disks:
        p = os.path.join(d.root, ".minio.sys", "multipart", "shaX",
                         "upl1", "dd")
        os.makedirs(p)
        with open(os.path.join(p, "part.1"), "wb") as f:
            f.write(b"junk")
    # a LIVE session must be untouched
    up = zz.new_multipart_upload("b", "live-mpu")
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"stale_multipart": 1}
    assert not os.path.exists(os.path.join(
        eng.disks[0].root, ".minio.sys", "multipart", "shaX"))
    # live session still works end-to-end
    from minio_tpu.object import CompletePart
    pi = zz.put_object_part("b", "live-mpu", up, 1, b"m" * 700)
    zz.complete_multipart_upload("b", "live-mpu", up,
                                 [CompletePart(1, pi.etag)])
    assert get_bytes(zz, "b", "live-mpu") == b"m" * 700


def test_fsck_torn_registry_rewrites_from_best_copy(tmp_path):
    zz = make_zones(tmp_path, pools=2)
    try:
        epoch = zz.set_pool_state(1, "suspended")   # persist a real doc
        zz.set_pool_state(1, "active")
        # tear pool 0's copy only
        zz.server_sets[0].put_object(MINIO_META_BUCKET,
                                     "topology/pools.json", b'{"epo')
        rep = run_fsck(zz, repair=True, tmp_age_s=0)
        assert rep.counts() == {"torn_registry": 1}
        assert rep.repaired_counts() == {"torn_registry": 1}
        assert run_fsck(zz, tmp_age_s=0).clean
        # the rewritten copy parses and carries the good epoch
        loaded = TopologyStore.load(zz)
        assert loaded is not None and loaded.epoch >= epoch
    finally:
        zz.close()


def test_fsck_torn_registry_single_copy_drops(zz):
    zz.server_sets[0].put_object(MINIO_META_BUCKET,
                                 "replicate/targets.json", b"\x00garb")
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"torn_registry": 1}
    assert run_fsck(zz, tmp_age_s=0).clean
    with pytest.raises(api_errors.ObjectApiError):
        zz.get_object(MINIO_META_BUCKET, "replicate/targets.json")


def test_fsck_dangling_stub(tmp_path, zz):
    from minio_tpu.tier.config import TierConfig, TierManager
    tiers = TierManager(zz)
    tiers.add(TierConfig.from_dict(
        {"name": "t1", "type": "fs",
         "params": {"path": str(tmp_path / "tier")}}))
    zz.put_object("b", "cold", b"cold" * 300)
    client = tiers.client("t1")
    import io as _io
    client.put("rk1", _io.BytesIO(b"cold" * 300), 1200)
    zz.transition_object("b", "cold", tier="t1", remote_object="rk1")
    # intact stub: clean
    assert run_fsck(zz, tiers=tiers, tmp_age_s=0).clean
    client.delete("rk1")                      # remote copy vanishes
    rep = run_fsck(zz, repair=True, tiers=tiers, tmp_age_s=0)
    assert rep.counts() == {"dangling_stub": 1}
    assert rep.repaired_counts() == {"dangling_stub": 1}
    with pytest.raises(api_errors.ObjectApiError):
        zz.get_object_info("b", "cold")
    assert run_fsck(zz, tiers=tiers, tmp_age_s=0).clean


def test_fsck_metacache_orphan_segment_and_broken_manifest(zz):
    # orphan segment: a seg object no manifest references
    zz.put_object(MINIO_META_BUCKET, mc_prefix("b") + "seg-dead.json",
                  b"[]")
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"orphan_metacache_segment": 1}
    assert run_fsck(zz, tmp_age_s=0).clean
    # manifest referencing a missing segment: dropped whole
    zz.put_object(MINIO_META_BUCKET, manifest_key("b"), json.dumps(
        {"format": 1, "bucket": "b", "gen": 3,
         "segments": [{"key": mc_prefix("b") + "seg-gone.json",
                       "first": "", "count": 0}]}).encode())
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"broken_metacache_manifest": 1}
    assert run_fsck(zz, tmp_age_s=0).clean
    with pytest.raises(api_errors.ObjectApiError):
        zz.get_object(MINIO_META_BUCKET, manifest_key("b"))


def test_fsck_fs_backend_unsupported(tmp_path):
    from minio_tpu.object.fs import FSObjects
    rep = run_fsck(FSObjects(str(tmp_path / "fs")))
    assert not rep.supported and rep.clean


def test_fsck_metrics_count_per_class(zz):
    from minio_tpu.utils import telemetry
    fam = telemetry.REGISTRY.counter("minio_tpu_fsck_findings_total")
    zz.put_object("b", "obj", b"x" * 400)
    eng = eng_of(zz)
    os.unlink(os.path.join(eng.disks[0].root, "b", "obj", "xl.meta"))
    before = dict(getattr(fam, "_values", {}))
    run_fsck(zz, repair=True, tmp_age_s=0)
    text = telemetry.REGISTRY.render()
    assert 'minio_tpu_fsck_findings_total{class="meta_missing"}' in text
    assert 'minio_tpu_fsck_repaired_total{class="meta_missing"}' in text
    assert before is not None   # smoke: family existed before the run


# ---------------------------------------------------------------------------
# commit-window aborts: previous version stays readable (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("point", ["put.shards.before_meta",
                                   "put.meta.before_rename"])
def test_crash_between_fanout_and_commit_keeps_previous(zz, point):
    zz.put_object("b", "obj", b"OLD" * 500)
    crashpoint.arm(point)
    with pytest.raises(crashpoint.CrashpointAbort):
        zz.put_object("b", "obj", b"NEW" * 700)
    crashpoint.disarm()
    assert get_bytes(zz, "b", "obj") == b"OLD" * 500
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert len(rep.unrepaired) == 0
    assert run_fsck(zz, tmp_age_s=0).clean
    assert get_bytes(zz, "b", "obj") == b"OLD" * 500


def test_partial_rename_degrades_not_tears(zz):
    """One drive's rename aborted mid-fan-out: the commit still meets
    quorum, the object reads back complete, and fsck+heal restore full
    redundancy."""
    crashpoint.arm("put.rename.partial", nth=1)
    zz.put_object("b", "part", b"P" * 3000)     # succeeds degraded
    crashpoint.disarm()
    assert get_bytes(zz, "b", "part") == b"P" * 3000
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert len(rep.unrepaired) == 0
    assert run_fsck(zz, tmp_age_s=0).clean
    assert get_bytes(zz, "b", "part") == b"P" * 3000


# ---------------------------------------------------------------------------
# torn-write injection (storage.write_all.commit)
# ---------------------------------------------------------------------------

def test_torn_write_injection(tmp_path):
    """The crashpoint hook doubles as the torn-write injector: the
    armed action commits a TRUNCATED copy under the final name before
    aborting — the on-disk state a power cut without fsync discipline
    leaves — and the tolerant doc loader reads it as absent."""
    from minio_tpu.storage.xl_storage import XLStorage
    d = XLStorage(str(tmp_path / "drv"))
    d.make_vol_bulk("vol")
    doc = json.dumps({"epoch": 12, "pools": ["active"]}).encode()
    crashpoint.arm("storage.write_all.commit",
                   action=crashpoint.torn_write_action(0.5))
    with pytest.raises(crashpoint.CrashpointAbort):
        d.write_all("vol", "doc.json", doc)
    crashpoint.disarm()
    torn = d.read_all("vol", "doc.json")
    assert 0 < len(torn) < len(doc)
    assert atomicfile.load_json_doc(torn) is None
    # a clean rewrite replaces the torn copy atomically
    d.write_all("vol", "doc.json", doc)
    assert atomicfile.load_json_doc(d.read_all("vol", "doc.json")) \
        == json.loads(doc)


def test_torn_meta_on_one_drive_converges(zz):
    """Tear ONE drive's xl.meta mid-PUT (the first raw-file commit of a
    PUT is a drive's committed journal): quorum still commits, the
    object reads back complete, and fsck's heal drops the torn journal
    for a whole one and reclaims the staging the torn drive left."""
    crashpoint.arm("storage.write_all.commit",
                   action=crashpoint.torn_write_action(0.3))
    zz.put_object("b", "torn", b"T" * 2500)
    crashpoint.disarm()
    assert get_bytes(zz, "b", "torn") == b"T" * 2500
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert len(rep.unrepaired) == 0
    assert run_fsck(zz, tmp_age_s=0).clean
    assert get_bytes(zz, "b", "torn") == b"T" * 2500


# ---------------------------------------------------------------------------
# MRF drain crash (in-process: crash loses only the retry)
# ---------------------------------------------------------------------------

def test_mrf_drain_crash():
    healed = []
    mrf = MRFHealer(lambda b, o, v: healed.append((b, o, v)),
                    backoff_base=0.01, backoff_max=0.05)
    try:
        crashpoint.arm("mrf.drain.before_heal")
        assert mrf.enqueue("b", "o", "v")
        deadline = time.monotonic() + 5
        while crashpoint.hits("mrf.drain.before_heal") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert crashpoint.hits("mrf.drain.before_heal") >= 1
        # the aborted drain requeued the entry instead of losing it
        assert mrf.drain(timeout=5)
        assert healed == [("b", "o", "v")]
        assert mrf.requeued >= 1 and mrf.healed == 1
    finally:
        crashpoint.disarm()
        mrf.close()


# ---------------------------------------------------------------------------
# torn checkpoint/registry loaders (satellite bugfix sweep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("payload", [b'{"updated": 5, "mark',  # torn
                                     b"12",      # valid-JSON wrong type
                                     b"",        # empty file
                                     b"\xff\xfe garbage"])
def test_rebalance_checkpoint_torn_tolerated(tmp_path, payload):
    zz = make_zones(tmp_path, pools=2)
    try:
        zz.server_sets[0].put_object(MINIO_META_BUCKET, reb_ckpt(1),
                                     payload)
        assert Rebalancer.load_checkpoint(zz, 1) is None
        # a GOOD copy on another pool still wins
        zz.server_sets[1].put_object(
            MINIO_META_BUCKET, reb_ckpt(1),
            json.dumps({"updated": 9.0, "bucket": "b",
                        "marker": "k"}).encode())
        doc = Rebalancer.load_checkpoint(zz, 1)
        assert doc and doc["marker"] == "k"
        # resume with only the torn copy must not crash boot
        zz.server_sets[0].put_object(MINIO_META_BUCKET, reb_ckpt(1),
                                     payload)
        zz.server_sets[1].delete_object(MINIO_META_BUCKET, reb_ckpt(1))
        assert Rebalancer.load_checkpoint(zz, 1) is None
        assert zz.resume_rebalance_if_pending() is False
    finally:
        zz.close()


def test_resync_checkpoint_torn_tolerated(zz):
    from minio_tpu.replicate.resync import _checkpoint_object
    arn = "arn:minio:repl:site:x"
    zz.put_object(MINIO_META_BUCKET, _checkpoint_object(arn), b'{"to')
    assert Resyncer.load_checkpoint(zz, arn) is None


def test_registry_loads_tolerate_torn_copy(tmp_path):
    zz = make_zones(tmp_path, pools=2)
    try:
        epoch = zz.set_pool_state(1, "suspended")
        zz.server_sets[0].put_object(MINIO_META_BUCKET,
                                     "topology/pools.json", b"[1, 2")
        loaded = TopologyStore.load(zz)
        assert loaded is not None and loaded.epoch == epoch
        # both copies torn: load reports nothing, boot defaults apply
        zz.server_sets[1].put_object(MINIO_META_BUCKET,
                                     "topology/pools.json", b"[1, 2")
        assert TopologyStore.load(zz) is None
        fresh = ErasureServerSets(zz.server_sets)   # boots all-active
        assert fresh.topology.write_pools() == [0, 1]
    finally:
        zz.close()


def test_tier_and_target_registry_tolerate_torn_docs(zz):
    from minio_tpu.replicate.targets import TargetRegistry
    from minio_tpu.tier.config import TierManager
    zz.put_object(MINIO_META_BUCKET, "tier/config.json", b'{"epoch"')
    zz.put_object(MINIO_META_BUCKET, "replicate/targets.json", b"7")
    assert TierManager(zz).load() is False
    reg = TargetRegistry(zz)
    assert reg.load() is False


# ---------------------------------------------------------------------------
# metacache persist crash: fallback + rebuild, never a half manifest
# ---------------------------------------------------------------------------

def _attach(zz, **kw):
    kw.setdefault("staleness_s", 0.0)
    kw.setdefault("flush_s", 0.05)
    mgr = MetacacheManager(zz, **kw)
    mgr.start()
    zz.attach_metacache(mgr)
    return mgr


def _oracle(zz, bucket="b"):
    mc, zz.metacache = zz.metacache, None
    try:
        objs, _p, _t = zz.list_objects(bucket, "", "", "", 1000)
        return [o.name for o in objs]
    finally:
        zz.metacache = mc


def test_metacache_persist_crash_falls_back_and_rebuilds(zz):
    """Crash between segment writes and the manifest write: the next
    manager start finds no (or a prior) manifest, walk-rebuilds, and
    serves pages equal to the merge-walk oracle; fsck reclaims the
    orphaned segments the dead attempt left."""
    for i in range(8):
        zz.put_object("b", f"k{i:02d}", bytes([i]) * 300)
    mgr = _attach(zz, persist_s=0.0)
    crashpoint.arm("metacache.persist.before_manifest")
    try:
        deadline = time.monotonic() + 10
        while mgr.persist_errors == 0 and time.monotonic() < deadline:
            zz.list_objects("b", "", "", "", 100)   # build + serve
            time.sleep(0.05)
        assert mgr.persist_errors >= 1, "persist crash never fired"
    finally:
        crashpoint.disarm()
    # live serving survived the failed persist
    objs, _p, _t = zz.list_objects("b", "", "", "", 100)
    assert [o.name for o in objs] == _oracle(zz)
    mgr.close(flush=False)
    zz.metacache = None

    # "restart": a fresh manager finds segments without a manifest —
    # it must walk-rebuild, never serve the half-written state
    mgr2 = _attach(zz)
    objs, _p, _t = zz.list_objects("b", "", "", "", 100)
    assert [o.name for o in objs] == _oracle(zz)
    mgr2.close(flush=False)
    zz.metacache = None

    # the IN-PROCESS abort runs _persist's failure path, which
    # reclaims the attempt's fresh segments itself (PR 7 discipline) —
    # so fsck finds a clean tree here; the true orphan-segment crash
    # state (hard exit skips the cleanup) is produced and repaired by
    # the subprocess matrix case for this same point
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert set(rep.counts()) <= {"orphan_metacache_segment"}
    assert len(rep.unrepaired) == 0
    assert run_fsck(zz, tmp_age_s=0).clean


def test_metacache_half_manifest_never_served(zz):
    """A manifest referencing segments that never landed (crash inside
    the segment fan-out of an earlier gen) must abandon the load and
    rebuild from the walk — pages stay oracle-identical."""
    def plant_half_manifest():
        zz.put_object(MINIO_META_BUCKET, manifest_key("b"), json.dumps(
            {"format": 1, "bucket": "b", "gen": 9,
             "segments": [{"key": mc_prefix("b") + "seg-never.json",
                           "first": "", "count": 5}]}).encode())

    for i in range(5):
        zz.put_object("b", f"m{i}", bytes([i + 1]) * 200)
    # restart-before-manager state: fsck must classify and drop it
    plant_half_manifest()
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert "broken_metacache_manifest" in rep.counts()
    assert run_fsck(zz, tmp_age_s=0).clean
    # a manager starting over the same state abandons the load and
    # walk-rebuilds (its first due persist then replaces the manifest
    # wholesale) — pages stay oracle-identical throughout
    plant_half_manifest()
    mgr = _attach(zz)
    try:
        objs, _p, _t = zz.list_objects("b", "", "", "", 100)
        assert [o.name for o in objs] == _oracle(zz)
    finally:
        mgr.close(flush=False)
        zz.metacache = None


# ---------------------------------------------------------------------------
# atomicfile
# ---------------------------------------------------------------------------

def test_write_atomic_and_fsync_knob(tmp_path, monkeypatch):
    p = str(tmp_path / "sub" / "doc.json")
    os.makedirs(os.path.dirname(p))
    atomicfile.write_atomic(p, b"hello")
    assert open(p, "rb").read() == b"hello"
    monkeypatch.setenv("MINIO_TPU_FSYNC", "on")
    assert atomicfile.fsync_enabled()
    atomicfile.write_atomic(p, b"world")     # barriers on: still atomic
    assert open(p, "rb").read() == b"world"
    assert not [f for f in os.listdir(os.path.dirname(p))
                if f.endswith(".tmp")]


def test_load_json_doc_shapes():
    assert atomicfile.load_json_doc(b'{"a": 1}') == {"a": 1}
    assert atomicfile.load_json_doc(b'{"a": 1') is None     # torn
    assert atomicfile.load_json_doc(b"12") is None          # wrong type
    assert atomicfile.load_json_doc(b"[1]") is None
    assert atomicfile.load_json_doc(b"") is None
    assert atomicfile.load_json_doc(b"\xff\x00") is None


# ---------------------------------------------------------------------------
# review-fix regressions
# ---------------------------------------------------------------------------

def test_fsck_reclaims_atomic_temp_siblings(zz):
    """A crash between write_atomic's temp write and its rename leaves
    `xl.meta.<hex>.tmp` INSIDE the object dir (not the tmp bucket) —
    fsck must reclaim it under the same age gate."""
    zz.put_object("b", "obj", b"x" * 600)
    d0 = eng_of(zz).disks[0]
    leftover = os.path.join(d0.root, "b", "obj", "xl.meta.ab12cd34.tmp")
    with open(leftover, "wb") as f:
        f.write(b'{"half')
    # fresh + default age gate: could be an in-flight commit — spared
    assert run_fsck(zz).counts() == {}
    rep = run_fsck(zz, repair=True, tmp_age_s=0)
    assert rep.counts() == {"stale_tmp": 1}
    assert not os.path.exists(leftover)
    assert get_bytes(zz, "b", "obj") == b"x" * 600
    assert run_fsck(zz, tmp_age_s=0).clean


def test_fsck_stub_spared_on_transient_tier_error(tmp_path, zz):
    """Only a POSITIVE TierObjectNotFound classifies a stub as
    dangling: an unreachable tier (network down at boot fsck) must
    never cause the irreversible stub drop."""
    from minio_tpu.tier.client import TierClientError
    from minio_tpu.tier.config import TierConfig, TierManager
    tiers = TierManager(zz)
    tiers.add(TierConfig.from_dict(
        {"name": "t1", "type": "fs",
         "params": {"path": str(tmp_path / "tier")}}))
    zz.put_object("b", "cold", b"c" * 900)
    import io as _io
    tiers.client("t1").put("rk", _io.BytesIO(b"c" * 900), 900)
    zz.transition_object("b", "cold", tier="t1", remote_object="rk")

    class DownClient:
        def head(self, key):
            raise TierClientError("connection refused")

    class DownTiers:
        def client(self, name):
            return DownClient()

    rep = run_fsck(zz, repair=True, tiers=DownTiers(), tmp_age_s=0)
    assert rep.counts() == {}           # cannot check != safe to drop
    # the stub is still there and restorable
    assert zz.get_object_info("b", "cold") is not None
    # an unmounted tier name is equally non-definitive
    class EmptyTiers:
        def client(self, name):
            raise KeyError(name)
    assert run_fsck(zz, repair=True, tiers=EmptyTiers(),
                    tmp_age_s=0).counts() == {}
