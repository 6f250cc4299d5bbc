"""The host's waits, named: every drive-pool and prefetch-pool task's
wait for a thread (`telemetry.submit`), the `/metrics` families that
count them, and the per-layer readers that read the new spans."""

from __future__ import annotations

import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from minio_tpu.object import metadata as meta
from minio_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from benchlib import harness  # noqa: E402  (pure Python, no program import)

MS = 1_000_000
WAIT = telemetry.REGISTRY.histogram("minio_tpu_host_pool_wait_seconds")


def observed(stage: str, pool: str = "drive_pool") -> int:
    """Tasks observed in the wait histogram; a scrape folds in the
    waits the workers buffered."""
    telemetry.REGISTRY.render()
    return WAIT.count(pool=pool, stage=stage)


@pytest.fixture()
def one_worker(monkeypatch):
    """The drive pool swapped for one thread, as test_gray swaps it, and
    that thread held until `release` is set."""
    pool = ThreadPoolExecutor(max_workers=1)
    monkeypatch.setattr(meta, "_POOL", pool)
    release = threading.Event()
    pool.submit(release.wait, 10)
    yield release
    release.set()
    pool.shutdown(wait=True)


def _probe(i: int) -> None:
    with telemetry.span("disk.probe", n=i):
        pass


def test_a_queued_task_waits_under_its_submitter(one_worker):
    block_s = 0.05
    with telemetry.trace("caller"):
        with telemetry.span("fanout") as fan:
            futs = [meta.submit_disk_task(_probe, i, stage="probe")
                    for i in range(2)]
            time.sleep(block_s)
            one_worker.set()
            for f in futs:
                f.result(5)
    kids = list(fan.children)
    waits = sorted((c for c in kids if c.name == "drive_pool.wait"),
                   key=lambda c: c.attrs["ahead"])
    disks = {c.attrs["n"]: c for c in kids if c.name == "disk.probe"}
    assert len(waits) == 2 and len(disks) == 2
    for i, w in enumerate(waits):
        assert w.parent_id == fan.span_id
        assert w.attrs == {"stage": "probe", "ahead": i}
        assert w.duration_s >= block_s
        # the wait ends where its task's drive span begins
        assert 0 <= disks[i].t0_ns - w.t1_ns < MS
    assert waits[1].t1_ns >= waits[0].t1_ns


def test_no_wait_span_without_a_trace_or_after_the_submitter(one_worker):
    seen = []

    def look():
        seen.append(telemetry.current_span())

    before = observed("late")
    untraced = meta.submit_disk_task(look, stage="late")
    with telemetry.trace("caller"):
        with telemetry.span("fanout") as fan:
            late = meta.submit_disk_task(look, stage="late")
        # the submitter finished before its task started
        one_worker.set()
        untraced.result(5)
        late.result(5)
    assert seen[0] is None                  # no context copied
    assert seen[1] is fan                   # the context rode along
    assert fan.children == []
    # every task is counted, traced or not
    assert observed("late") == before + 2


@pytest.mark.parametrize("path", ["for_each_disk", "for_each_disk_quorum"])
def test_fan_outs_name_their_stage(monkeypatch, path):
    monkeypatch.setattr(meta, "_POOL", ThreadPoolExecutor(max_workers=2))
    disks = [object(), None, object(), object()]
    before = observed("fan")
    with telemetry.trace("caller") as root:
        if path == "for_each_disk":
            _, errs = meta.for_each_disk(disks, lambda i, d: i, stage="fan")
        else:
            _, errs = meta.for_each_disk_quorum(
                disks, lambda i, d: i, 3, stall_s=1.0, stage="fan")
    meta._POOL.shutdown(wait=True)
    assert errs[0] is None and errs[1] is not None
    waits = [c for c in root.children if c.name == "drive_pool.wait"]
    # one task a drive that is there; the empty slot queues nothing
    assert len(waits) == 3
    assert {w.attrs["stage"] for w in waits} == {"fan"}
    assert observed("fan") == before + 3


def test_prefetch_pool_and_the_gauges():
    from minio_tpu.parallel import pipeline  # noqa: F401  (its pool)
    pool = ThreadPoolExecutor(max_workers=1)
    release = threading.Event()
    pool.submit(release.wait, 10)
    try:
        with telemetry.trace("get") as root:
            ran = telemetry.submit(pool, "prefetch_pool", lambda: 1,
                                   stage="lookahead")
            cancelled = telemetry.submit(pool, "prefetch_pool", lambda: 2)
            text = telemetry.REGISTRY.render()
            assert 'minio_tpu_host_pool_queued_tasks{pool="prefetch_pool"} 2' \
                in text
            # a lookahead the stream took back never starts
            assert telemetry.cancel(cancelled, "prefetch_pool")
            release.set()
            assert ran.result(5) == 1
    finally:
        release.set()
        pool.shutdown(wait=True)
    text = telemetry.REGISTRY.render()
    assert 'minio_tpu_host_pool_queued_tasks{pool="prefetch_pool"} 0' in text
    assert 'minio_tpu_host_pool_workers{pool="drive_pool"} 64' in text
    workers = max(16, 4 * (os.cpu_count() or 4))
    assert f'minio_tpu_host_pool_workers{{pool="prefetch_pool"}} ' \
        f'{workers}' in text
    waits = [c for c in root.children if c.name == "prefetch_pool.wait"]
    assert [w.attrs for w in waits] == [{"stage": "lookahead", "ahead": 0}]


def test_the_queue_count_loses_no_update_under_contention():
    """Many submitters, more than cores, on a small pool: every task is
    observed once, and the queued count returns to exactly 0."""
    pool = ThreadPoolExecutor(max_workers=4)
    before = observed("stress", "prefetch_pool")
    threads, per = 2 * (os.cpu_count() or 4) + 4, 50
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    cancelled = []
    try:
        def submitter():
            futs = [telemetry.submit(pool, "prefetch_pool", int,
                                     stage="stress") for _ in range(per)]
            cancelled.extend(f for f in futs[::7]
                             if telemetry.cancel(f, "prefetch_pool"))
            for f in futs:
                if not f.cancelled():
                    f.result(30)
        ts = [threading.Thread(target=submitter) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
        pool.shutdown(wait=True)
    # each task either started (and was observed once) or was cancelled
    assert observed("stress", "prefetch_pool") - before \
        == threads * per - len(cancelled)
    assert 'minio_tpu_host_pool_queued_tasks{pool="prefetch_pool"} 0' \
        in telemetry.REGISTRY.render()


# ---------------------------------------------------------------------------
# the six readers, on a synthetic window
# ---------------------------------------------------------------------------

def _sp(name, sid, parent, t0, t1, cpu=None, **attrs):
    d = {"name": name, "trace_id": "t", "span_id": sid, "t0_ns": t0 * MS,
         "t1_ns": t1 * MS, "tid": 1}
    if parent:
        d["parent_id"] = parent
    if cpu is not None:
        d["cpu_ns"] = cpu * MS
    if attrs:
        d["attrs"] = attrs
    return d


def _window(op, spans):
    return {"op": op, "verb": "encode" if op == "PUT" else "decode",
            "spans": spans, "anchors": {"t0": 0, "t1": 1000 * MS}}


def put_window():
    return _window("PUT", [
        _sp("PutObject", "p", "", 0, 100),
        _sp("drive_pool.wait", "w1", "p", 1, 3, stage="stat_vol"),
        _sp("pipeline.shard_write", "sw", "p", 10, 40),
        _sp("drive_pool.wait", "w2", "sw", 10, 16, stage="shard_write"),
        _sp("disk.shard_write", "d1", "sw", 16, 36, cpu=5),
        _sp("disk.shard_write", "d2", "sw", 12, 22),   # no cpu_ns: not read
        _sp("put.rename", "rn", "p", 50, 70),
        _sp("drive_pool.wait", "w3", "rn", 50, 54, stage="rename"),
        _sp("disk.rename_data", "r1", "rn", 54, 64, cpu=9),
        # a PUT that ended after the window: none of it is read
        _sp("PutObject", "late", "", 900, 1100),
        _sp("drive_pool.wait", "w4", "late", 900, 1000),
    ])


def get_window():
    spans = []
    # GET a: two groups, each with a decode wait; b: one host verify;
    # c: neither (its one drive wait still counts)
    for rid, t0, parts in (("a", 0, (("get.decode_wait", 8),
                                     ("get.decode_wait", 12))),
                           ("b", 200, (("get.host_verify", 30),)),
                           ("c", 400, ())):
        spans.append(_sp("GetObject", rid, "", t0, t0 + 100))
        spans.append(_sp("drive_pool.wait", rid + "w", rid, t0, t0 + 2,
                         stage="read_version"))
        spans.append(_sp("disk.shard_read", rid + "r", rid, t0 + 2,
                         t0 + 12, cpu=4))
        for j, (name, dur) in enumerate(parts):
            vd = f"{rid}v{j}"
            spans.append(_sp("pipeline.verify_decode", vd, rid,
                             t0 + 20 + 40 * j, t0 + 55 + 40 * j))
            spans.append(_sp(name, vd + "x", vd, t0 + 21 + 40 * j,
                             t0 + 21 + 40 * j + dur))
    return _window("GET", spans)


def test_host_wait_readers_on_a_put_window():
    win, reader = put_window(), harness.load_reader
    # three waits of 2, 6 and 4 ms under the one PUT that ended
    assert reader("drive_queue_ms.put")(win) == pytest.approx(4.0)
    # shard write 20 ms (5 on the CPU) + rename 10 ms (9): 16 of 30 off
    assert reader("drive_offcpu_share.put")(win) \
        == pytest.approx(100 * 16 / 30)
    for name in ("drive_queue_ms.get", "drive_offcpu_share.get",
                 "decode_wait_ms.get", "host_verify_ms.get"):
        assert reader(name)(win) is None        # a PUT window


def test_host_wait_readers_on_a_get_window():
    win, reader = get_window(), harness.load_reader
    assert reader("drive_queue_ms.get")(win) == pytest.approx(2.0)
    assert reader("drive_offcpu_share.get")(win) == pytest.approx(60.0)
    # summed a GET, averaged over the GETs that have one
    assert reader("decode_wait_ms.get")(win) == pytest.approx(20.0)
    assert reader("host_verify_ms.get")(win) == pytest.approx(30.0)
    for name in ("drive_queue_ms.put", "drive_offcpu_share.put"):
        assert reader(name)(win) is None        # a GET window


@pytest.mark.parametrize("name", [
    "drive_queue_ms.put", "drive_offcpu_share.put", "drive_queue_ms.get",
    "drive_offcpu_share.get", "decode_wait_ms.get", "host_verify_ms.get"])
def test_host_wait_readers_return_none_without_their_spans(name):
    reader = harness.load_reader(name)
    win = put_window() if name.endswith(".put") else get_window()
    wanted = {"drive_queue_ms": ("drive_pool.wait",),
              "drive_offcpu_share": ("disk.shard_write", "disk.rename_data",
                                     "disk.shard_read"),
              "decode_wait_ms": ("get.decode_wait",),
              "host_verify_ms": ("get.host_verify",)}[name.split(".")[0]]
    # the parent's tree: the spans this reader reads are not there
    older = dict(win, spans=[sp for sp in win["spans"]
                             if sp["name"] not in wanted])
    assert reader(older) is None
    # an untraced run's window
    assert reader({"op": win["op"], "verb": win["verb"],
                   "trace": None}) is None
    # spans without their CPU stamps (the recorder was off)
    bare = dict(win, spans=[{k: v for k, v in sp.items() if k != "cpu_ns"}
                            for sp in win["spans"]])
    if name.startswith("drive_offcpu_share"):
        assert reader(bare) is None
    else:
        assert reader(bare) is not None
