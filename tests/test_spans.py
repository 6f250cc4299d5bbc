"""One span tree per request on one monotonic clock: span stamps and
ids, the window recorder, the former's collect/slot split, the tree a
PUT and a degraded GET give through a live node, the admin recorder
surface, and the name scopes of the fused steps."""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

from minio_tpu.s3.credentials import Credentials
from minio_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from benchlib import spanview  # noqa: E402  (pure Python, no program import)

CREDS = Credentials("spantestkey", "spantestsecret1")


def _flat(tree: dict) -> list:
    out = [tree]
    for c in tree.get("children", ()):
        out.extend(_flat(c))
    return out


# ---------------------------------------------------------------------------
# clock and identity
# ---------------------------------------------------------------------------

def test_spans_of_two_threads_order_by_t0_ns():
    """`start` is wall time and can step; t0_ns/t1_ns are one monotonic
    clock for every thread, and each span names the thread it ran on."""
    order = []
    with telemetry.trace("two-threads") as root:
        def work(name, gate, done):
            gate.wait(5)
            with telemetry.span(name, parent=root) as sp:
                order.append(sp)
                time.sleep(0.002)
            done.set()
        gates = [threading.Event() for _ in range(2)]
        dones = [threading.Event() for _ in range(2)]
        ts = [threading.Thread(target=work, args=(f"t{i}", gates[i],
                                                  dones[i]))
              for i in range(2)]
        for t in ts:
            t.start()
        gates[1].set()
        assert dones[1].wait(5)
        gates[0].set()
        assert dones[0].wait(5)
        for t in ts:
            t.join(5)
    d = root.to_dict()
    kids = sorted(d["children"], key=lambda c: c["t0_ns"])
    assert [k["name"] for k in kids] == ["t1", "t0"]
    assert kids[0]["t1_ns"] <= kids[1]["t0_ns"]        # t1 ended first
    assert len({k["tid"] for k in kids} | {d["tid"]}) == 3
    for k in kids:
        assert d["t0_ns"] <= k["t0_ns"] <= k["t1_ns"] <= d["t1_ns"]
        assert abs((k["t1_ns"] - k["t0_ns"]) / 1e6
                   - k["duration_ms"]) < 0.01
    assert "start" in d and "duration_ms" in d          # what it had


def test_span_ids_are_counted_not_drawn():
    ids = []

    def many():
        with telemetry.trace("ids") as root:
            for _ in range(200):
                with telemetry.span("x"):
                    pass
        ids.extend(sp.span_id for sp in root.walk())
    ts = [threading.Thread(target=many) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10)
    assert len(ids) == len(set(ids)) == 4 * 201
    assert len({i[:6] for i in ids}) == 1               # the process tag


def test_timed_and_accum():
    """`timed` gives the interval with or without a trace (one timing
    per site); `accum` folds many crossings into one span."""
    with telemetry.timed("untraced") as t:
        time.sleep(0.001)
    assert t.seconds >= 0.001
    telemetry.accum("nothing").add(1, 2)                # no trace: no-ops
    telemetry.accum("nothing").flush(blocks=1)
    with telemetry.trace("acc") as root:
        with telemetry.timed("site", blocks=2) as t:
            time.sleep(0.001)
        acc = telemetry.accum("crossed")
        for _ in range(5):
            a = time.perf_counter_ns()
            acc.add(a, a + 1000)
        acc.flush(blocks=5)
        acc.flush()                                     # empty: no span
    kids = {c.name: c for c in root.children}
    assert kids["site"].duration_s == t.seconds
    assert kids["crossed"].attrs == {"busy_ns": 5000, "calls": 5,
                                     "blocks": 5}
    assert len(root.children) == 2


def test_shard_write_span_says_one_vectored_write_a_group(tmp_path):
    """`disk.shard_write` names the mechanism: on a local drive a whole
    group of B frames is writes=1, vectored=1 (it was 2 x B buffered
    writes), and the two /metrics counters read 1 syscall a B frames."""
    from minio_tpu.object.sets import ErasureSets
    reg = telemetry.REGISTRY
    syscalls = reg.counter("minio_tpu_shard_write_syscalls_total")
    frames = reg.counter("minio_tpu_shard_write_frames_total")
    sets = ErasureSets.from_drives(
        [str(tmp_path / f"d{i}") for i in range(6)], 1, 6, 2,
        block_size=1 << 16)
    try:
        sets.make_bucket("b")
        before = syscalls.value(), frames.value()
        with telemetry.trace("put") as root:
            sets.put_object("b", "o", os.urandom(16 * (1 << 16)))
        spans = [sp for sp in root.walk() if sp.name == "disk.shard_write"]
        blocks = sum(sp.attrs["blocks"] for sp in spans)
        assert blocks == 6 * 16 and len(spans) < blocks  # groups, not frames
        assert {sp.attrs["disk"] for sp in spans} == set(range(6))
        for sp in spans:
            assert (sp.attrs["writes"], sp.attrs["vectored"]) == (1, 1), \
                sp.attrs
        assert syscalls.value() - before[0] == len(spans)
        assert frames.value() - before[1] == blocks
    finally:
        sets.close()


# ---------------------------------------------------------------------------
# the window recorder
# ---------------------------------------------------------------------------

def test_recorder_off_keeps_nothing_and_sampling_is_as_it_was(monkeypatch):
    sink = telemetry.SpanSink(capacity=4, slow_s=0.05, sample=0.0)
    monkeypatch.setattr(telemetry, "SPANS", sink)
    assert telemetry._recording is False
    with telemetry.trace("fast") as fast:
        with telemetry.span("child") as child:
            pass
    with telemetry.trace("slow") as slow:
        time.sleep(0.06)
    # tail sampling: only the slow root is in the ring
    assert [t["name"] for t in sink.dump()] == ["slow"]
    assert sink.kept_total == 1 and sink.dropped_total == 1
    # nothing was recorded, no thread-CPU clock was read
    assert sink._rec == [] and sink._rec_dropped == 0
    assert fast.cpu_ns == child.cpu_ns == slow.cpu_ns == -1
    assert "cpu_ns" not in slow.to_dict()


def test_recorder_on_keeps_fast_roots_whole_and_counts_drops(monkeypatch):
    sink = telemetry.SpanSink(capacity=4, slow_s=3600.0, sample=0.0)
    monkeypatch.setattr(telemetry, "SPANS", sink)
    monkeypatch.setattr(telemetry.SpanSink, "RECORD_CAP", 3)
    sink.record_begin()
    try:
        for i in range(5):
            with telemetry.trace(f"r{i}"):
                with telemetry.span("work"):
                    # 5 ms of this thread's OWN CPU: a wall-clock spin
                    # is not granted them on a loaded box
                    t_end = time.thread_time() + 0.005
                    while time.thread_time() < t_end:
                        pass
    finally:
        win = sink.record_end()
    assert telemetry._recording is False
    assert sink.dump() == []                            # ring: none is slow
    assert win["roots"] == 3 and win["dropped"] == 2
    names = [sp["name"] for sp in win["spans"]]
    assert names == ["r0", "work", "r1", "work", "r2", "work"]
    work = win["spans"][1]
    assert work["parent_id"] == win["spans"][0]["span_id"]
    # a 5 ms spin burnt CPU on its own thread: wall - cpu is the wait
    assert work["cpu_ns"] >= 4e6
    assert work["t1_ns"] - work["t0_ns"] >= work["cpu_ns"] * 0.8
    assert win["t_ns"][1] > win["t_ns"][0]
    assert win["cpu_s"][1] - win["cpu_s"][0] >= 0.02
    # off again: the next root is not kept
    with telemetry.trace("after"):
        pass
    assert sink._rec == []


# ---------------------------------------------------------------------------
# the former: collect + slot = queue, children inside parents
# ---------------------------------------------------------------------------

@pytest.fixture()
def dev_routed(monkeypatch):
    from minio_tpu.object import codec as codec_mod
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)


def test_dispatch_children_nest_and_collect_plus_slot_is_queue(dev_routed):
    from minio_tpu import bitrot
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.parallel.scheduler import BatchScheduler

    hist = telemetry.REGISTRY.histogram("minio_tpu_device_dispatch_seconds")
    stages = ("queue", "collect", "slot", "collector_blocked", "transfer",
              "h2d", "compute", "fetch")
    before = {s: hist.count(verb="encode", stage=s) for s in stages}
    # one slot: the second bucket's group waits for it, so the
    # collector is blocked and `slot` is not nothing
    sched = BatchScheduler(max_wait=0.02, inflight=1)
    algo = bitrot.BitrotAlgorithm.HIGHWAYHASH256
    roots = []
    try:
        def put(k):
            codec = codec_mod.Codec(k, 2, k * 4096)
            data = np.random.randint(0, 255, (4, k, 4096), dtype=np.uint8)
            with telemetry.trace(f"former-{k}") as root:
                assert sched.submit(codec, data, algo).result(120)
            roots.append(root)
        ts = [threading.Thread(target=put, args=(k,)) for k in (4, 6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(180)
    finally:
        stats = sched.stats()
        sched.close()
    assert len(roots) == 2
    for s in stages:
        assert hist.count(verb="encode", stage=s) > before[s], s
    # the readback's rate a verb without a new reader: bytes that
    # crossed over the fetch stages' seconds
    enc = stats["verbs"]["encode"]
    assert enc["fetched_bytes"] == sum(
        4 * (2 * 4096 + (k + 2) * 32) for k in (4, 6))
    assert 0 < enc["fetch_seconds"] < 60
    for root in roots:
        tree = root.to_dict()
        by = {sp["name"]: sp for sp in _flat(tree)}
        d = by["sched.dispatch"]
        assert {c["name"] for c in d["children"]} >= {
            "sched.queue", "sched.transfer", "sched.h2d", "sched.compute",
            "sched.fetch"}
        # what crossed back, and as what: parity (4, 2, 4096) uint8
        # crosses as 32-bit words
        k = int(tree["name"].rsplit("-", 1)[1])
        assert by["sched.fetch"]["attrs"] == {
            "bytes": 4 * (2 * 4096 + (k + 2) * 32),
            "form": "uint32[4, 2, 1024]"}
        q = by["sched.queue"]
        assert [c["name"] for c in q["children"]] == ["sched.collect",
                                                      "sched.slot"]
        col, slot = by["sched.collect"], by["sched.slot"]
        dur = {n: by[n]["t1_ns"] - by[n]["t0_ns"] for n in by}
        assert abs(dur["sched.collect"] + dur["sched.slot"]
                   - dur["sched.queue"]) < 1e6            # within 1 ms
        assert col["t1_ns"] == slot["t0_ns"]
        # every attached span lies inside its parent, on the one clock
        def inside(node):
            for c in node.get("children", ()):
                assert node["t0_ns"] <= c["t0_ns"] \
                    and c["t1_ns"] <= node["t1_ns"] + 1000, (node["name"],
                                                             c["name"])
                inside(c)
        inside(tree)
        # stages follow each other: transfer, h2d, compute, fetch (a
        # stage's start is its end stamp minus its seconds: to 0.1 ms)
        order = [by[f"sched.{s}"] for s in ("transfer", "h2d", "compute",
                                            "fetch")]
        for a, b in zip(order, order[1:]):
            assert a["t1_ns"] <= b["t0_ns"] + 100_000


# ---------------------------------------------------------------------------
# a live node: one tree per request, every boundary of the table
# ---------------------------------------------------------------------------

PUT_SPANS = {"s3.auth", "s3.body_hash", "s3.respond", "engine.put_object",
             "put.read_stream", "put.buffer_wait", "put.hash_verify",
             "pipeline.encode", "sched.dispatch", "sched.queue",
             "sched.collect", "sched.slot", "sched.transfer", "sched.h2d",
             "sched.compute", "sched.fetch", "pipeline.shard_write",
             "disk.shard_write", "put.commit", "put.rename",
             "disk.rename_data", "drive_pool.wait"}
GET_SPANS = {"s3.auth", "s3.respond", "get.open", "engine.get_object",
             "get.read_shards", "disk.shard_read",
             "pipeline.verify_decode", "sched.dispatch", "sched.queue",
             "sched.collect", "sched.slot", "sched.transfer", "sched.h2d",
             "sched.compute", "sched.fetch", "get.join", "drive_pool.wait",
             "prefetch_pool.wait"}


@pytest.fixture()
def node(dev_routed, tmp_path):
    from minio_tpu.cluster import start_single
    nd = start_single([str(tmp_path / "d{1...6}")], "127.0.0.1", 0, CREDS,
                      parity=2, block_size=1 << 16)
    yield nd
    nd.shutdown()


def test_put_and_degraded_get_give_one_whole_tree_each(node, tmp_path):
    from tests.test_telemetry import Client
    c = Client(node.s3.port, CREDS)
    assert c.request("PUT", "/spanb")[0] == 200
    body = os.urandom(20 * (1 << 16) + 999)     # 3 groups of 8, short tail
    c.request("PUT", "/spanb/warm", body=body)  # programs compiled
    telemetry.SPANS.record_begin()
    try:
        assert c.request("PUT", "/spanb/obj", body=body)[0] == 200
        # one drive gone, as the benchmark pulls it: slot empty, path a file
        eng = node.sets.sets[0]
        disk = eng.disks[0]
        path = getattr(disk, "inner", disk).root
        eng.disks[0] = None
        shutil.rmtree(path)
        open(path, "w").close()
        for _ in range(2):                      # the decode programs, then warm
            st, got = c.request("GET", "/spanb/obj")
            assert st == 200 and got == body
    finally:
        win = telemetry.SPANS.record_end()
    assert win["dropped"] == 0
    spans = win["spans"]
    kids = spanview.children_of(spans)
    for root_name, want in (("PutObject", PUT_SPANS),
                            ("GetObject", GET_SPANS)):
        root = [r for r in spanview.roots(spans, root_name)
                if (r.get("attrs") or {}).get("path") == "/spanb/obj"][-1]
        tree = spanview.subtree(root, kids)
        assert len({sp["trace_id"] for sp in tree}) == 1
        names = {sp["name"] for sp in tree}
        assert want <= names, sorted(want - names)
        # one span per group or per request, not one per block
        assert len(tree) < 150, len(tree)
        # self times + children account for the root: nothing of the
        # request lies outside its tree (2 %)
        assert spanview.coverage(root, kids) >= 0.98
        for sp in tree:                          # children inside parents
            for ch in kids.get(sp["span_id"], ()):
                assert sp["t0_ns"] - 1000 <= ch["t0_ns"] \
                    and ch["t1_ns"] <= sp["t1_ns"] + 1000, (sp["name"],
                                                            ch["name"])
    put = [r for r in spanview.roots(spans, "PutObject")
           if r["attrs"]["path"] == "/spanb/obj"][-1]
    by = {}
    for sp in spanview.subtree(put, kids):
        by.setdefault(sp["name"], []).append(sp)
    assert len(by["put.read_stream"]) == 3          # one per group
    assert sum(sp["attrs"]["calls"] for sp in by["put.read_stream"]) >= 21
    assert len(by["s3.body_hash"]) == 1
    assert by["s3.body_hash"][0]["attrs"]["bytes"] == len(body)
    assert by["s3.body_hash"][0]["parent_id"] == put["span_id"]
    assert len(by["pipeline.encode"]) == len(by["pipeline.shard_write"])
    # each drive's writer closes in the last group's write task, and in
    # no other
    assert sorted(sp["attrs"]["closed"] for sp in by["disk.shard_write"]) \
        == [0] * 12 + [1] * 6
    assert "put.stage" not in by and "put.close" not in by
    assert "cpu_ns" in put                           # recorded: thread CPU


def _split_verify_decode(spans: list) -> list:
    """Each `pipeline.verify_decode` of the window with the names of its
    `get.decode_wait` / `get.host_verify` children, after checking they
    lie inside it and do not overlap."""
    kids = spanview.children_of(spans)
    out = []
    for vd in (sp for sp in spans if sp["name"] == "pipeline.verify_decode"):
        parts = sorted((c for c in kids.get(vd["span_id"], ())
                        if c["name"] in ("get.decode_wait",
                                         "get.host_verify")),
                       key=lambda c: c["t0_ns"])
        for c in parts:
            assert vd["t0_ns"] <= c["t0_ns"] <= c["t1_ns"] <= vd["t1_ns"]
        for a, b in zip(parts, parts[1:]):
            assert a["t1_ns"] <= b["t0_ns"], (a, b)
        for c in parts:
            if c["name"] == "get.host_verify":
                assert c["attrs"]["shards"] > 0
                assert c["attrs"]["bytes"] >= c["attrs"]["shards"]
        out.append([c["name"] for c in parts])
    return out


@pytest.mark.parametrize("decode", ["device", "fails"])
def test_degraded_get_splits_its_verify_decode(node, monkeypatch, decode):
    """A degraded GET's verify+decode names its two waits: the stream's
    wait on the former's future (`get.decode_wait`) and the host's batch
    verify of the shards no launch covered (`get.host_verify`). An
    object that lost a data shard waits on a decode; one that lost
    parity only verifies on the host; when the shared dispatch fails,
    the group falls back to the host and one verify_decode holds
    both."""
    from concurrent.futures import Future
    from tests.test_telemetry import Client
    from minio_tpu.object import metadata as meta
    c = Client(node.s3.port, CREDS)
    assert c.request("PUT", "/vdb")[0] == 200
    body = os.urandom(9 * (1 << 16) + 5)
    eng = node.sets.sets[0]
    lost: dict = {}                  # what drive 0 holds: "data" / "parity"
    for i in range(16):
        assert c.request("PUT", f"/vdb/o{i}", body=body)[0] == 200
        fi = next(f for f in meta.read_all_file_info(
            eng.disks, "vdb", f"o{i}")[0] if f is not None)
        kind = "data" if fi.erasure.distribution[0] <= 4 else "parity"
        lost.setdefault(kind, f"/vdb/o{i}")
    keys = [lost["data"]] if decode == "fails" \
        else [lost["data"], lost["parity"]]
    path = getattr(eng.disks[0], "inner", eng.disks[0]).root
    eng.disks[0] = None
    shutil.rmtree(path)
    open(path, "w").close()
    for key in keys:                            # the decode programs
        assert c.request("GET", key)[1] == body
    if decode == "fails":
        def failed(*_a, **_kw):
            fut = Future()
            fut.set_exception(RuntimeError("dispatch lost"))
            return fut
        monkeypatch.setattr(eng.scheduler, "submit_decode", failed)
    telemetry.SPANS.record_begin()
    try:
        for key in keys:
            assert c.request("GET", key)[1] == body
        assert c.request("GET", "/vdb")[0] == 200     # the last root is in
    finally:
        win = telemetry.SPANS.record_end()
    parts = _split_verify_decode(win["spans"])
    assert parts
    if decode == "device":
        assert ["get.decode_wait"] in parts         # lost a data shard
        assert ["get.host_verify"] in parts         # lost parity only
    else:
        assert ["get.decode_wait", "get.host_verify"] in parts


@pytest.mark.parametrize("key", ["fresh", "overwrite"])
def test_commit_spans_say_what_each_drive_was_handed(node, key):
    """put.commit counts its quorum fan-outs (one: rename); every
    disk.rename_data says whether it read a staged journal back (never,
    for a PUT) and what it found at the destination."""
    from tests.test_telemetry import Client
    c = Client(node.s3.port, CREDS)
    assert c.request("PUT", "/cmtb")[0] == 200
    if key == "overwrite":
        assert c.request("PUT", "/cmtb/k", body=b"a" * 3000)[0] == 200
    counters = [telemetry.REGISTRY.counter(name, "") for name in (
        "minio_tpu_put_commits_total",
        "minio_tpu_put_close_fanouts_total")]
    before = [ctr.value() for ctr in counters]
    telemetry.SPANS.record_begin()
    try:
        assert c.request("PUT", "/cmtb/k", body=b"b" * 5000)[0] == 200
        # a root is offered after its answer is out: one more round
        # trip, and the PUT's tree is in the window
        assert c.request("GET", "/cmtb/k")[1] == b"b" * 5000
    finally:
        spans = telemetry.SPANS.record_end()["spans"]
    commits = [sp for sp in spans if sp["name"] == "put.commit"]
    assert [sp["attrs"]["fanouts"] for sp in commits] == [1]
    assert not {"put.close_writers", "put.write_meta", "put.stage",
                "put.close"} & {sp["name"] for sp in spans}
    renames = [sp["attrs"] for sp in spans
               if sp["name"] == "disk.rename_data"]
    assert len(renames) == 6
    dst = "fresh" if key == "fresh" else "journal"
    assert all(a == {"src_read": 0, "dst": dst} for a in renames), renames
    # /metrics: one commit, no fallback close
    after = [ctr.value() for ctr in counters]
    assert [a - b for a, b in zip(after, before)] == [1, 0]
    text = telemetry.REGISTRY.render()
    assert "# TYPE minio_tpu_put_close_fanouts_total counter" in text


def test_admin_spans_record_then_fetch(node):
    from tests.test_telemetry import Client
    c = Client(node.s3.port, CREDS)
    assert c.request("PUT", "/recb")[0] == 200
    st, body = c.request("GET", "/minio/admin/v3/spans",
                         query={"record": "0.4"})
    assert st == 200 and json.loads(body)["recording"] is True
    assert c.request("PUT", "/recb/o", body=b"x" * 1000)[0] == 200
    st, body = c.request("GET", "/minio/admin/v3/spans",
                         query={"recorded": "1"})
    assert json.loads(body) == {"recording": True}    # still running
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        st, body = c.request("GET", "/minio/admin/v3/spans",
                             query={"recorded": "1"})
        win = json.loads(body)
        if "spans" in win:
            break
        time.sleep(0.05)
    # a 1 kB PUT is far under the ring's 500 ms: only the recorder has it
    assert any(sp["name"] == "PutObject" for sp in win["spans"])
    assert win["dropped"] == 0 and len(win["cpu_s"]) == 2
    st, _ = c.request("GET", "/minio/admin/v3/spans",
                      query={"record": "never"})
    assert st == 400
    assert telemetry._recording is False


# ---------------------------------------------------------------------------
# names on the device
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("step", ["put_step", "get_step", "heal_step"])
def test_fused_steps_carry_their_name_scopes(step):
    """Metadata only (the golden tests hold the bytes): the lowered
    program names what each operation IS."""
    from minio_tpu.models import pipeline
    from minio_tpu.ops import gf256
    x = np.zeros((2, 4, 4096), np.uint8)
    if step == "put_step":
        lowered = pipeline.put_step.lower(x, 4, 2)
    else:
        m2 = gf256.expand_to_gf2(np.ones((1, 4), np.uint8))
        lowered = getattr(pipeline, step).lower(x, m2, 1, 4)
    text = lowered.as_text(debug_info=True)
    for scope in ("rs_matmul", "bitrot_hash", "pack"):
        assert f"/{scope}/" in text, scope


def test_pallas_call_is_named():
    import inspect
    from minio_tpu.ops import rs_pallas
    assert 'name="gf_matmul"' in inspect.getsource(rs_pallas._run)
