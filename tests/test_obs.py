"""Cluster observability plane (ISSUE 13): federated metrics scrape,
live cluster trace streaming, device-dispatch attribution, edge-loop
observability, /spans filters, and edge/threaded trace parity.

The multi-node harness runs two real ClusterNodes on loopback ports
(the test_cluster pattern) and proves the acceptance list:

  1. the ?cluster=1 exposition equals the bucket-wise merge of the
     per-node registries (counters summed, node labels on gauges), and
     a KILLED peer yields a degraded-but-successful scrape with
     `minio_tpu_cluster_scrape_failed_total{node}` counted;
  2. a ?follow=1 trace stream opened on node A delivers a request
     served by node B — on both frontends — and a client disconnect
     unwinds every peer subscription without leaking a worker thread;
  3. dispatch-stage histograms show a nonzero queue/transfer/compute
     split and pass the exposition lint.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import re
import socket
import threading
import time
import urllib.parse

import pytest

from minio_tpu.cluster import ClusterNode, NodeSpec
from minio_tpu.madmin import AdminClient
from minio_tpu.object.sets import ErasureSets
from minio_tpu.s3 import signature as sig
from minio_tpu.s3.admin import mount_admin
from minio_tpu.s3.credentials import Credentials
from minio_tpu.s3.server import S3Server
from minio_tpu.utils import promfed, telemetry

CREDS = Credentials("obstestkey123", "obstestsecret1234")
REGION = "us-east-1"


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def _boot_cluster(tmp_path, edge: bool = True):
    """Two real nodes, booted concurrently (bootstrap verify needs
    both listening)."""
    import os
    ports = _free_ports(2)
    nodes = [NodeSpec("127.0.0.1", ports[i],
                      [str(tmp_path / f"n{i}d{j}") for j in range(2)])
             for i in range(2)]
    out: list = [None, None]
    errs: list = [None, None]
    was = os.environ.get("MINIO_TPU_EDGE")
    os.environ["MINIO_TPU_EDGE"] = "on" if edge else "off"
    try:
        def boot(i):
            try:
                out[i] = ClusterNode(nodes, i, CREDS, parity=1,
                                     set_drive_count=4,
                                     block_size=1 << 16,
                                     format_timeout=60.0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs[i] = e

        threads = [threading.Thread(target=boot, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        if was is None:
            os.environ.pop("MINIO_TPU_EDGE", None)
        else:
            os.environ["MINIO_TPU_EDGE"] = was
    for e in errs:
        if e is not None:
            raise e
    assert all(o is not None for o in out)
    return out


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    nodes = _boot_cluster(tmp_path_factory.mktemp("obscluster"))
    yield nodes
    for n in nodes:
        try:
            n.shutdown()
        except Exception:  # noqa: BLE001 — second shutdown of a node
            pass           # the kill test already stopped


def _signed_request(port, method, path, query=None, body=b""):
    query = {k: [v] for k, v in (query or {}).items()}
    qs = urllib.parse.urlencode({k: v[0] for k, v in query.items()})
    hdrs = sig.sign_v4(method, path, query,
                       {"host": f"127.0.0.1:{port}"},
                       hashlib.sha256(body).hexdigest(), CREDS, REGION)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request(method, path + (f"?{qs}" if qs else ""), body=body,
                 headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _mc(node) -> AdminClient:
    return AdminClient("127.0.0.1", node.spec.port, CREDS.access_key,
                       CREDS.secret_key)


def _follow_pumps() -> list:
    return [t for t in threading.enumerate()
            if t.name == "trace-follow-peer" and t.is_alive()]


def _await_no_pumps(deadline_s: float = 12.0) -> None:
    deadline = time.monotonic() + deadline_s
    while _follow_pumps() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not _follow_pumps(), (
        "peer trace subscriptions leaked pump threads: "
        + ", ".join(t.name for t in _follow_pumps()))


# ---------------------------------------------------------------------------
# 1. federated metrics scrape
# ---------------------------------------------------------------------------

PEER_EXPO = """# HELP minio_obs_fed_total synthetic ops
# TYPE minio_obs_fed_total counter
minio_obs_fed_total{api="x"} 5
# HELP minio_obs_fed_depth synthetic queue depth
# TYPE minio_obs_fed_depth gauge
minio_obs_fed_depth 7
# HELP minio_obs_fed_seconds synthetic latency
# TYPE minio_obs_fed_seconds histogram
minio_obs_fed_seconds_bucket{le="0.1"} 2
minio_obs_fed_seconds_bucket{le="+Inf"} 4
minio_obs_fed_seconds_sum 1.5
minio_obs_fed_seconds_count 4
"""


def test_cluster_scrape_is_bucketwise_merge(cluster):
    """The ?cluster=1 exposition equals promfed's merge of the
    per-node registries: counters summed (no node label), gauges
    node-labelled, histograms bucket-wise summed. Node B's exposition
    is stubbed (in one process both nodes share the registry, so the
    REAL per-node divergence a deployment has must be injected)."""
    a, b = cluster
    # local (node A) side of the synthetic family
    telemetry.REGISTRY.counter("minio_obs_fed_total",
                               "synthetic ops").inc(3, api="x")
    telemetry.REGISTRY.gauge("minio_obs_fed_depth",
                             "synthetic queue depth").set(2)
    h = telemetry.REGISTRY.histogram("minio_obs_fed_seconds",
                                     "synthetic latency",
                                     buckets=(0.1,))
    h.observe(0.05)
    b._peer_rpc.get_metrics_text = lambda: PEER_EXPO
    merged = _mc(a).cluster_metrics()

    fams = promfed.parse_exposition(merged)
    # counter summed across nodes: 3 (A) + 5 (B stub)
    assert fams["minio_obs_fed_total"].samples[
        ("minio_obs_fed_total", (("api", "x"),))] == 8
    # gauges: one series per node, node label attached
    depth = fams["minio_obs_fed_depth"].samples
    assert depth[("minio_obs_fed_depth",
                  (("node", a.spec.addr),))] == 2
    assert depth[("minio_obs_fed_depth",
                  (("node", b.spec.addr),))] == 7
    # histogram bucket-wise: A contributes 1 obs in le=0.1, B stubs 2/4
    lat = fams["minio_obs_fed_seconds"].samples
    assert lat[("minio_obs_fed_seconds_bucket",
                (("le", "0.1"),))] == 3
    assert lat[("minio_obs_fed_seconds_bucket",
                (("le", "+Inf"),))] == 5
    assert lat[("minio_obs_fed_seconds_count", ())] == 5
    # ... and the endpoint output IS the library merge of the same
    # inputs (the acceptance equality, not just spot samples)
    local_text = a.admin.metrics.local_text()
    expect = promfed.merge_expositions(
        [(a.spec.addr, local_text), (b.spec.addr, PEER_EXPO)])
    exp_fams = promfed.parse_exposition(expect)
    for name in ("minio_obs_fed_total", "minio_obs_fed_depth",
                 "minio_obs_fed_seconds"):
        assert fams[name].samples == exp_fams[name].samples, name


def test_cluster_scrape_deadline_bounded(cluster):
    """A peer that answers too slowly counts as scrape-failed: the
    per-peer deadline bounds the whole federated scrape."""
    import os
    a, b = cluster

    def slow():
        time.sleep(5.0)
        return PEER_EXPO

    b._peer_rpc.get_metrics_text = slow
    was = os.environ.get("MINIO_TPU_CLUSTER_SCRAPE_S")
    os.environ["MINIO_TPU_CLUSTER_SCRAPE_S"] = "0.5"
    shed = telemetry.REGISTRY.counter(
        "minio_tpu_cluster_scrape_failed_total")
    before = shed.value(node=b.spec.addr)
    try:
        t0 = time.monotonic()
        merged = _mc(a).cluster_metrics()
        assert time.monotonic() - t0 < 4.0
    finally:
        if was is None:
            os.environ.pop("MINIO_TPU_CLUSTER_SCRAPE_S", None)
        else:
            os.environ["MINIO_TPU_CLUSTER_SCRAPE_S"] = was
        b._peer_rpc.get_metrics_text = lambda: PEER_EXPO
    assert shed.value(node=b.spec.addr) == before + 1
    assert "minio_tpu_cluster_scrape_failed_total" in merged
    # the timed-out scrape tripped the peer transport offline (that is
    # the transport's deadline semantics); wait for the health probe to
    # re-admit it so later tests see a whole cluster
    deadline = time.monotonic() + 20
    while not all(p.online for p in a.notification.peers) and \
            time.monotonic() < deadline:
        time.sleep(0.2)
    assert all(p.online for p in a.notification.peers)


# ---------------------------------------------------------------------------
# 2. live cluster trace streaming
# ---------------------------------------------------------------------------

def test_follow_delivers_peer_records_and_unwinds(cluster):
    """A ?follow=1 stream on node A delivers a request SERVED BY node
    B (peer subscription grafting), then ends without leaking the
    pump threads."""
    a, b = cluster
    got: list = []
    t = threading.Thread(
        target=lambda: got.extend(
            _mc(a).trace_follow(count=1, api="PutObject", timeout=60)),
        daemon=True)
    t.start()
    time.sleep(0.8)                    # peer subscriptions armed
    st, _ = _signed_request(b.spec.port, "PUT", "/obsfollow")
    assert st == 200
    st, _ = _signed_request(b.spec.port, "PUT", "/obsfollow/obj",
                            body=b"follow me")
    assert st == 200
    t.join(timeout=20)
    assert not t.is_alive(), "follow stream never delivered"
    assert got and got[0]["api"] == "PutObject"
    assert got[0]["node"] == b.spec.addr, got[0]
    assert "ttfb_ms" in got[0]
    _await_no_pumps()


def test_follow_disconnect_frees_workers(cluster):
    """A client that vanishes mid-follow must unwind the server-side
    subscription (heartbeat write fails -> generator closes -> peer
    pumps exit) — no worker thread leaks."""
    a, _b = cluster
    path = "/minio/admin/v3/trace"
    query = {"follow": ["1"]}
    qs = urllib.parse.urlencode({"follow": "1"})
    hdrs = sig.sign_v4("GET", path, query,
                       {"host": f"127.0.0.1:{a.spec.port}"},
                       hashlib.sha256(b"").hexdigest(), CREDS, REGION)
    s = socket.create_connection(("127.0.0.1", a.spec.port),
                                 timeout=10)
    head = f"GET {path}?{qs} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in hdrs.items()) + "\r\n"
    s.sendall(head.encode())
    buf = s.recv(4096)                 # headers (+ maybe a heartbeat)
    assert b"200" in buf.split(b"\r\n", 1)[0]
    deadline = time.monotonic() + 10
    while not _follow_pumps() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert _follow_pumps(), "peer subscription never opened"
    s.close()                          # client dies
    _await_no_pumps()


def test_follow_threaded_frontend(tmp_path_factory):
    """The same cross-node follow delivery on the THREADED frontend
    (the byte-level oracle must hold the stream too)."""
    nodes = _boot_cluster(tmp_path_factory.mktemp("obsthreaded"),
                          edge=False)
    a, b = nodes
    try:
        assert not a.s3.edge_enabled
        got: list = []
        t = threading.Thread(
            target=lambda: got.extend(
                _mc(a).trace_follow(count=1, api="PutObject",
                                    timeout=60)),
            daemon=True)
        t.start()
        time.sleep(0.8)
        st, _ = _signed_request(b.spec.port, "PUT", "/obsthr")
        assert st == 200
        st, _ = _signed_request(b.spec.port, "PUT", "/obsthr/obj",
                                body=b"x")
        assert st == 200
        t.join(timeout=20)
        assert got and got[0]["node"] == b.spec.addr
        _await_no_pumps()
        # /events?follow=1 parity on the same threaded cluster: the
        # journal stream must hold across frontends too (ISSUE 18)
        from minio_tpu.utils import eventlog
        ev: list = []
        t2 = threading.Thread(
            target=lambda: ev.extend(
                _mc(a).events_follow(count=1, classes="net.heal",
                                     timeout=60)),
            daemon=True)
        t2.start()
        deadline = time.monotonic() + 10
        while not _event_pumps() and time.monotonic() < deadline:
            time.sleep(0.1)
        _drive_event_until(
            t2, lambda: eventlog.emit("net.heal", peers="thr|parity"))
        assert ev and ev[0]["class"] == "net.heal"
        _await_no_event_pumps()
    finally:
        for n in nodes:
            n.shutdown()


# ---------------------------------------------------------------------------
# 2b. live journal streaming — /events?follow=1 (ISSUE 18)
# ---------------------------------------------------------------------------

def _event_pumps() -> list:
    return [t for t in threading.enumerate()
            if t.name == "event-follow-peer" and t.is_alive()]


def _await_no_event_pumps(deadline_s: float = 12.0) -> None:
    deadline = time.monotonic() + deadline_s
    while _event_pumps() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not _event_pumps(), (
        "peer event subscriptions leaked pump threads: "
        + ", ".join(t.name for t in _event_pumps()))


def _drive_event_until(thread, emit_fn, deadline_s: float = 15.0):
    """Emit on a cadence until the follow consumer finishes — the
    stream's peer grafts subscribe asynchronously, so a single emit
    can race the subscription window."""
    deadline = time.monotonic() + deadline_s
    while thread.is_alive() and time.monotonic() < deadline:
        emit_fn()
        thread.join(timeout=0.3)
    assert not thread.is_alive(), "events follow never delivered"


def test_events_follow_delivers_and_unwinds(cluster):
    """A /events?follow=1 stream on node A delivers a journal event,
    grafts peer subscriptions (the pump threads exist while open), and
    ends at count without leaking them."""
    from minio_tpu.utils import eventlog
    a, _b = cluster
    got: list = []
    t = threading.Thread(
        target=lambda: got.extend(
            _mc(a).events_follow(count=1, classes="net.heal",
                                 timeout=60)),
        daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not _event_pumps() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert _event_pumps(), "peer event subscription never opened"
    _drive_event_until(
        t, lambda: eventlog.emit("net.heal", peers="obs|follow"))
    assert got and got[0]["class"] == "net.heal"
    assert got[0]["attrs"]["peers"] == "obs|follow"
    assert got[0]["sub"] == "net" and "seq" in got[0]
    _await_no_event_pumps()


def test_events_follow_disconnect_frees_workers(cluster):
    """A client that vanishes mid-/events-follow must unwind the
    server-side generator (heartbeat write fails -> peer pumps exit) —
    the PR-12 trace-stream lesson applied to the journal stream."""
    a, _b = cluster
    path = "/minio/admin/v3/events"
    query = {"follow": ["1"]}
    qs = urllib.parse.urlencode({"follow": "1"})
    hdrs = sig.sign_v4("GET", path, query,
                       {"host": f"127.0.0.1:{a.spec.port}"},
                       hashlib.sha256(b"").hexdigest(), CREDS, REGION)
    s = socket.create_connection(("127.0.0.1", a.spec.port),
                                 timeout=10)
    head = f"GET {path}?{qs} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in hdrs.items()) + "\r\n"
    s.sendall(head.encode())
    buf = s.recv(4096)                 # headers (+ maybe a heartbeat)
    assert b"200" in buf.split(b"\r\n", 1)[0]
    deadline = time.monotonic() + 10
    while not _event_pumps() and time.monotonic() < deadline:
        time.sleep(0.1)
    assert _event_pumps(), "peer event subscription never opened"
    s.close()                          # client dies
    _await_no_event_pumps()


def test_events_endpoint_filters_and_cluster_merge(cluster):
    """The non-follow /events window: class/severity filters apply,
    and ?cluster=1 merges peer windows WITHOUT duplicating entries —
    in-process nodes share one journal, so the merge must dedupe by
    (node, seq)."""
    from minio_tpu.utils import eventlog
    a, _b = cluster
    eventlog.emit("drive.suspect", drive="/obs/d9", set=3)
    ents = _mc(a).events(classes="drive.suspect")
    assert any(e["attrs"].get("drive") == "/obs/d9" for e in ents)
    assert all(e["class"] == "drive.suspect" for e in ents)
    for e in _mc(a).events(severity="error"):
        assert e["sev"] in ("error", "crit"), e
    merged = _mc(a).events(cluster=True, classes="drive.suspect")
    keys = [(e["node"], e["seq"]) for e in merged]
    assert len(keys) == len(set(keys)), "cluster merge duplicated"
    assert any(e["attrs"].get("drive") == "/obs/d9" for e in merged)


def test_drivehealth_surfaces_journal(cluster):
    """Satellite (a): the drivehealth document carries the
    journal-backed transition history next to the in-memory deque."""
    from minio_tpu.utils import eventlog
    a, _b = cluster
    eventlog.emit("drive.probation", drive="/obs/dh", set=1)
    doc = _mc(a).drive_health()
    j = doc.get("journal")
    assert isinstance(j, list)
    assert any(e["class"] == "drive.probation"
               and e["attrs"].get("drive") == "/obs/dh" for e in j)
    assert all(e["sub"] in ("drive", "health") for e in j)


def test_slo_endpoint_reports_objectives(cluster):
    """GET /slo answers with the burn-rate status document."""
    a, _b = cluster
    doc = _mc(a).slo()
    assert "objectives" in doc and "burn_threshold" in doc
    names = {o["objective"] for o in doc["objectives"]}
    assert {"read-availability", "write-availability",
            "read-latency", "write-latency"} <= names


# ---------------------------------------------------------------------------
# single-server surfaces: shed reason, spans filters, edge parity,
# loop lag, stage split
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def layer(tmp_path_factory):
    root = tmp_path_factory.mktemp("obsdrives")
    sets = ErasureSets.from_drives(
        [str(root / f"d{i}") for i in range(4)], 1, 4, 2,
        block_size=1 << 16)
    yield sets
    sets.close()


def _mk_server(layer, **env) -> S3Server:
    import os
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        srv = S3Server(layer, creds=CREDS, region=REGION).start()
        mount_admin(srv)
        return srv
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_shed_reason_rides_trace_records(layer):
    """A 503 shed's trace record carries WHY (the admission reason
    label) — on the staging-window path through the middleware."""
    srv = _mk_server(layer, MINIO_TPU_EDGE="on")
    try:
        srv.api.admission._shed_until = time.monotonic() + 30.0
        try:
            st, _ = _signed_request(srv.port, "PUT", "/shedtr/obj",
                                    body=b"x" * 64)
            assert st == 503
        finally:
            srv.api.admission._shed_until = 0.0
        entries = [e for e in srv.api.trace.recent
                   if e.get("status") == 503
                   and e.get("path") == "/shedtr/obj"]
        assert entries and entries[-1]["shed_reason"] == "staging", \
            entries[-2:]
    finally:
        srv.stop()


def test_spans_endpoint_filters(layer):
    srv = _mk_server(layer, MINIO_TPU_EDGE="on")
    was = (telemetry.SPANS.slow_s, telemetry.SPANS.sample)
    telemetry.SPANS.configure(sample=1.0)
    try:
        assert _signed_request(srv.port, "PUT", "/spfil")[0] == 200
        assert _signed_request(srv.port, "PUT", "/spfil/obj",
                               body=b"z" * 4096)[0] == 200
        assert _signed_request(srv.port, "GET", "/spfil/obj")[0] == 200
        st, body = _signed_request(srv.port, "GET",
                                   "/minio/admin/v3/spans",
                                   {"api": "PutObject",
                                    "count": "100"})
        assert st == 200
        spans = json.loads(body)["spans"]
        assert spans and all(s["name"] == "PutObject" for s in spans)
        tid = spans[0]["trace_id"]
        st, body = _signed_request(srv.port, "GET",
                                   "/minio/admin/v3/spans",
                                   {"trace_id": tid})
        picked = json.loads(body)["spans"]
        assert len(picked) == 1 and picked[0]["trace_id"] == tid
    finally:
        telemetry.SPANS.configure(*was)
        srv.stop()


def _find(node: dict, name: str) -> list:
    out = [node] if node["name"] == name else []
    for c in node.get("children", ()):
        out.extend(_find(c, name))
    return out


def test_edge_trace_parity_with_threaded_oracle(layer):
    """An edge-served request roots the SAME span tree shape as the
    threaded oracle: same root name and attrs, engine child present,
    TTFB recorded (trace entry + histogram family) — satellite 2's
    parity pin."""
    from minio_tpu.s3.edge import dispatch as edge_dispatch
    was = (telemetry.SPANS.slow_s, telemetry.SPANS.sample)
    telemetry.SPANS.configure(sample=1.0)
    roots: dict = {}
    entries: dict = {}
    ttfb_delta: dict = {}
    try:
        for tag, env in (("edge", "on"), ("threaded", "off")):
            srv = _mk_server(layer, MINIO_TPU_EDGE=env)
            try:
                assert srv.edge_enabled == (env == "on")
                path = f"/part-{tag}/obj"
                before = edge_dispatch._HTTP_TTFB.count(
                    api="PutObject")
                assert _signed_request(srv.port, "PUT",
                                       f"/part-{tag}")[0] == 200
                assert _signed_request(srv.port, "PUT", path,
                                       body=b"p" * 100000)[0] == 200
                # the client sees the response a hair before the
                # server thread reaches the histogram observes in
                # run_request's finally — poll, don't read instantly
                hist_deadline = time.monotonic() + 5.0
                while time.monotonic() < hist_deadline:
                    ttfb_delta[tag] = edge_dispatch._HTTP_TTFB.count(
                        api="PutObject") - before
                    if ttfb_delta[tag]:
                        break
                    time.sleep(0.01)
                # the client sees the response a hair before the
                # server closes (and offers) the root span — poll
                trees: list = []
                deadline = time.monotonic() + 5.0
                while not trees and time.monotonic() < deadline:
                    trees = [t for t in telemetry.SPANS.dump(200)
                             if t["name"] == "PutObject"
                             and t.get("attrs", {}).get("path") == path]
                    if not trees:
                        time.sleep(0.05)
                assert trees, f"no kept PutObject tree for {tag}"
                roots[tag] = trees[-1]
                ent = [e for e in srv.api.trace.recent
                       if e.get("path") == path
                       and e.get("api") == "PutObject"]
                assert ent
                entries[tag] = ent[-1]
            finally:
                srv.stop()
    finally:
        telemetry.SPANS.configure(*was)
    e, t = roots["edge"], roots["threaded"]
    # same root identity: name + attr KEYS + method attr value
    assert e["name"] == t["name"] == "PutObject"
    assert set(e.get("attrs", {})) == set(t.get("attrs", {}))
    assert e["attrs"]["method"] == t["attrs"]["method"] == "PUT"
    # same tree shape where it matters: the engine child roots below
    # the handler on both transports
    assert _find(e, "engine.put_object") and \
        _find(t, "engine.put_object")
    # TTFB recorded on both: per-request histogram sample + entry field
    assert ttfb_delta == {"edge": 1, "threaded": 1}
    assert entries["edge"].get("ttfb_ms", 0) > 0
    assert entries["threaded"].get("ttfb_ms", 0) > 0


def test_edge_loop_lag_and_pool_gauges(layer):
    """The edge's own observability: the per-loop lag sampler observes
    ticks and the worker-pool busy/idle gauges render at exposition
    time."""
    srv = _mk_server(layer, MINIO_TPU_EDGE="on",
                     MINIO_TPU_EDGE_LAG_S="0.05")
    try:
        # a request spins up a pool worker so the gauges have a pool
        assert _signed_request(
            srv.port, "GET", "/minio/prometheus/metrics")[0] == 200
        time.sleep(0.5)                # a few sampler ticks
        st, body = _signed_request(srv.port, "GET",
                                   "/minio/prometheus/metrics")
        assert st == 200
        text = body.decode()
        m = re.search(
            r'minio_tpu_edge_loop_lag_seconds_count\{loop="0"\} (\d+)',
            text)
        assert m and int(m.group(1)) >= 3, \
            "lag sampler never ticked"
        for fam in ("minio_tpu_edge_pool_size",
                    "minio_tpu_edge_pool_busy",
                    "minio_tpu_edge_pool_idle",
                    "minio_tpu_edge_pool_pending",
                    "minio_tpu_edge_open_conns"):
            assert f"\n{fam} " in text or f"\n{fam}{{" in text, fam
    finally:
        srv.stop()


def test_promfed_label_escape_roundtrip():
    """Label values survive the merge's escape/unescape — sequential
    .replace() corrupted backslash-bearing values ('\\\\' + 'n' read
    back as a newline; review finding)."""
    for v in ("C:\\d1\\new", 'quo"te', "multi\nline", "\\n", "plain"):
        assert promfed._unescape(promfed._escape(v)) == v, v
    merged = promfed.merge_expositions(
        [("n1", '# TYPE g gauge\ng{path="C:\\\\d1\\\\new"} 1\n')])
    fams = promfed.parse_exposition(merged)
    assert ("g", (("node", "n1"), ("path", "C:\\d1\\new"))) \
        in fams["g"].samples


def test_filtered_nonfollow_stream_idles_out_on_matches():
    """A filtered non-follow stream on a server with steady
    NON-matching traffic must still terminate at idle_timeout: idle
    counts from the last MATCHED entry, else the worker + hub
    subscription leak forever (review finding)."""
    from minio_tpu.s3.trace import TraceSys
    ts = TraceSys(node_name="n1")
    stop = threading.Event()

    def spam():
        while not stop.is_set():
            ts.record("GET", "/b/k", "", 200, 0.001, api="GetObject")
            time.sleep(0.05)

    t = threading.Thread(target=spam, daemon=True)
    t.start()
    try:
        t0 = time.monotonic()
        out = list(ts.stream(idle_timeout=0.5, apis={"PutObject"}))
        dt = time.monotonic() - t0
        assert out == []
        assert dt < 5.0, f"filtered stream never idled out ({dt:.1f}s)"
    finally:
        stop.set()
        t.join(timeout=2)


# ---------------------------------------------------------------------------
# 3. dispatch-stage attribution
# ---------------------------------------------------------------------------

def test_dispatch_stage_split_and_exposition_lint(monkeypatch):
    """A fused dispatch records a nonzero queue/transfer/compute stage
    split (histogram + child spans under sched.dispatch) and the
    family renders as a lint-clean histogram triplet."""
    import numpy as np
    from minio_tpu import bitrot
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.parallel.scheduler import BatchScheduler

    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    hist = telemetry.REGISTRY.histogram(
        "minio_tpu_device_dispatch_seconds")
    stages = ("queue", "collect", "slot", "collector_blocked",
              "transfer", "h2d", "compute", "fetch")
    before = {s: hist.count(verb="encode", stage=s) for s in stages}
    sched = BatchScheduler(max_wait=0.05)
    codec = codec_mod.Codec(4, 2, 4 * 4096)
    data = np.random.randint(0, 255, (4, 4, 4096), dtype=np.uint8)
    try:
        with telemetry.trace("obs-stage-test") as root:
            out = sched.submit(
                codec, data,
                bitrot.BitrotAlgorithm.HIGHWAYHASH256).result(120)
        assert out is not None, "dispatch declined the device route"
    finally:
        sched.close()
    # nonzero queue + compute split (transfer can round to ~0 on a
    # single-group batch but must be OBSERVED; fetch merges into
    # compute on the mesh path)
    for s in ("queue", "transfer", "compute"):
        assert hist.count(verb="encode", stage=s) > before[s], s
    # queue's two parts are observed once per group beside it, the
    # collector's own blocked time once per launch, the upload apart
    # from the program
    for s in ("collect", "slot", "collector_blocked", "h2d", "fetch"):
        assert hist.count(verb="encode", stage=s) == before[s] + 1, s
    # the dispatch span carries the stage children, and the queue span
    # its two parts — which add up to it
    tree = root.to_dict()
    d = _find(tree, "sched.dispatch")
    assert d, tree
    child_names = {c["name"] for c in d[0].get("children", ())}
    assert {"sched.queue", "sched.transfer", "sched.h2d",
            "sched.compute", "sched.fetch"} <= child_names, child_names
    q = _find(tree, "sched.queue")[0]
    parts = {c["name"]: c for c in q["children"]}
    assert set(parts) == {"sched.collect", "sched.slot"}
    assert abs(sum(c["t1_ns"] - c["t0_ns"] for c in parts.values())
               - (q["t1_ns"] - q["t0_ns"])) < 1e6
    # a 50 ms grace window with one submitter: the wait is `collect`
    assert parts["sched.collect"]["duration_ms"] >= 40
    # exposition lint: histogram triplet with consistent labels
    text = telemetry.REGISTRY.render()
    fam = "minio_tpu_device_dispatch_seconds"
    assert f"# TYPE {fam} histogram" in text
    assert re.search(
        fam + r'_bucket\{stage="compute",verb="encode",le="[^"]+"\}',
        text)
    assert f"{fam}_sum{{" in text and f"{fam}_count{{" in text
    # inflight gauge registered and rendered
    assert "minio_tpu_sched_inflight_dispatches" in text


# ---------------------------------------------------------------------------
# killed peer — LAST: tears down node B of the shared cluster
# ---------------------------------------------------------------------------

def test_killed_peer_degrades_scrape_not_fails(cluster):
    """Kill node B for real: node A's ?cluster=1 scrape still answers
    (node A's families present) and the failure is counted per node in
    minio_tpu_cluster_scrape_failed_total."""
    a, b = cluster
    b_addr = b.spec.addr
    shed = telemetry.REGISTRY.counter(
        "minio_tpu_cluster_scrape_failed_total")
    before = shed.value(node=b_addr)
    b.shutdown()
    merged = _mc(a).cluster_metrics()
    assert shed.value(node=b_addr) >= before + 1
    fams = promfed.parse_exposition(merged)
    assert "minio_tpu_http_requests_duration_seconds" in fams
    assert fams["minio_tpu_cluster_scrape_failed_total"].samples[
        ("minio_tpu_cluster_scrape_failed_total",
         (("node", b_addr),))] >= 1
