"""Event journal (ISSUE 18, tentpole layer 1) + bench_trend satellite.

Fast (tier-1) coverage of the incident plane's foundation:

  * EVENT_MATRIX names a real emit for EVERY registered event class —
    a new class without a matrix entry fails test_matrix_covers_registry
    (the crashpoint-matrix pattern), so the registry can't grow
    untested;
  * registry validation (duplicate names, bad severities, unbounded
    attr keys are rejected at define time);
  * ring/recent filter semantics, persistence roundtrip across
    instances, torn-segment tolerance (the crash window serves the
    surviving prefix), stream backlog + (node, seq) dedup against a
    grafted peer echo;
  * tools/bench_trend.py --smoke and its regression exit code.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from minio_tpu.utils import eventlog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every registered event class -> a representative attr payload using
# exactly the declared attr keys. Adding a class to the registry
# without adding it here fails test_matrix_covers_registry.
EVENT_MATRIX = {
    "drive.suspect": {"drive": "/d/0", "set": 0},
    "drive.probation": {"drive": "/d/0", "set": 0},
    "drive.reconvict": {"drive": "/d/0", "set": 0},
    "drive.readmit": {"drive": "/d/0", "set": 0},
    "mrf.enqueue": {"queued": 3},
    "mrf.drain": {"healed": 2, "failed": 0},
    "admission.shed": {"reason": "staging"},
    "health.transition": {"kind": "drive", "target": "/d/0",
                          "state": "suspect", "event": "suspect"},
    "membership.generation": {"peer": "127.0.0.1:9001",
                              "generation": 42},
    "net.partition": {"rule": "both", "peers": "a|b"},
    "net.heal": {"peers": "a|b"},
    "registry.fork": {"epoch": 7, "forks": 1},
    "crashpoint.armed": {"point": "put.meta.before_rename", "nth": 1},
    "device.decline": {"stage": "boot", "reason": "no-device",
                       "detail": "RuntimeError: no backend"},
    "fsck.complete": {"findings": 1, "repaired": 1, "unrepaired": 0},
    "fsck.unrepaired": {"findings": 1},
    "rebalance.checkpoint": {"pool": 0, "objects": 10},
    "resync.checkpoint": {"target": "arn:x", "objects": 5},
    "slo.breach": {"objective": "read-availability", "window": "60s",
                   "burn": 14.2},
    "slo.clear": {"objective": "read-availability"},
    "incident.captured": {"trigger": "slo.breach",
                          "incident": "inc-1-001-slo-breach",
                          "events": 12},
    "qos.update": {"epoch": 3, "tenants": 2, "tiers": 1},
    "tenant.shed": {"tenant": "alice", "reason": "rate"},
    "notify.update": {"epoch": 2, "targets": 1},
    "notify.offline": {"target": "arn:minio:sqs::hook1:webhook"},
    "notify.redrive": {"target": "arn:minio:sqs::hook1:webhook",
                       "delivered": 3},
    "notify.drop": {"target": "arn:minio:sqs::hook1:webhook"},
}


def fresh() -> eventlog.EventJournal:
    return eventlog.EventJournal()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_matrix_covers_registry():
    """Every registered event class has a matrix emit and vice versa
    — and each matrix payload uses exactly the declared attr keys."""
    registered = set(eventlog.EVENTS)
    matrix = set(EVENT_MATRIX)
    assert registered - matrix == set(), \
        f"event classes without a matrix emit: {registered - matrix}"
    assert matrix - registered == set(), \
        f"matrix names unregistered classes: {matrix - registered}"
    for name, attrs in EVENT_MATRIX.items():
        assert set(attrs) == set(eventlog.EVENTS[name].attrs), name
    assert len(registered) >= 20


def test_every_matrix_class_emits():
    j = fresh()
    for name, attrs in sorted(EVENT_MATRIX.items()):
        e = j.emit(name, **attrs)
        assert e is not None and e["class"] == name
        assert e["sev"] in eventlog.SEVERITIES
        assert e["attrs"] == attrs
    assert j.seq == len(EVENT_MATRIX)


def test_define_rejects_bad_registrations():
    with pytest.raises(ValueError):
        eventlog.define("drive.suspect", "drive", "warn", (), "dup")
    with pytest.raises(ValueError):
        eventlog.define("x.bogus-sev", "x", "fatal", (), "bad sev")
    with pytest.raises(ValueError):
        eventlog.define("x.unbounded", "x", "info", ("bucket",),
                        "unbounded attr key")
    assert "x.bogus-sev" not in eventlog.EVENTS
    assert "x.unbounded" not in eventlog.EVENTS


def test_emit_unregistered_raises():
    j = fresh()
    with pytest.raises(ValueError):
        j.emit("no.such.class", a=1)


def test_sev_rank_orders_severities():
    ranks = [eventlog.sev_rank(s) for s in eventlog.SEVERITIES]
    assert ranks == sorted(ranks) and len(set(ranks)) == len(ranks)
    assert eventlog.sev_rank("unknown") == -1


def test_render_table_lists_every_class():
    table = eventlog.render_table()
    for name in eventlog.EVENTS:
        assert f"`{name}`" in table, name


def test_emit_once_dedupes_for_process_lifetime():
    first = eventlog.emit_once("device.decline", stage="unit-test",
                               reason="once")
    again = eventlog.emit_once("device.decline", stage="unit-test",
                               reason="once")
    other = eventlog.emit_once("device.decline", stage="unit-test",
                               reason="other")
    assert first is not None and again is None
    assert other is not None


# ---------------------------------------------------------------------------
# ring + filters
# ---------------------------------------------------------------------------

def test_recent_filters_and_since_seq():
    j = fresh()
    j.emit("drive.suspect", drive="/d/0", set=0)
    j.emit("net.partition", rule="both", peers="a|b")
    j.emit("registry.fork", epoch=1, forks=1)
    assert [e["class"] for e in j.recent()] == [
        "drive.suspect", "net.partition", "registry.fork"]
    assert [e["class"] for e in j.recent(classes={"net.partition"})] \
        == ["net.partition"]
    assert [e["class"] for e in j.recent(subsystems={"drive"})] == \
        ["drive.suspect"]
    crit = eventlog.sev_rank("crit")
    assert [e["class"] for e in j.recent(min_sev=crit)] == \
        ["registry.fork"]
    assert [e["class"] for e in j.recent(since_seq=2)] == \
        ["registry.fork"]
    assert len(j.recent(1)) == 1


def test_emit_respects_kill_switch(monkeypatch):
    j = fresh()
    monkeypatch.setenv("MINIO_TPU_EVENTLOG", "off")
    assert j.emit("drive.suspect", drive="/d/0", set=0) is None
    assert j.dropped_total == 1 and j.recent() == []
    monkeypatch.setenv("MINIO_TPU_EVENTLOG", "on")
    assert j.emit("drive.suspect", drive="/d/0", set=0) is not None


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def test_persistence_roundtrip(tmp_path):
    d = str(tmp_path / "eventlog")
    j = fresh()
    j.attach(d, node="n1", segment_events=4, flush_s=30.0)
    for i in range(6):
        j.emit("mrf.enqueue", queued=i)
    j.close()
    segs = [n for n in os.listdir(d) if n.startswith("seg-")]
    assert segs, "close() must persist the pending tail"

    j2 = fresh()
    j2.attach(d, node="n1", segment_events=4, flush_s=30.0)
    replayed = j2.recent()
    assert [e["attrs"]["queued"] for e in replayed] == list(range(6))
    assert j2.seq == 6, "seq must advance past persisted entries"
    # new emits continue the sequence — no seq reuse after restart
    e = j2.emit("mrf.drain", healed=1, failed=0)
    assert e["seq"] == 7
    j2.close()


def test_torn_segment_serves_surviving_prefix(tmp_path):
    d = str(tmp_path / "eventlog")
    j = fresh()
    # big segment_events + long flush_s: segment boundaries are set by
    # the explicit flush() calls, not the background flusher
    j.attach(d, node="n1", segment_events=100, flush_s=30.0)
    j.emit("mrf.enqueue", queued=0)
    j.emit("mrf.enqueue", queued=1)
    j.flush()
    j.emit("mrf.enqueue", queued=2)
    j.emit("mrf.enqueue", queued=3)
    j.close()
    segs = sorted(n for n in os.listdir(d) if n.startswith("seg-"))
    assert len(segs) >= 2
    # tear the LAST segment mid-write (the crash window)
    with open(os.path.join(d, segs[-1]), "wb") as f:
        f.write(b'{"v": 1, "events": [{"cl')
    j2 = fresh()
    j2.attach(d, node="n1")
    got = [e["attrs"]["queued"] for e in j2.recent()]
    assert got == [0, 1], \
        f"torn tail must not hide the surviving prefix: {got}"
    j2.close()


def test_segment_retention_prunes_oldest(tmp_path):
    d = str(tmp_path / "eventlog")
    j = fresh()
    j.attach(d, node="n1", segment_events=100, flush_s=30.0,
             keep_segments=3)
    for i in range(8):
        j.emit("mrf.enqueue", queued=i)
        j.flush()
    j.close()
    segs = [n for n in os.listdir(d) if n.startswith("seg-")]
    assert len(segs) <= 3


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def _lines(chunks) -> list:
    out = []
    for c in chunks:
        if c.strip():
            out.append(json.loads(c))
    return out


def test_stream_backlog_then_idle_end():
    j = fresh()
    for i in range(3):
        j.emit("mrf.enqueue", queued=i)
    got = _lines(j.stream(idle_timeout=0.2, backlog=10))
    assert [e["attrs"]["queued"] for e in got] == [0, 1, 2]


def test_stream_max_entries_cuts_live_feed():
    j = fresh()
    done: list = []

    def consume():
        done.extend(_lines(j.stream(max_entries=2, idle_timeout=5.0,
                                    follow=True)))

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while j.hub.subscriber_count == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    j.emit("mrf.enqueue", queued=1)
    j.emit("mrf.enqueue", queued=2)
    t.join(timeout=10)
    assert not t.is_alive() and len(done) == 2


def test_stream_dedupes_peer_echo_by_node_seq():
    """In-process multi-node clusters share ONE journal: a peer graft
    echoes local entries back, and the stream must drop the echo by
    (node, seq) identity."""
    j = fresh()
    j.node = "n1"
    local = j.emit("net.heal", peers="a|b")
    echo = dict(local)

    def peer_iter():
        yield echo
        yield {"ts": echo["ts"], "class": "net.partition",
               "sev": "error", "sub": "net", "node": "n2",
               "attrs": {"rule": "both", "peers": "a|b"}, "seq": 1}

    got = _lines(j.stream(idle_timeout=0.5, backlog=10,
                          peer_subs=lambda: [peer_iter()]))
    keys = [(e["node"], e["class"]) for e in got]
    assert keys.count(("n1", "net.heal")) == 1, keys
    assert ("n2", "net.partition") in keys, keys


def test_stream_filters_apply_to_peer_entries():
    j = fresh()
    j.node = "n1"

    def peer_iter():
        yield {"ts": 1.0, "class": "drive.suspect", "sev": "warn",
               "sub": "drive", "node": "n2",
               "attrs": {"drive": "/d/1", "set": 0}, "seq": 1}
        yield {"ts": 1.1, "class": "net.heal", "sev": "info",
               "sub": "net", "node": "n2", "attrs": {"peers": "a|b"},
               "seq": 2}

    got = _lines(j.stream(idle_timeout=0.5, subsystems={"drive"},
                          peer_subs=lambda: [peer_iter()]))
    assert [e["class"] for e in got] == ["drive.suspect"]


# ---------------------------------------------------------------------------
# bench_trend (satellite)
# ---------------------------------------------------------------------------

def _trend(*argv) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_trend.py"),
         *argv], capture_output=True, text=True, timeout=60)


def test_bench_trend_smoke():
    r = _trend("--smoke")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok" in r.stdout


def test_bench_trend_gates_on_regression(tmp_path):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(
        {"n": 1, "parsed": {"value": 10.0, "put_p99_ms": 5.0}}))
    new.write_text(json.dumps(
        {"n": 2, "parsed": {"value": 5.0, "put_p99_ms": 5.0}}))
    r = _trend(str(old), str(new), "--threshold", "5")
    assert r.returncode == 1 and "REGRESSED" in r.stdout
    # within threshold -> passes
    r2 = _trend(str(old), str(new), "--threshold", "60")
    assert r2.returncode == 0, r2.stdout
