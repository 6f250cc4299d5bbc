"""tools/check — the project-invariant linter: every rule provably
fires on a seeded bad fixture, stays quiet on the good twin, honors
suppressions, and the runner exits 0 on the committed tree (the smoke
pin that keeps the CI gate from silently rotting)."""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from check import knobtable, rules_ast, rules_project, run as check_run  # noqa: E402
from check.core import Source  # noqa: E402

from minio_tpu.utils import knobs  # noqa: E402


def _src(rel: str, text: str) -> Source:
    return Source("<fixture>", rel, text)


# ---------------------------------------------------------------------------
# rule: lock-blocking
# ---------------------------------------------------------------------------

BAD_LOCK = '''
import os, time, json, shutil
class M:
    def hot(self):
        with self._mu:
            time.sleep(0.1)
    def io(self):
        with self._cond:
            open("/tmp/x")
            os.replace("a", "b")
            shutil.rmtree("d")
    def layer(self):
        with self._lock:
            self.obj.put_object("b", "k", b"")
    def dev(self):
        with self._mu:
            self.codec.encode_and_hash_batch(None, None)
    def launch(self):
        with self._mu:
            self.codec._launch(None, None, (), (), None)
    def fut(self):
        with self._mu:
            self.f.result()
    def evwait(self):
        with self._mu:
            self.event.wait(1)
    def _write_meta(self):
        json.dump({}, open("m", "w"))
    def indirect(self):
        with self._mu:
            self._write_meta()
'''

GOOD_LOCK = '''
import time
class M:
    def ok(self):
        with self._mu:
            self.x = 1
        time.sleep(0.1)
    def condwait(self):
        with self._cond:
            self._cond.wait(0.2)
    def kick(self):
        with self._mu:
            self._kick.wait(0.1)
    def later(self):
        with self._mu:
            def cb():
                open("/tmp/x")
            self.cb = cb
'''


def test_lock_rule_fires_on_every_banned_class():
    vs = rules_ast.check_lock_blocking(
        [_src("minio_tpu/object/metacache.py", BAD_LOCK)])
    msgs = "\n".join(v.message for v in vs)
    assert "time.sleep" in msgs
    assert "open()" in msgs
    assert "os.replace" in msgs
    assert "shutil.rmtree" in msgs
    assert ".put_object()" in msgs
    assert ".encode_and_hash_batch()" in msgs
    assert "._launch()" in msgs
    assert ".result()" in msgs
    assert ".wait()" in msgs
    assert "_write_meta() which performs" in msgs      # helper indirection
    assert len(vs) >= 10


def test_lock_rule_quiet_on_good_and_non_hot_modules():
    assert rules_ast.check_lock_blocking(
        [_src("minio_tpu/object/metacache.py", GOOD_LOCK)]) == []
    # the same bad code outside the designated hot list is not flagged
    assert rules_ast.check_lock_blocking(
        [_src("minio_tpu/features/events.py", BAD_LOCK)]) == []


def test_lock_rule_flags_manual_acquire():
    """`x.acquire(); try/finally` holds the lock invisibly to the
    with-body scan — the spelling itself is flagged, and a deliberate
    site argues its suppression inline."""
    code = ('class M:\n'
            '    def manual(self):\n'
            '        self._mu.acquire()\n'
            '        try:\n'
            '            pass\n'
            '        finally:\n'
            '            self._mu.release()\n')
    vs = rules_ast.check_lock_blocking(
        [_src("minio_tpu/object/metacache.py", code)])
    assert len(vs) == 1 and "manual self._mu.acquire()" in vs[0].message
    ok = code.replace(
        "        self._mu.acquire()\n",
        "        # check: allow(lock-blocking) argued reason\n"
        "        self._mu.acquire()\n")
    # suppression applies via the runner's filter; the raw rule still
    # reports — mirror run_checks' filtering here
    from check.core import filter_allowed
    src = _src("minio_tpu/object/metacache.py", ok)
    assert filter_allowed(src, rules_ast.check_lock_blocking([src])) == []


def test_lock_rule_suppression_on_with_line():
    code = ('import time\n'
            'class M:\n'
            '    def hot(self):\n'
            '        with self._mu:  '
            '# check: allow(lock-blocking) argued reason here\n'
            '            time.sleep(0.1)\n')
    assert rules_ast.check_lock_blocking(
        [_src("minio_tpu/object/metacache.py", code)]) == []


# ---------------------------------------------------------------------------
# rule: metrics-hygiene
# ---------------------------------------------------------------------------

BAD_METRICS = '''
from ..utils import telemetry
def hot_path():
    telemetry.REGISTRY.counter("minio_tpu_per_call_total", "h").inc()
C = telemetry.REGISTRY.counter("minio_tpu_badname", "h")
G = telemetry.REGISTRY.gauge("minio_tpu_twice_total", "h")
H = telemetry.REGISTRY.counter("minio_tpu_twice_total", "other help")
def a():
    C.inc(verb="x")
def b():
    C.inc(lane="y")
'''

GOOD_METRICS = '''
from ..utils import telemetry
C = telemetry.REGISTRY.counter("minio_tpu_good_total", "h")
_F = None
def _resolver_counter():
    global _F
    if _F is None:
        _F = telemetry.REGISTRY.counter("minio_tpu_memo_total", "h")
    return _F
def _collect_things():
    telemetry.REGISTRY.gauge("minio_tpu_live", "h").set(1)
class X:
    def __init__(self):
        self.h = telemetry.REGISTRY.histogram("minio_tpu_lat_seconds", "h")
def use():
    C.inc(verb="a")
def use2():
    C.inc(2, verb="b")
'''


def test_metrics_rule_fires():
    vs = rules_ast.check_metrics_hygiene(
        [_src("minio_tpu/object/zz.py", BAD_METRICS)])
    msgs = "\n".join(v.message for v in vs)
    assert "resolved inside hot_path()" in msgs
    assert "must end in `_total`" in msgs
    assert "ends in `_total` but is not a Counter" in msgs
    assert "one family, one kind" in msgs or "different help" in msgs
    assert "label sets must be consistent" in msgs


def test_metrics_rule_quiet_on_good():
    assert rules_ast.check_metrics_hygiene(
        [_src("minio_tpu/object/zz.py", GOOD_METRICS)]) == []


# ---------------------------------------------------------------------------
# rule: knob-env
# ---------------------------------------------------------------------------

BAD_KNOBS = '''
import os
A = os.environ.get("MINIO_TPU_SOMETHING", "1")
B = os.getenv("MINIO_TPU_OTHER")
C = "MINIO_TPU_FLAG" in os.environ
D = os.environ["MINIO_TPU_SUB"]
from ..utils import knobs
E = knobs.get_int("MINIO_TPU_NOT_REGISTERED")
'''

GOOD_KNOBS = '''
import os
from ..utils import knobs
A = knobs.get_int("MINIO_TPU_SCHED_MAX_BATCH")
B = os.environ.get("JAX_PLATFORMS", "")      # non-knob env is fine
'''


def test_knob_rule_fires_on_every_raw_read_form():
    vs = rules_ast.check_knob_env(
        [_src("minio_tpu/object/zz.py", BAD_KNOBS)], set(knobs.KNOBS))
    assert len(vs) == 5
    msgs = "\n".join(v.message for v in vs)
    assert "MINIO_TPU_SOMETHING" in msgs
    assert "MINIO_TPU_NOT_REGISTERED" in msgs


def test_knob_rule_quiet_on_good_and_inside_knobs_py():
    assert rules_ast.check_knob_env(
        [_src("minio_tpu/object/zz.py", GOOD_KNOBS)],
        set(knobs.KNOBS)) == []
    # knobs.py itself is the sanctioned home of RAW reads — only the
    # unregistered-getter-name check still applies there
    vs = rules_ast.check_knob_env(
        [_src("minio_tpu/utils/knobs.py", BAD_KNOBS)],
        set(knobs.KNOBS))
    assert len(vs) == 1 and "MINIO_TPU_NOT_REGISTERED" in vs[0].message


# ---------------------------------------------------------------------------
# rule: hook-coverage
# ---------------------------------------------------------------------------

ENGINE_OK = '''
class ErasureObjects:
    def put_object(self, b, k, r):
        return self._put(b, k)
    def _put(self, b, k):
        self._notify_degraded(b, k, "")
        self._notify_namespace(b, k)
    def update_object_metadata(self, b, k):
        self._notify_degraded(b, k, "")
        self._notify_namespace(b, k)
    def transition_object(self, b, k):
        self._notify_degraded(b, k, "")
        self._notify_namespace(b, k)
    def put_stub_version(self, b, k):
        self._notify_degraded(b, k, "")
        self._notify_namespace(b, k)
    def delete_object(self, b, k):
        self._flag_degraded_delete(b, k, "", [])
        self._notify_namespace(b, k)
    def put_delete_marker(self, b, k):
        self._flag_degraded_delete(b, k, "", [])
        self._notify_namespace(b, k)
    def delete_objects(self, b, ks):
        self._flag_degraded_delete(b, "", "", [])
        self._notify_namespace(b, "")
'''

MULTIPART_OK = '''
class MultipartMixin(ErasureObjects):
    def complete_multipart_upload(self, b, k, u, parts):
        self._notify_degraded(b, k, "")
        self._notify_namespace(b, k)
'''


def test_hook_rule_green_on_complete_fixture_and_fires_on_gap():
    ok = [_src("minio_tpu/object/engine.py", ENGINE_OK),
          _src("minio_tpu/object/multipart.py", MULTIPART_OK)]
    assert rules_project.check_hook_coverage(ok) == []
    # drop the namespace hook from delete_object -> flagged
    broken = ENGINE_OK.replace(
        '    def delete_object(self, b, k):\n'
        '        self._flag_degraded_delete(b, k, "", [])\n'
        '        self._notify_namespace(b, k)\n',
        '    def delete_object(self, b, k):\n'
        '        self._flag_degraded_delete(b, k, "", [])\n')
    vs = rules_project.check_hook_coverage(
        [_src("minio_tpu/object/engine.py", broken),
         _src("minio_tpu/object/multipart.py", MULTIPART_OK)])
    assert any("delete_object() never fires _notify_namespace" in v.message
               for v in vs)
    # drop the degraded hook from put_object's helper -> flagged
    broken2 = ENGINE_OK.replace(
        '    def _put(self, b, k):\n'
        '        self._notify_degraded(b, k, "")\n',
        '    def _put(self, b, k):\n')
    vs2 = rules_project.check_hook_coverage(
        [_src("minio_tpu/object/engine.py", broken2),
         _src("minio_tpu/object/multipart.py", MULTIPART_OK)])
    assert any("put_object() never fires on_degraded_write" in v.message
               for v in vs2)


def test_hook_rule_green_on_real_tree():
    from check.core import load_sources
    assert rules_project.check_hook_coverage(load_sources()) == []


# ---------------------------------------------------------------------------
# rule: error-map
# ---------------------------------------------------------------------------

API_ERRORS_FIX = '''
class ObjectApiError(Exception):
    pass
class Mapped(ObjectApiError):
    pass
class Internal(ObjectApiError):
    pass
class Orphan(ObjectApiError):
    pass
'''

S3_ERRORS_FIX = '''
ERROR_TABLE: dict = {
    "MappedCode": (400, "m"),
}
INTERNAL_ONLY = (oerr.Internal,)
def api_error_from(exc):
    mapping = [
        (oerr.Mapped, "MappedCode"),
    ]
'''


def test_error_rule_fires_on_orphan_and_bad_code():
    vs = rules_project.check_error_map(
        [_src("minio_tpu/object/api_errors.py", API_ERRORS_FIX),
         _src("minio_tpu/s3/s3errors.py", S3_ERRORS_FIX)])
    assert any("Orphan has no api_error_from mapping" in v.message
               for v in vs)
    assert not any("Mapped has no" in v.message for v in vs)
    assert not any("Internal has no" in v.message for v in vs)
    # a mapping to a code missing from ERROR_TABLE is flagged
    bad = S3_ERRORS_FIX.replace('"MappedCode")', '"GhostCode")')
    vs2 = rules_project.check_error_map(
        [_src("minio_tpu/object/api_errors.py", API_ERRORS_FIX),
         _src("minio_tpu/s3/s3errors.py", bad)])
    assert any("GhostCode" in v.message for v in vs2)
    # a literal S3Error("Unknown") anywhere is flagged
    handler = 'def h():\n    raise S3Error("NoSuchCode")\n'
    vs3 = rules_project.check_error_map(
        [_src("minio_tpu/object/api_errors.py", API_ERRORS_FIX),
         _src("minio_tpu/s3/s3errors.py", S3_ERRORS_FIX),
         _src("minio_tpu/s3/handlers.py", handler)])
    assert any("NoSuchCode" in v.message for v in vs3)


def test_error_rule_green_on_real_tree():
    from check.core import load_sources
    assert rules_project.check_error_map(load_sources()) == []


# ---------------------------------------------------------------------------
# the knob registry itself
# ---------------------------------------------------------------------------

def test_knob_typed_getters_and_fallbacks(monkeypatch):
    assert knobs.get_int("MINIO_TPU_SCHED_MAX_BATCH") == 32
    monkeypatch.setenv("MINIO_TPU_SCHED_MAX_BATCH", "64")
    assert knobs.get_int("MINIO_TPU_SCHED_MAX_BATCH") == 64
    monkeypatch.setenv("MINIO_TPU_SCHED_MAX_BATCH", "garbage")
    assert knobs.get_int("MINIO_TPU_SCHED_MAX_BATCH") == 32   # fallback
    monkeypatch.setenv("MINIO_TPU_METACACHE", "off")
    assert knobs.get_bool("MINIO_TPU_METACACHE") is False
    monkeypatch.setenv("MINIO_TPU_METACACHE", "weird")
    assert knobs.get_bool("MINIO_TPU_METACACHE") is True      # default
    with pytest.raises(KeyError):
        knobs.get_int("MINIO_TPU_NOT_A_KNOB")
    with pytest.raises(KeyError):
        knobs.get_raw("MINIO_TPU_NOT_A_KNOB")


def test_knob_table_covers_registry_and_readme_is_fresh():
    table = knobs.render_table()
    for name in knobs.KNOBS:
        assert f"`{name}`" in table
    # committed README must match the registry (the drift gate)
    assert knobtable.check_drift() == []


def test_knob_drift_detected(tmp_path, monkeypatch):
    readme = tmp_path / "README.md"
    readme.write_text("# x\n\nno markers here\n")
    monkeypatch.setattr(knobtable, "README", str(readme))
    vs = knobtable.check_drift()
    assert vs and "markers missing" in vs[0].message
    mod = knobtable.load_knobs()
    readme.write_text(
        f"# x\n\n{mod.TABLE_BEGIN}\nstale table\n{mod.TABLE_END}\n")
    vs2 = knobtable.check_drift()
    assert vs2 and "drifted" in vs2[0].message


# ---------------------------------------------------------------------------
# the runner (CI gate)
# ---------------------------------------------------------------------------

def test_runner_exits_zero_on_tree(capsys, tmp_path):
    """THE smoke pin: the committed tree is lint-clean, so the gate
    can't rot into a permanently-red (ignored) state."""
    report = tmp_path / "check.json"
    assert check_run.main(["--json", str(report)]) == 0
    doc = json.loads(report.read_text())
    assert doc["gate"] == "pass"
    assert doc["violations"] == []
    assert doc["files_scanned"] > 100


def test_runner_single_rule_and_json_stdout(capsys):
    assert check_run.main(["--rule", "error-map", "--json", "-"]) == 0
    out = capsys.readouterr().out
    doc = json.loads(out[:out.rindex("}") + 1])
    assert doc["gate"] == "pass"


def test_replication_chain_rule():
    """The hook-coverage rule proves every mutation verb reaches the
    replication queue: feed -> attach_replication ->
    plane.on_namespace_change -> cluster wiring. Breaking any link
    fires; the real tree is green (covered by
    test_hook_rule_green_on_real_tree)."""
    ok_engine = [_src("minio_tpu/object/engine.py", ENGINE_OK),
                 _src("minio_tpu/object/multipart.py", MULTIPART_OK)]
    ss_ok = '''
class ErasureServerSets:
    def attach_replication(self, plane):
        self.replication = plane
        self.register_namespace_listener(plane.on_namespace_change)
'''
    plane_ok = '''
class ReplicationPlane:
    def on_namespace_change(self, bucket, key):
        pass
'''
    cluster_ok = '''
def boot(layer, plane):
    layer.attach_replication(plane)
'''
    full = ok_engine + [
        _src("minio_tpu/object/server_sets.py", ss_ok),
        _src("minio_tpu/replicate/plane.py", plane_ok),
        _src("minio_tpu/cluster.py", cluster_ok)]
    assert rules_project.check_hook_coverage(full) == []

    # attach loses its register call -> flagged
    vs = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", '''
class ErasureServerSets:
    def attach_replication(self, plane):
        self.replication = plane
'''),
        _src("minio_tpu/replicate/plane.py", plane_ok),
        _src("minio_tpu/cluster.py", cluster_ok)])
    assert any("register_namespace_listener" in v.message for v in vs)

    # the plane loses its listener method -> flagged
    vs2 = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", ss_ok),
        _src("minio_tpu/replicate/plane.py",
             "class ReplicationPlane:\n    pass\n"),
        _src("minio_tpu/cluster.py", cluster_ok)])
    assert any("on_namespace_change() missing" in v.message for v in vs2)

    # cluster boot forgets to attach -> flagged
    vs3 = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", ss_ok),
        _src("minio_tpu/replicate/plane.py", plane_ok),
        _src("minio_tpu/cluster.py", "def boot(layer):\n    pass\n")])
    assert any("never calls attach_replication" in v.message
               for v in vs3)


def test_notify_chain_rule():
    """The hook-coverage rule proves every mutation verb reaches
    bucket event notification: feed -> attach_notifications ->
    NotificationPlane.on_namespace_change -> cluster wiring. Breaking
    any link fires; absent the notify plane module the chain is out of
    scope (so fixture trees above stay green)."""
    ok_engine = [_src("minio_tpu/object/engine.py", ENGINE_OK),
                 _src("minio_tpu/object/multipart.py", MULTIPART_OK)]
    ss_ok = '''
class ErasureServerSets:
    def attach_replication(self, plane):
        self.replication = plane
        self.register_namespace_listener(plane.on_namespace_change)
    def attach_notifications(self, plane):
        self.notifications = plane
        self.register_namespace_listener(plane.on_namespace_change)
'''
    repl_plane_ok = '''
class ReplicationPlane:
    def on_namespace_change(self, bucket, key):
        pass
'''
    notify_plane_ok = '''
class NotificationPlane:
    def on_namespace_change(self, bucket, key):
        pass
'''
    cluster_ok = '''
def boot(layer, repl, notify):
    layer.attach_replication(repl)
    layer.attach_notifications(notify)
'''
    full = ok_engine + [
        _src("minio_tpu/object/server_sets.py", ss_ok),
        _src("minio_tpu/replicate/plane.py", repl_plane_ok),
        _src("minio_tpu/notify/plane.py", notify_plane_ok),
        _src("minio_tpu/cluster.py", cluster_ok)]
    assert rules_project.check_hook_coverage(full) == []

    # attach_notifications loses its register call -> flagged
    vs = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", '''
class ErasureServerSets:
    def attach_replication(self, plane):
        self.replication = plane
        self.register_namespace_listener(plane.on_namespace_change)
    def attach_notifications(self, plane):
        self.notifications = plane
'''),
        _src("minio_tpu/replicate/plane.py", repl_plane_ok),
        _src("minio_tpu/notify/plane.py", notify_plane_ok),
        _src("minio_tpu/cluster.py", cluster_ok)])
    assert any("attach_notifications() never calls "
               "register_namespace_listener" in v.message for v in vs)

    # attach_notifications gone entirely -> flagged
    vs1 = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", '''
class ErasureServerSets:
    def attach_replication(self, plane):
        self.replication = plane
        self.register_namespace_listener(plane.on_namespace_change)
'''),
        _src("minio_tpu/replicate/plane.py", repl_plane_ok),
        _src("minio_tpu/notify/plane.py", notify_plane_ok),
        _src("minio_tpu/cluster.py", cluster_ok)])
    assert any("attach_notifications() missing" in v.message
               for v in vs1)

    # the plane loses its listener method -> flagged
    vs2 = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", ss_ok),
        _src("minio_tpu/replicate/plane.py", repl_plane_ok),
        _src("minio_tpu/notify/plane.py",
             "class NotificationPlane:\n    pass\n"),
        _src("minio_tpu/cluster.py", cluster_ok)])
    assert any("NotificationPlane.on_namespace_change() missing"
               in v.message for v in vs2)

    # cluster boot forgets to attach -> flagged
    vs3 = rules_project.check_hook_coverage(ok_engine + [
        _src("minio_tpu/object/server_sets.py", ss_ok),
        _src("minio_tpu/replicate/plane.py", repl_plane_ok),
        _src("minio_tpu/notify/plane.py", notify_plane_ok),
        _src("minio_tpu/cluster.py", '''
def boot(layer, repl):
    layer.attach_replication(repl)
''')])
    assert any("never calls attach_notifications" in v.message
               for v in vs3)


# ---------------------------------------------------------------------------
# rule: admission
# ---------------------------------------------------------------------------

BAD_SHED = '''
from minio_tpu.s3.s3errors import S3Error
from minio_tpu.utils import telemetry
def shed(self, ctx):
    telemetry.REGISTRY.counter(
        "minio_tpu_requests_shed_total",
        "Requests shed").inc(reason="ad-hoc")
    raise S3Error("SlowDown", "go away")
'''


def test_admission_rule_fires_on_stray_shed():
    """A SlowDown decision or a requests_shed_total reference outside
    the AdmissionController module is an error (migrating the
    handlers' original shed window is what proved this fires)."""
    vs = rules_ast.check_admission(
        [_src("minio_tpu/s3/handlers.py", BAD_SHED)])
    msgs = "\n".join(v.message for v in vs)
    assert "S3Error(\"SlowDown\")" in msgs
    assert "requests_shed_total" in msgs
    assert len(vs) == 2


def test_admission_rule_quiet_in_controller_and_on_tree():
    # the controller module itself is the ONE exempt home
    assert rules_ast.check_admission(
        [_src("minio_tpu/s3/edge/admission.py", BAD_SHED)]) == []
    # the committed tree is clean: the handlers' shed window migrated
    from check.core import load_sources
    assert rules_ast.check_admission(load_sources()) == []


BAD_PROBE = '''
from minio_tpu.utils.bandwidth import TokenBucket
bucket = TokenBucket(10.0, 10.0)
def maybe_throttle(ctx):
    if bucket.try_take(1):
        return
    wait = bucket.peek(ctx.content_length)
    ctx.respond(503, retry_after=wait)
'''


def test_admission_rule_fires_on_stray_budget_probe():
    """A TokenBucket admission probe (try_take / peek with an amount)
    outside the admission/QoS plane is a private refusal path in the
    making — the rule catches the probe itself, before anyone wires
    it to a 503 (ISSUE 19 satellite)."""
    vs = rules_ast.check_admission(
        [_src("minio_tpu/object/engine.py", BAD_PROBE)])
    msgs = "\n".join(v.message for v in vs)
    assert "budget probe outside the admission/QoS plane" in msgs
    assert len(vs) == 2                # try_take AND peek both flagged


def test_admission_rule_budget_probe_quiet_in_qos_plane():
    # the three modules that ARE the plane may probe freely
    for home in ("minio_tpu/s3/edge/admission.py",
                 "minio_tpu/s3/qos.py",
                 "minio_tpu/utils/bandwidth.py"):
        assert rules_ast.check_admission([_src(home, BAD_PROBE)]) == []
    # zero-argument .peek() calls (the s3select parser's lookahead)
    # are NOT budget probes and stay quiet anywhere
    lookahead = "def parse(tok):\n    return tok.peek()\n"
    assert rules_ast.check_admission(
        [_src("minio_tpu/s3select/sql.py", lookahead)]) == []


# ---------------------------------------------------------------------------
# rule: metrics-hygiene / label cardinality (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

BAD_CARDINALITY = '''
from ...utils import telemetry
_OPS = telemetry.REGISTRY.counter("minio_tpu_zz_ops_total", "ops")
def hot(self, bucket, key, oi):
    _OPS.inc(bucket=bucket)
    _OPS.inc(verb=key)
    telemetry.REGISTRY.histogram(
        "minio_tpu_zz_seconds", "lat").observe(0.1, target=oi.name)
'''

GOOD_CARDINALITY = '''
from ...utils import telemetry
_OPS = telemetry.REGISTRY.counter("minio_tpu_zz_ops_total", "ops")
def hot(self, verb, reason):
    _OPS.inc(verb=verb)
    _OPS.inc(reason=reason)
    _OPS.inc(stage="compute")
    _OPS.inc(path="fallback")        # constant value: bounded
'''


def test_label_cardinality_fires_in_hot_modules():
    """Raw bucket/object/key names as metric label values in hot-path
    modules are unbounded cardinality: the key form (bucket=...), the
    value form (verb=key) and the attribute form (target=oi.name) all
    fire."""
    vs = rules_ast.check_label_cardinality(
        [_src("minio_tpu/object/engine.py", BAD_CARDINALITY)])
    msgs = "\n".join(v.message for v in vs)
    assert len(vs) == 3, vs
    assert "request-derived 'bucket'" in msgs
    assert "`key`" in msgs
    assert "`oi.name`" in msgs


ALIAS_CARDINALITY = '''
from ...utils import telemetry
g = telemetry.REGISTRY.gauge
def hot(self, bucket):
    g("minio_tpu_zz_depth", "d").set(1, bucket=bucket)
'''


def test_label_cardinality_sees_aliased_getters():
    """`g = REGISTRY.gauge; g("n").set(..., bucket=b)` must fire too —
    the attribute-only scan's blind spot (review finding)."""
    vs = rules_ast.check_label_cardinality(
        [_src("minio_tpu/object/engine.py", ALIAS_CARDINALITY)])
    assert len(vs) == 1 and "request-derived 'bucket'" in vs[0].message


def test_label_cardinality_quiet_on_bounded_and_cold_modules():
    # bounded vocabularies (verb/reason/stage + constants) stay clean
    assert rules_ast.check_label_cardinality(
        [_src("minio_tpu/object/engine.py", GOOD_CARDINALITY)]) == []
    # the same bad code OUTSIDE a hot-path module is tolerated (the
    # admin handler's per-bucket usage gauges refresh at exposition
    # time and clear() on every scrape)
    assert rules_ast.check_label_cardinality(
        [_src("minio_tpu/s3/admin.py", BAD_CARDINALITY)]) == []
    # the committed tree argues every hot-path label bounded
    from check.core import load_sources
    assert rules_ast.check_label_cardinality(load_sources()) == []


# ---------------------------------------------------------------------------
# README metrics table (generated; drift gated)
# ---------------------------------------------------------------------------

def test_metrics_table_covers_registry_and_readme_is_fresh():
    from check import metricstable
    fams = metricstable.collect_families()
    # the core families the telemetry plane registers must be seen by
    # the static scan (registration sites, not a live render)
    for fam in ("minio_tpu_http_requests_duration_seconds",
                "minio_tpu_device_dispatch_seconds",
                "minio_tpu_requests_shed_total",
                "minio_tpu_cluster_scrape_failed_total",
                "minio_tpu_edge_loop_lag_seconds",
                # registered through getter ALIASES (g = REGISTRY.gauge)
                # — the attribute-only scan's blind spot, found in
                # review: the table must see these too
                "minio_tpu_edge_pool_busy",
                "minio_disks_online"):
        assert fam in fams, fam
    table = metricstable.render_table()
    for fam in fams:
        assert fam in table
    # committed README is fresh (the gate would fail otherwise)
    assert metricstable.check_drift() == []


def test_metrics_table_drift_detected(monkeypatch, tmp_path):
    from check import metricstable
    stale = tmp_path / "README.md"
    with open(metricstable.README, encoding="utf-8") as f:
        text = f.read()
    stale.write_text(text.replace(
        "| counter |", "| gauge |", 1), encoding="utf-8")
    monkeypatch.setattr(metricstable, "README", str(stale))
    vs = metricstable.check_drift()
    assert vs and "drifted" in vs[0].message


# ---------------------------------------------------------------------------
# rule: crashpoint
# ---------------------------------------------------------------------------

BAD_COMMIT = '''
class Store:
    def commit(self, d):
        # write + rename across >= 2 paths, no crashpoint declared
        d.write_all("v", "xl.meta", b"m")
        d.rename_data("tmp", "t", "dd", "b", "o")

    def save_everywhere(self, pools, payload):
        for z in pools:
            z.put_object(".minio.sys", "doc.json", payload)
'''

GOOD_COMMIT = '''
from ..utils import crashpoint

class Store:
    def commit(self, d):
        d.write_all("v", "xl.meta", b"m")
        crashpoint.hit("put.meta.before_rename")
        d.rename_data("tmp", "t", "dd", "b", "o")

    def save_everywhere(self, pools, payload):
        for z in pools:
            crashpoint.hit("topology.save.pool")
            z.put_object(".minio.sys", "doc.json", payload)

    def single_write(self, d):
        d.write_all("v", "doc.json", b"x")      # one path: no window

    def read_side(self, d):
        return d.read_all("v", "doc.json")
'''

BAD_HIT_NAMES = '''
from ..utils import crashpoint

def f(name):
    crashpoint.hit("not.a.registered.point")
    crashpoint.hit(name)
'''


def _crash_registered():
    from check import crashtable
    return set(crashtable.load_crashpoints().CRASHPOINTS)


def test_crashpoint_rule_fires_on_uncovered_commit_windows():
    src = _src("minio_tpu/object/topology.py", BAD_COMMIT)
    vs = rules_project.check_crashpoint([src], _crash_registered())
    msgs = [v.message for v in vs]
    assert len(vs) == 2
    assert any("commit" in m and "write" in m for m in msgs)
    assert any("loop" in m or "persistence" in m for m in msgs)


def test_crashpoint_rule_quiet_on_declared_and_cold_modules():
    good = _src("minio_tpu/object/topology.py", GOOD_COMMIT)
    assert rules_project.check_crashpoint([good],
                                          _crash_registered()) == []
    # same bad shape OUTSIDE the designated commit modules: quiet
    cold = _src("minio_tpu/s3/handlers.py", BAD_COMMIT)
    assert rules_project.check_crashpoint([cold],
                                          _crash_registered()) == []


def test_crashpoint_rule_flags_bad_hit_names_everywhere():
    src = _src("minio_tpu/s3/handlers.py", BAD_HIT_NAMES)
    vs = rules_project.check_crashpoint([src], _crash_registered())
    assert len(vs) == 2
    assert any("unregistered" in v.message for v in vs)
    assert any("constant" in v.message for v in vs)


def test_crashpoint_rule_suppression():
    suppressed = BAD_COMMIT.replace(
        "    def commit(self, d):",
        "    # check: allow(crashpoint) two-phase handled by caller\n"
        "    def commit(self, d):")
    src = _src("minio_tpu/object/topology.py", suppressed)
    from check.core import filter_allowed
    vs = filter_allowed(src, rules_project.check_crashpoint(
        [src], _crash_registered()))
    assert len(vs) == 1          # only save_everywhere still flagged


def test_crashpoint_rule_green_on_real_tree():
    from check.core import load_sources, filter_allowed
    sources = load_sources()
    by_rel = {s.rel: s for s in sources}
    vs = rules_project.check_crashpoint(sources, _crash_registered())
    out = []
    for v in vs:
        src = by_rel.get(v.path)
        if src is None or not src.is_allowed(v.rule, v.line):
            out.append(v)
    assert out == [], [str(v) for v in out]


def test_crashpoint_table_covers_registry_and_readme_is_fresh():
    from check import crashtable
    mod = crashtable.load_crashpoints()
    table = mod.render_table()
    for name in mod.CRASHPOINTS:
        assert f"`{name}`" in table
    assert crashtable.check_drift() == []


def test_crashpoint_table_drift_detected(tmp_path, monkeypatch):
    from check import crashtable
    readme = tmp_path / "README.md"
    readme.write_text("# x\n\nno markers\n")
    monkeypatch.setattr(crashtable, "README", str(readme))
    vs = crashtable.check_drift()
    assert vs and "markers missing" in vs[0].message
    mod = crashtable.load_crashpoints()
    readme.write_text(
        f"# x\n\n{mod.TABLE_BEGIN}\nstale\n{mod.TABLE_END}\n")
    vs2 = crashtable.check_drift()
    assert vs2 and "drifted" in vs2[0].message


# ---------------------------------------------------------------------------
# rule: deadline (ISSUE 15 satellite — gray-failure plane)
# ---------------------------------------------------------------------------

BAD_DEADLINE = '''
def fan_out(self, futs, sock):
    for f in futs:
        f.result()
    return sock.recv(4096)
'''

GOOD_DEADLINE = '''
def fan_out(self, futs, sock):
    for f in futs:
        f.result(timeout=5.0)
    out = [f.result(2.0) for f in futs]
    # check: allow(deadline) bounded by the hedged reader's own deadline
    out.append(futs[0].result())
    return out
'''


def test_deadline_rule_fires_on_bare_waits():
    vs = rules_ast.check_deadline(
        [_src("minio_tpu/object/engine.py", BAD_DEADLINE)])
    msgs = "\n".join(v.message for v in vs)
    assert "bare unbounded future .result()" in msgs
    assert ".recv()" in msgs
    assert len(vs) == 2


def test_deadline_rule_quiet_on_bounded_and_cold_modules():
    from check.core import filter_allowed
    src = _src("minio_tpu/object/engine.py", GOOD_DEADLINE)
    # timeout args are clean; the bare one carries its allow() argument
    assert filter_allowed(src, rules_ast.check_deadline([src])) == []
    # a module outside the hot list is not scanned at all
    assert rules_ast.check_deadline(
        [_src("minio_tpu/utils/telemetry.py", BAD_DEADLINE)]) == []


def test_deadline_rule_clean_on_tree():
    """Every hot-path fan-out in the committed tree either carries a
    timeout, rides the hedged reader / quorum lane, or argues its
    bound inline — the satellite's deliverable."""
    from check.core import filter_allowed, load_sources
    sources = load_sources()
    by_rel = {s.rel: s for s in sources}
    vs = rules_ast.check_deadline(sources)
    left = []
    for v in vs:
        src = by_rel.get(v.path)
        left.extend(filter_allowed(src, [v]) if src else [v])
    assert left == []


# ---------------------------------------------------------------------------
# rule: crypto-hygiene
# ---------------------------------------------------------------------------

BAD_CRYPTO = '''
from ..ops import chacha20_ref
from ..ops.chacha20_ref import tag_detached
from ..features.crypto import _pkg_nonce

def rogue_nonce(base, seq):
    # hand-rolled seq mixing: the exact bug class the rule forbids
    return _pkg_nonce(base, seq)

def rogue_tag(key, nonce, aad, ct):
    return tag_detached(key, nonce, aad, ct)

def rogue_xor(data, key, nonce):
    return chacha20_ref.xor_stream(data, key, nonce)
'''

GOOD_CRYPTO = '''
from ..features import crypto as sse

def fine(oek, base, data):
    enc = sse.ChaChaEncryptor(oek, base)
    return enc.update(data) + enc.finalize()
'''


def test_crypto_hygiene_fires_on_rogue_primitive_use():
    vs = rules_project.check_crypto_hygiene(
        [_src("minio_tpu/s3/handlers.py", BAD_CRYPTO)])
    msgs = "\n".join(v.message for v in vs)
    assert "chacha20_ref" in msgs
    assert "_pkg_nonce" in msgs or "tag_detached" in msgs
    # 3 rogue imports + 3 rogue calls
    assert len(vs) >= 5


def test_crypto_hygiene_quiet_on_owner_and_consumers():
    # the owner derives nonces and drives the AEAD reference freely
    assert rules_project.check_crypto_hygiene(
        [_src("minio_tpu/features/crypto.py", BAD_CRYPTO)]) == []
    # the fused device programs may import the jax kernels (keystream
    # over nonce arrays crypto.py already derived)
    assert rules_project.check_crypto_hygiene(
        [_src("minio_tpu/models/pipeline.py",
              "from ..ops import chacha20_jax\n")]) == []
    # high-level transform consumers are clean
    assert rules_project.check_crypto_hygiene(
        [_src("minio_tpu/s3/handlers.py", GOOD_CRYPTO)]) == []


def test_crypto_hygiene_clean_on_tree():
    """Package nonces are derived only inside features/crypto.py in the
    committed tree — the satellite's deliverable."""
    from check.core import filter_allowed, load_sources
    sources = load_sources()
    by_rel = {s.rel: s for s in sources}
    vs = rules_project.check_crypto_hygiene(sources)
    left = []
    for v in vs:
        src = by_rel.get(v.path)
        left.extend(filter_allowed(src, [v]) if src else [v])
    assert left == []
