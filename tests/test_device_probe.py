"""The one device probe, the compile-cache rule, a counted device fault,
and chip_smoke.py's CPU dry run (so the script cannot rot)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from minio_tpu.object import codec as codec_mod
from minio_tpu.utils import device, eventlog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- probe -------------------------------------------------------------------

def test_probe_decided_once_and_keeps_the_reason(monkeypatch):
    """A backend that cannot initialise is asked ONCE, and its own
    words survive into the probe — not a bare False."""
    calls = []

    def broken():
        calls.append(1)
        raise RuntimeError("Unable to initialize backend 'tpu': "
                           "chip held by pid 4242")

    monkeypatch.setattr(device, "_PROBED", None)
    monkeypatch.setattr(device.jax, "devices", broken)
    first = device.probe()
    again = device.probe()
    assert first is again and len(calls) == 1
    assert not first.is_tpu and first.count == 0
    assert "RuntimeError" in first.reason
    assert "chip held by pid 4242" in first.reason
    # every reader sees the host route, none asks JAX again
    from minio_tpu.ops import highwayhash_jax, rs_tpu
    from minio_tpu.scan import kernels
    assert codec_mod.Codec(4, 2, 1 << 16)._route(1 << 30) != "device"
    assert rs_tpu.default_use_pallas() is False
    assert highwayhash_jax._groups() == highwayhash_jax._GROUPS_CPU
    assert kernels.decline_reason() == "no-device"
    assert codec_mod.data_path_line().startswith(
        "data path: host CPU — no accelerator: RuntimeError")
    assert len(calls) == 1


def test_probe_reports_what_jax_reports():
    import jax
    dp = device.probe()
    assert (dp.platform, dp.device_kind, dp.count) == (
        jax.devices()[0].platform, jax.devices()[0].device_kind,
        len(jax.devices()))
    assert dp.reason                       # tier-1 runs on the CPU
    assert "cpu" in codec_mod.data_path_line()


def test_scan_plane_declines_by_name_on_a_tpu(monkeypatch):
    """The TPU has no IEEE float64: the scan plane says so up front
    instead of answering approximately."""
    from minio_tpu.scan import kernels
    monkeypatch.setattr(device, "_PROBED", device.DataPath(
        "tpu", "TPU v5 lite", 1, ""))
    assert kernels.decline_reason() == "no-f64"
    monkeypatch.setenv("MINIO_TPU_SCAN_DEVICE", "force")
    assert kernels.decline_reason() == ""


# -- compile cache -----------------------------------------------------------

def test_compile_cache_dir_is_the_envs_or_the_checkouts(monkeypatch,
                                                       tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.compile_cache_dir() == (str(tmp_path), False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert device.compile_cache_dir() == (
        os.path.join(REPO, ".jax_cache"), True)


def test_probe_places_the_cache_only_when_the_env_does_not(monkeypatch,
                                                           tmp_path):
    updates = []
    monkeypatch.setattr(device.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    device._probe_once()
    assert updates == []                   # JAX reads the env itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    device._probe_once()
    assert [v for _k, v in updates] == [os.path.join(REPO, ".jax_cache")]


# -- a device fault is counted -----------------------------------------------

def test_raising_dispatch_is_counted_and_the_get_still_succeeds(
        monkeypatch, tmp_path):
    from minio_tpu.models import pipeline
    from minio_tpu.parallel.scheduler import BatchScheduler
    from tests.test_engine import BLOCK, make_engine

    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    eng = make_engine(tmp_path)
    eng.make_bucket("bucket")
    data = np.random.default_rng(3).integers(
        0, 256, 3 * BLOCK + 77, dtype=np.uint8).tobytes()
    eng.put_object("bucket", "obj", data)
    import glob
    for f in sorted(glob.glob(os.path.join(
            str(tmp_path), "d*", "bucket", "obj", "*", "part.1")))[:2]:
        os.remove(f)

    def boom(*_a, **_kw):
        raise RuntimeError("XLA:TPU halted: chip fault (test)")

    monkeypatch.setattr(pipeline, "get_step", boom)
    seq0 = max((e["seq"] for e in eventlog.JOURNAL.recent()), default=0)
    sched = BatchScheduler(max_wait=0.01)
    eng.scheduler = sched
    try:
        _oi, it = eng.get_object("bucket", "obj")
        assert b"".join(it) == data        # availability kept: host path
        st = sched.stats()
        assert st["errors"]["decode"] >= 1
        assert st["verbs"]["decode"]["batches"] == 0
        events = [e["attrs"] for e in eventlog.JOURNAL.recent(
            classes={"device.decline"}, since_seq=seq0)]
        assert any(a.get("stage") == "decode" and a.get("reason") == "error"
                   and "chip fault (test)" in a.get("detail", "")
                   for a in events), events
    finally:
        eng.scheduler = None
        sched.close()


def test_cpu_routed_batch_is_not_a_dispatch(monkeypatch):
    """minio_tpu_sched_batches_total counts launches, not declines."""
    from minio_tpu import bitrot as bitrot_mod
    from minio_tpu.parallel import scheduler as sched_mod

    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    # enqueued (a device exists) but under the routing threshold: the
    # codec declines inside the dispatch
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 1 << 40)
    before = sched_mod._BATCHES_TOTAL.value(verb="encode")
    sched = sched_mod.BatchScheduler(max_wait=0.01)
    try:
        data = np.zeros((2, 4, 256), np.uint8)
        out = sched.encode_and_hash(
            codec_mod.Codec(4, 2, 4 * 256), data,
            bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S)
        assert out is None
        st = sched.stats()
        assert st["batches"] == 0 and st["dispatched_blocks"] == 0
        assert st["verbs"]["encode"]["cpu_routed"] == 1
        assert sched_mod._BATCHES_TOTAL.value(verb="encode") == before
    finally:
        sched.close()


# -- chip_smoke.py -----------------------------------------------------------

def _smoke(*flags: str) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flags],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_to_pass_without_a_chip():
    r = _smoke()
    assert r.returncode != 0
    assert "no chip found" in r.stderr
    assert r.stdout.strip() == ""          # no result line


def test_chip_smoke_cpu_dry_run_drives_every_phase():
    r = _smoke("--dry-run-cpu")
    assert "DRY RUN" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.returncode == 3, r.stdout[-2000:] + r.stderr[-2000:]
    *_, summary, last = r.stdout.strip().splitlines()
    # last line: the driver's verdict shape, exactly — and never a pass
    verdict = json.loads(last)
    assert verdict == {"ok": False, "device": {
        "platform": "cpu", "kind": verdict["device"]["kind"], "count": 1}}
    assert isinstance(verdict["device"]["kind"], str)
    assert summary.startswith("report ")
    rep = json.loads(summary[len("report "):])
    assert rep["ok"] is False and rep["dry_run"] is True
    assert rep["failed"] == [] and rep["dispatch_errors"] == 0
    assert list(rep)[-1] == "claim" and rep["claim"] is None
    assert all(p["ok"] for p in rep["phases"])
    assert {(p["pass"], p["phase"]) for p in rep["phases"]} >= {
        (ps, ph) for ps in "AB" for ph in
        ("boot", "put", "get", "lose-shards", "degraded-get", "mrf-heal",
         "admin-heal")
    } | {("A", "select")}
    assert all(rep["compute_observations"][v] >= 1
               for v in ("encode", "decode", "recover"))
    assert rep["select"] == "device"
