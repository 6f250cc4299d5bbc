"""Test configuration: force the CPU backend with 8 virtual devices.

Tier-1 runs on the local XLA-CPU backend (fast, and 8 virtual devices
for the sharding tests), whatever the machine has: the platform is
pinned before the backend initialises. The compile cache follows the
program's own rule (minio_tpu/utils/device.py): the directory
JAX_COMPILATION_CACHE_DIR names when it is set, else
<checkout>/.jax_cache — the HH256 kernel costs ~10 s of XLA compile per
distinct shape, so the cache is what keeps reruns fast.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")

from minio_tpu.utils import device

device.probe()


import threading
import time

import pytest

# test modules that run with the lock-order watchdog ON by default
# (opt out with MINIO_TPU_LOCKCHECK=off): the suites that actually
# interleave threads, so a future lock-order inversion fails loudly in
# tier-1 instead of hanging a production box
_LOCKCHECK_MODULES = ("test_chaos", "test_concurrency", "test_lockcheck")


@pytest.fixture(autouse=True)
def _lockcheck_watchdog(request):
    mod = request.module.__name__.rpartition(".")[2]
    # honor every false spelling the knob vocabulary accepts
    opted_out = os.environ.get("MINIO_TPU_LOCKCHECK", "").strip().lower() \
        in ("off", "0", "false", "no")
    if mod not in _LOCKCHECK_MODULES or opted_out:
        yield
        return
    from minio_tpu.utils import lockcheck
    prev = os.environ.get("MINIO_TPU_LOCKCHECK")
    os.environ["MINIO_TPU_LOCKCHECK"] = "on"
    lockcheck.refresh()
    lockcheck.reset()
    try:
        yield
        # cycles raised on daemon/background threads are swallowed by
        # their thread loops — surface them here
        cycles = lockcheck.violations("cycle")
        assert not cycles, (
            "lock-order watchdog recorded cycle(s): "
            + "; ".join(v.detail for v in cycles))
    finally:
        if prev is None:
            os.environ.pop("MINIO_TPU_LOCKCHECK", None)
        else:
            os.environ["MINIO_TPU_LOCKCHECK"] = prev
        lockcheck.refresh()
        lockcheck.reset()


# process-global worker pools that are CREATED lazily and live for the
# interpreter's lifetime by design (metadata._POOL drive fan-out,
# pipeline.PREFETCH_POOL) — the leak sentinel must not blame the first
# test that happens to touch them
_LONGLIVED_PREFIXES = ("drive-io", "get-prefetch")


@pytest.fixture(autouse=True)
def _thread_leak_sentinel():
    """No stray non-daemon threads may survive a test: a leaked
    scheduler dispatch pool or cluster worker keeps the interpreter
    alive after pytest finishes and convoys later tests. Fixtures that
    start workers must close() them. Daemon threads are exempt (all
    long-running daemons in-tree are daemonized); so are the
    process-global lazy pools above."""
    before = set(threading.enumerate())
    yield
    def strays():
        return [t for t in threading.enumerate()
                if t not in before and t.is_alive() and not t.daemon
                and not t.name.startswith(_LONGLIVED_PREFIXES)]
    s = strays()
    deadline = time.time() + 2.0
    while s and time.time() < deadline:
        for t in s:                 # a finishing worker gets a grace join
            t.join(timeout=0.25)
        s = strays()
    assert not s, (
        "test leaked non-daemon thread(s): "
        + ", ".join(sorted(t.name for t in s))
        + " — the owning fixture must close() its workers")


@pytest.fixture(autouse=True)
def _no_background_program_loads(request, monkeypatch):
    """On a TPU the codec starts, once a geometry, a background load of
    the ten ragged encode rungs when a launch with a short block first
    routes to the device (parallel/ladder.load_encode_ragged). A test
    that fakes the TPU on XLA-CPU would compile those programs behind
    its back, on threads that outlive it; it launches its ragged
    programs through the jit call instead. The tests of the load
    itself carry the marker `ragged_loads` and get the real one."""
    if request.node.get_closest_marker("ragged_loads") is None:
        from minio_tpu.parallel import ladder
        monkeypatch.setattr(ladder, "load_encode_ragged",
                            lambda *_a, **_kw: False)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "ragged_loads: the test wants the real background load of the "
        "ragged encode rungs (parallel/ladder.load_encode_ragged), "
        "which every other test gets as a stub")
    config.addinivalue_line(
        "markers",
        "native: exercises the C++ library under ASan/UBSan "
        "(make -C native sanitize; run with `pytest -m native`)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests driven by a seeded NaughtyDisk "
        "schedule; cheap seeded subset runs in tier-1, long randomized "
        "schedules are additionally marked slow. Reproduce any failure "
        "with MINIO_TPU_CHAOS_SEED=<seed printed in the failing test's "
        "captured stdout>")
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 "
        "(-m 'not slow'); run with `pytest -m slow`")
