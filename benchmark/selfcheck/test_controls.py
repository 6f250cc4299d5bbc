"""The harness sees a broken timed path: the rest of a run is driven at
a size a test can hold (rehearse.py: XLA-CPU, 64 KiB blocks) with a
fault planted under the codec, and the comparison comes out false —
once for each fault a cell can have, and for each cell's control. A
clean rehearsal of the same cells passes its checks.

    python3 -m pytest benchmark/selfcheck/test_controls.py -q

Each case is a process of its own: the node, the codec patch and the
program's counters are process-wide.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
PUT, GET = "ec12p4.put64m-c8", "ec12p4.get64m-1down-c8"


def rehearse(workload: str, fault: str) -> tuple[int, dict]:
    cmd = [sys.executable, os.path.join(BENCH, "rehearse.py"),
           "--workload", workload, "--seconds", "2", "--seed", "2147483659"]
    if fault:
        cmd += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                       env=env)
    lines = [ln for ln in p.stdout.splitlines()
             if ln.startswith("DRY RUN findings ")]
    assert lines, p.stderr[-2000:]
    assert "DRY RUN" in p.stdout.splitlines()[0]
    return p.returncode, json.loads(lines[-1][len("DRY RUN findings "):])


@pytest.mark.parametrize("workload,fault,tripped", [
    (PUT, "encode-parity-altered", "drive_files_wrong"),
    (PUT, "encode-digest-skipped", "drive_files_wrong"),
    ("ec8p8.put64m-c8", "encode-digest-skipped", "drive_files_wrong"),
    (GET, "decode-output-altered", "answers_wrong"),
    (GET, "decode-skipped", "answers_wrong"),
])
def test_fault_is_seen(workload, fault, tripped):
    rc, found = rehearse(workload, fault)
    assert rc == 1 and found["checks_pass"] is False
    c = found["compared"][tripped]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", [PUT, GET])
def test_clean_rehearsal_passes_its_checks_and_never_the_run(workload):
    rc, found = rehearse(workload, "")
    assert rc == 3 and found["checks_pass"] is True
    assert all(c["value"] <= c["limit"] if c["is"] == "max"
               else c["value"] >= c["limit"]
               for c in found["compared"].values())


# -- a run that is ended from outside leaves no RAM and no process behind ----

def _mentions(word: str) -> list[int]:
    """pids of live processes whose command line holds `word`."""
    out = []
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(path, "rb") as f:
                if word.encode() in f.read():
                    out.append(int(path.split("/")[2]))
        except OSError:
            pass
    return [p for p in out if p != os.getpid()]


@pytest.mark.parametrize("how", [signal.SIGTERM, signal.SIGKILL])
def test_an_ended_run_frees_its_drive_tree_and_its_children(how):
    from benchlib import harness
    seed = 2147480000 + int(how)           # names this run's clients
    p = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload",
         PUT, "--seconds", "120", "--seed", str(seed)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    root = os.path.join(harness.drive_base(), f"run-{p.pid}")
    try:
        deadline = time.time() + 120
        while not glob.glob(os.path.join(root, "d1", "bench", "put", "*")):
            assert p.poll() is None and time.time() < deadline
            time.sleep(0.2)                # the window's PUTs are landing
        assert _mentions(f'"seed": {seed}') and _mentions(root)
        p.send_signal(how)
        p.wait(timeout=30)
        deadline = time.time() + 30
        while (os.path.exists(root) or _mentions(f'"seed": {seed}')
               or _mentions(root)) and time.time() < deadline:
            time.sleep(0.2)
        assert not os.path.exists(root)
        assert not os.path.exists(os.path.dirname(root))   # the base too
        assert not _mentions(f'"seed": {seed}')        # the clients
        assert not _mentions(root)                     # the janitor
    finally:
        if p.poll() is None:
            p.kill()
