"""One run of one cell: set-up, the window, the checks, the result.

`run.py` calls `run_cell` on a TPU; `rehearse.py` calls it with
`rehearsal=` sizes on XLA-CPU and prints its findings as a DRY RUN.
Cells, configurations, traffic mixes and per-layer metrics are found
by the names in BENCHMARK.json — nothing here knows any of them. The
end-to-end metrics are the harness's own, named from the mix's verb:
`<op>_MiB_s`, `<op>_p50_ms`, `<op>_p90_ms`, `<op>_p99_ms`, `setup_s`;
a cell reports those of them that BENCHMARK.json lists for it.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

from benchlib import check, loadgen, readers, traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)
TRACE_SECONDS = 2.0          # traces are large (~25k device events a launch)
SHM = "/dev/shm"             # RAM: a window's writes never reach a disk
# what a run holds that outlives a `finally` it never reaches: the
# watchdog and the signal handlers of run.py call `abandon()`
_LIVE: dict = {"root": None, "janitor": None, "clients": None}


class NoResult(Exception):
    """The run cannot give a result line (no chip, unknown cell, ...)."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# found by name
# ---------------------------------------------------------------------------

def load_cell(workload: str) -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise NoResult(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(BENCH_DIR, cell["traffic"])

    def mine(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def load_peak(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise NoResult(f"device kind {device_kind!r} is not in "
                       "benchmark/peaks.json — add it with its source")
    return peaks[device_kind]


def load_reader(metric: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# set-up pieces
# ---------------------------------------------------------------------------

def _is_tmpfs(path: str) -> bool:
    best, kind = "", ""
    real = os.path.realpath(path)
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mnt, fstype = line.split()[:3]
            mnt = mnt.replace("\\040", " ")
            if (real == mnt or real.startswith(mnt.rstrip("/") + "/")) \
                    and len(mnt) >= len(best):
                best, kind = mnt, fstype
    return kind == "tmpfs"


def drive_base() -> str:
    """Where this checkout's runs keep their drive trees: RAM-backed,
    and this checkout's alone. `$TMPDIR` when that is tmpfs (the driver
    gives each side its own); else a directory of /dev/shm named after
    the checkout's path, so two checkouts on one machine never meet."""
    tmp = os.environ.get("TMPDIR", "")
    if tmp and os.path.isdir(tmp) and _is_tmpfs(tmp):
        top = tmp
    elif os.path.isdir(SHM):
        top = SHM
    else:
        raise NoResult(f"neither $TMPDIR nor {SHM} is a RAM-backed "
                       "directory: the drives need one")
    tag = hashlib.sha1(REPO.encode()).hexdigest()[:12]
    return os.path.join(top, f"minio_tpu_bench-{tag}")


def _run_alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return b"benchmark" in f.read()
    except OSError:
        return False


def reclaim_stale(base: str) -> list[str]:
    """Trees of this checkout's earlier runs whose process is gone (a
    killed run skips its clean-up) are removed; a live run's is not."""
    gone = []
    for name in os.listdir(base) if os.path.isdir(base) else []:
        pid = name.rsplit("-", 1)[-1]
        if name.startswith("run-") and pid.isdigit() \
                and (int(pid) == os.getpid() or not _run_alive(int(pid))):
            shutil.rmtree(os.path.join(base, name), ignore_errors=True)
            gone.append(name)
    return gone


def make_drive_root(mix: dict, config: dict, seconds: float) -> str:
    """A fresh directory for the 16 drives, with room checked for what
    the window can write: rate x seconds x (k+m)/k, or the populated
    set. Fails loudly rather than filling the host's memory."""
    base = drive_base()
    stale = reclaim_stale(base)
    if stale:
        say(f"removed stale drive trees of this checkout: {stale}")
    n = config["drives"]
    parity = config["parity"] if config["parity"] is not None else n // 2
    inflate = n / (n - parity)
    if mix["op"] == "PUT":
        # the janitor leaves one object in keep_one_in, and whatever is
        # younger than its sweep (5 s of writes); the warm-up before the
        # window and the requests in flight after it write too
        kept_share = 1 / mix["keep_one_in"] if mix["keep_one_in"] > 1 else 1
        need = mix["room_MiB_s"] * (1 << 20) * inflate * (
            (seconds + 10) * kept_share + 5)
    else:
        need = mix["populate_objects"] * mix["object_bytes"] * inflate * 1.1
    os.makedirs(base, exist_ok=True)
    free = min(shutil.disk_usage(base).free, _mem_available())
    if free < need + (2 << 30):
        raise NoResult(f"{base} has {free >> 20} MiB free; the window can "
                       f"write {int(need) >> 20} MiB (+2 GiB spare)")
    root = os.path.join(base, f"run-{os.getpid()}")
    os.mkdir(root)
    return root


def abandon() -> None:
    """The run is being given up (watchdog, a signal): stop the clients
    and see that the drives' RAM is freed. The node dies with the
    process — and only then do its threads stop writing, so the tree is
    left to the janitor, which removes it once this process is gone
    (removed from here, 17 MB of writes in flight landed after the
    sweep: my chip run, PR 24)."""
    for p in getattr(_LIVE["clients"], "procs", []):
        p.kill()
    jan = _LIVE["janitor"]
    if _LIVE["root"] is not None and (jan is None or jan.poll() is not None):
        _remove_tree(_LIVE["root"])


def _remove_tree(root: str) -> None:
    """The run's drive tree, and the checkout's base directory with it
    when no other run's tree is in there."""
    shutil.rmtree(root, ignore_errors=True)
    try:
        os.rmdir(os.path.dirname(root))
    except OSError:
        pass


def guard(deadline_s: float) -> threading.Timer:
    """A run that outlives `deadline_s`, or is told to end (SIGTERM,
    SIGINT, SIGHUP), leaves through `abandon()`: no `finally` runs on
    those ways out. -> the watchdog, for the caller to cancel. A
    SIGKILL cannot be caught: the janitor then removes the tree."""
    def give_up(why: str) -> None:
        say(f"benchmark: {why} — giving up")
        abandon()
        os._exit(1)
    watchdog = threading.Timer(
        deadline_s, give_up, [f"still running after {deadline_s}s"])
    watchdog.daemon = True
    watchdog.start()
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, lambda n, _f: give_up(
            f"signal {signal.Signals(n).name}"))
    return watchdog


def _mem_available() -> int:
    """tmpfs pages are RAM: the room is what the kernel can still give."""
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise NoResult("/proc/meminfo has no MemAvailable")


def populate(nd, mix: dict, seed: int, drives: int) -> None:
    """The GET cell's working set, one sequential stream (one encode
    shape), through the endpoint like any client's PUT."""
    http_ = loadgen.Http("127.0.0.1", nd.port)
    pool = [traffic.body(seed, -1, i, mix["object_bytes"])
            for i in range(mix["distinct_bodies"])]
    shas = [hashlib.sha256(b).hexdigest() for b in pool]
    for j, key in enumerate(traffic.populated_keys(seed, mix, drives)):
        i = j % len(pool)
        status, data, _etag = http_.request(
            "PUT", f"/{traffic.BUCKET}/{key}", pool[i], shas[i])
        if status != 200:
            raise RuntimeError(f"populate PUT {key} -> {status} "
                               f"{data[:200]!r}")
    if http_.conn is not None:
        http_.conn.close()


def make_bucket(port: int) -> None:
    http_ = loadgen.Http("127.0.0.1", port)
    status, data, _ = http_.request("PUT", f"/{traffic.BUCKET}", b"",
                                    loadgen.EMPTY_SHA)
    http_.conn.close()
    if status != 200:
        raise RuntimeError(f"make bucket -> {status} {data[:200]!r}")


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, rehearsal: dict | None = None,
             fault: str = "", mix_set: dict | None = None) -> dict:
    """-> the result object (run.py prints it as the last line).
    `mix_set` overrides parameters of the cell's traffic mix: for the
    sweeps and pairs of PERF.md, never for a run the driver makes."""
    found = load_cell(workload)
    cell, config, mix = found["cell"], found["config"], dict(found["mix"])
    if rehearsal:
        mix.update(rehearsal["mix"])
    mix.update(mix_set or {})
    # started first: the clients prepare their bodies while the node boots
    clients = _LIVE["clients"] = loadgen.Clients(mix, seed,
                                                 config["drives"])
    nd = root = janitor = None
    try:
        from benchlib import node as node_mod
        dev = node_mod.devices()
        if not rehearsal:
            if dev["platform"] != "tpu":
                raise NoResult(f"no TPU: JAX reports {dev}")
            if dev["count"] < cell["chips"]:
                raise NoResult(f"cell needs {cell['chips']} chips, JAX "
                               f"reports {dev}")
            peak = load_peak(dev["kind"])
        else:
            peak = {"hbm_bytes_per_s": float("nan")}
        say(f"device {dev}  cell {workload}  seed {seed}")
        marks = [("jax", time.monotonic() - t_process_start)]

        def mark(what: str) -> None:
            marks.append((what, time.monotonic() - t_process_start))
        listener = node_mod.CompileListener()
        root = _LIVE["root"] = make_drive_root(mix, config, seconds)
        janitor = _LIVE["janitor"] = _start_janitor(
            root, [os.path.join(root, f"d{j + 1}")
                   for j in range(config["drives"])], mix)
        nd = node_mod.Node(config, root,
                           tiny=rehearsal and rehearsal["node"])
        if fault:
            from benchlib import faults
            faults.plant(fault)
        verb = node_mod.VERB_OF_OP[mix["op"]]
        mark("node")
        make_bucket(nd.port)
        offline = []
        if mix["op"] == "GET":
            populate(nd, mix, seed, config["drives"])
            offline = traffic.offline_drives(seed, mix, config["drives"])
            for index in offline:
                nd.pull_drive(index)
        mark("populate")
        nd.warm(verb, nd.launch_sizes(verb),
                tuple(mix.get("warm_lost_shards", (1,))))
        mark("warm")
        clients.wait_ready()
        mark("clients_ready")
        clients.start(nd.port)
        mark("clients_warm")
        say(f"set-up: {listener.snapshot()}  offline drives {offline}  "
            f"seconds since process start: {marks}")

        # ---- the window ----
        c0, comp0 = nd.counters(), listener.snapshot()
        t0 = time.monotonic()
        tr = trace_dir = stretch = None
        quarters = []
        for q in range(1, 5):
            if trace and q == 4:
                # the traced stretch is the END of the window, so that
                # stopping the profiler (seconds) falls after the mark.
                # The profiler records from inside start_trace; the
                # annotation marks, on the trace's own clock, the
                # stretch the counters ca..c1 cover
                length = min(TRACE_SECONDS, seconds / 4)
                _sleep_until(t0 + seconds - length)
                trace_dir, stretch = _start_trace()
                ca = nd.counters()
            _sleep_until(t0 + seconds * q / 4)
            quarters.append(nd.counters()["verbs"][verb]["batches"])
        c1, comp1 = nd.counters(), listener.snapshot()
        t1 = time.monotonic()
        if stretch is not None:
            stretch.__exit__(None, None, None)
        # ---- closed ----

        clients.signal_stop()
        if trace:
            tr = _reduce_trace(trace_dir, ca, c1, bool(rehearsal))
        records, errors = clients.collect()
        window_s = t1 - t0
        setup_s = t0 - t_process_start
        mem_peak = node_mod.memory_peak_bytes()
        in_window = [r for r in records if t0 <= r[1] <= t1]
        late_failures = [r for r in records
                         if t0 <= r[0] <= t1 < r[1] and not r[4]]
        attempted = len(in_window) + len(late_failures)
        failed = sum(1 for r in in_window if not r[4]) + len(late_failures)
        for e in errors[:5]:
            say(f"client: {e}")

        by_quarter = [b - a for a, b in zip(
            [c0["verbs"][verb]["batches"]] + quarters, quarters)]
        numbers = check.device_path(verb, c0, c1, bool(rehearsal))
        if mix["op"] == "PUT":
            numbers.update(check.put_cell(
                seed, mix, nd.k, nd.m, nd.block_size, nd.port,
                nd.drive_paths(), in_window))
        else:
            numbers.update(check.get_cell(
                in_window, nd.still_pulled(),
                c1["mrf"].get("healed", 0) - c0["mrf"].get("healed", 0),
                by_quarter))
        correct = check.verdict(numbers) and not rehearsal

        win = {"op": mix["op"], "verb": verb, "records": in_window,
               "c0": c0, "c1": c1, "window_s": window_s, "trace": tr,
               "compiles": comp1["programs"] - comp0["programs"],
               "geometry": {"k": nd.k, "m": nd.m,
                            "block_size": nd.block_size},
               "peak": peak}
        moved = sum(r[2] for r in in_window if r[4])
        lat = [(r[1] - r[0]) * 1e3 for r in in_window]
        op = mix["op"].lower()
        e2e = {f"{op}_MiB_s": moved / (1 << 20) / window_s,
               "setup_s": setup_s}
        for q in (50, 90, 99):
            e2e[f"{op}_p{q}_ms"] = readers.percentile(lat, q)
        metrics = {}
        for m in found["per_layer" if trace else "end_to_end"]:
            value = load_reader(m["name"])(win) if trace \
                else e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(dev, memory_peak_bytes=mem_peak)
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if tr:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
        beside = {
            "workload": workload, "seed": seed, "window_s": window_s,
            "requests_in_window": len(in_window),
            "compiles_in_window": win["compiles"],
            "set_up": comp0, "set_up_marks_s": marks,
            "verbs_in_window": {
                v: {f: c1["verbs"][v][f] - c0["verbs"][v][f]
                    for f in ("batches", "blocks", "cpu_routed", "errors")}
                for v in ("encode", "decode", "recover")},
            "mrf_at_start": c0["mrf"], "mrf_at_end": c1["mrf"],
            "offline_drives": offline,
            "launches_by_quarter": by_quarter,
            "latency_ms": {"n": len(lat), "p50": e2e[f"{op}_p50_ms"],
                           "p90": e2e[f"{op}_p90_ms"],
                           "max": max(lat, default=None)},
            "declines": c1["declines"],
            "gray_lane_at_start": c0["gray_lane"],
            "gray_lane_at_end": c1["gray_lane"],
            "decode_blocks_by_lost": {
                r: n - c0["decode_by_lost"].get(r, 0)
                for r, n in c1["decode_by_lost"].items()},
            "traced": tr and {f: tr[f] for f in (
                "busy_s", "window_s", "events_span_s", "outside_s",
                "blocks", "decode_by_lost")},
        }
        result["beside"] = beside
        result["compared"] = {k: {"value": v, "limit": lim, "is": how}
                              for k, (v, lim, how) in numbers.items()}
        say("beside " + json.dumps(beside))
        return result
    finally:
        clients.close()
        if janitor is not None:
            _stop_janitor(janitor)
        if nd is not None:
            nd.shutdown()
        if root is not None:
            _remove_tree(root)
        _LIVE.update(root=None, janitor=None, clients=None)


def _start_janitor(root: str, drives: list[str],
                   mix: dict) -> subprocess.Popen:
    """benchlib/janitor.py: removes the tree if this process is killed,
    and expires a PUT window's objects (`keep_one_in`)."""
    spec = {"root": root, "drives": drives,
            "prefix_dir": f"{traffic.BUCKET}/put",
            "keep_one_in": mix.get("keep_one_in", 0), "min_age_s": 3.0}
    return subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "benchlib", "janitor.py"),
         json.dumps(spec)], stdin=subprocess.PIPE, text=True)


def _stop_janitor(proc: subprocess.Popen) -> None:
    try:
        proc.stdin.write("stop\n")
        proc.stdin.close()
        proc.wait(timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        proc.kill()
        proc.wait()


def _sleep_until(t: float) -> None:
    rest = t - time.monotonic()
    if rest > 0:
        time.sleep(rest)


def _start_trace():
    """-> (trace directory, the open annotation that marks the stretch)"""
    import jax
    from benchlib import tracered
    out = os.path.join(REPO, ".bench_out", f"trace-{os.getpid()}")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    stretch = jax.profiler.TraceAnnotation(tracered.WINDOW_MARK)
    stretch.__enter__()
    return out, stretch


def _reduce_trace(out: str, ca: dict, cb: dict,
                  rehearsal: bool) -> dict | None:
    """Stop the profiler and reduce what it wrote, cut to the marked
    stretch; the blocks each verb dispatched in the stretch come from
    the former's counts at both ends of it."""
    import jax
    from benchlib import tracered
    jax.profiler.stop_trace()
    try:
        planes, mark = tracered.load_trace(tracered.find_xplane(out))
        if rehearsal and not planes:
            return None         # XLA-CPU: the trace has no device plane
        if mark is None:
            raise RuntimeError(f"the trace has no {tracered.WINDOW_MARK} "
                               "annotation: busy time cannot be cut to "
                               "the stretch the counters cover")
        red = tracered.reduce_planes(planes, clip=mark)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    red["blocks"] = {v: cb["verbs"][v]["blocks"] - ca["verbs"][v]["blocks"]
                     for v in cb["verbs"]}
    red["decode_by_lost"] = {
        r: n - ca["decode_by_lost"].get(r, 0)
        for r, n in cb["decode_by_lost"].items()}
    return red
