#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Boots the node IN THIS PROCESS with the call `python -m minio_tpu server`
makes (cluster.start_single), drives it over plain HTTP with SigV4-signed
requests from client threads, and decides pass/fail from the program's
own evidence (dispatch-stage histogram, scheduler error counts, the event
journal, the scan plane's counters) — not from HTTP 200s. One process
touches JAX: a chip belongs to one process at a time.

Two deployments back to back, both at the CLI's block size (4 MiB) and
default bitrot (HighwayHash256S), 16 drives under /dev/shm:

  A  parity=4 -> 12+4   8 concurrent 64 MiB PUTs, GET all back, remove
                        three drives' shards of four objects (at least
                        one DATA shard each), GET those, let the MRF
                        healer the GETs woke repair them, remove one
                        object's shards again and admin-heal it, verify
                        every healed shard (check_parts + full bitrot
                        scan), one SelectObjectContent over ~8 MiB CSV
  B  no flags  -> 8+8   2 concurrent 64 MiB PUTs, GET, remove one
                        drive's data shard of one object (r = 1),
                        degraded GET, both heals

    python chip_smoke.py [--seed N]

exits 0 only on a TPU with every phase passed. The last stdout line is
the verdict, `{"ok": true, "device": {"platform", "kind", "count"}}` with
the device as JAX reports it; the line before it is `report {...}`, the
summary (phases, routes, compile and cache counts, ending in
`"claim": null`). Without an accelerator it exits 2 and prints no result.
`--dry-run-cpu` drives the same phases at a tiny size with the device
route forced onto XLA-CPU: it prints `DRY RUN platform=cpu`, reports
`"ok": false`, and exits 3 when its phases pass — never 0.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import http.client
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
import urllib.parse

REGION = "us-east-1"
BUCKET = "smoke"
EXIT_NO_CHIP = 2
EXIT_DRY_RUN_OK = 3
DEADLINE_S = 1150          # the driver allows 1200 s, compilation included
ERASURE_STEPS = ("put_step", "get_step", "heal_step")
REPORT_PREFIX = "report "  # the summary line, second to last on stdout


class SmokeFailure(Exception):
    """A phase's own check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# plain HTTP client with SigV4 (s3/signature.sign_v4)
# ---------------------------------------------------------------------------

class Client:
    def __init__(self, host: str, port: int, creds):
        self.host, self.port, self.creds = host, port, creds

    def request(self, method: str, path: str, query: dict | None = None,
                body: bytes = b"") -> tuple[int, bytes]:
        from minio_tpu.s3 import signature as sig
        q = {k: [v] for k, v in (query or {}).items()}
        hdrs = sig.sign_v4(
            method, urllib.parse.quote(path), q,
            {"host": f"{self.host}:{self.port}"},
            hashlib.sha256(body).hexdigest(), self.creds, REGION)
        qs = urllib.parse.urlencode({k: v[0] for k, v in q.items()})
        # a cold server compiles inside requests: be patient
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=DEADLINE_S)
        try:
            conn.request(method, urllib.parse.quote(path)
                         + (f"?{qs}" if qs else ""), body=body,
                         headers=hdrs)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()


# ---------------------------------------------------------------------------
# evidence the program keeps about itself
# ---------------------------------------------------------------------------

class Evidence:
    """Compile events (jax.monitoring) and the program's own counters."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._mu = threading.Lock()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._mu:
                self.compile_s += secs
                self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        with self._mu:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def compile_mark(self) -> tuple[float, int]:
        with self._mu:
            return self.compile_s, self.compiles

    @staticmethod
    def compute_observations() -> dict:
        """minio_tpu_device_dispatch_seconds{stage="compute"} counts —
        the one series CPU-routed batches never feed."""
        from minio_tpu.parallel import scheduler as sched
        return {v: sched._DISPATCH_STAGE_SECONDS.count(verb=v,
                                                       stage="compute")
                for v in sched.VERBS}

    @staticmethod
    def dispatch_stages() -> dict:
        """Where the dispatches spent their time, by the program's own
        histogram: "verb.stage" -> observations and summed seconds."""
        from minio_tpu.parallel import scheduler as sched
        out = {}
        for key, (_b, total, n) in sorted(
                sched._DISPATCH_STAGE_SECONDS.series_snapshot().items()):
            lab = dict(key)
            out[f"{lab['verb']}.{lab['stage']}"] = {
                "n": n, "sum_s": round(total, 3)}
        return out

    @staticmethod
    def gray_lane() -> dict:
        """Writes the quorum ack abandoned and reads it hedged, by the
        gray-failure plane's own counters (0 on a quiet host)."""
        from minio_tpu.utils import healthtrack
        return {
            "abandoned_writes": {dict(k)["stage"]: int(v) for k, v in
                                 healthtrack._LAGGARDS.series().items()},
            "hedged_reads": {dict(k)["trigger"]: int(v) for k, v in
                             healthtrack._HEDGED.series().items()}}

    @staticmethod
    def declines() -> list[dict]:
        from minio_tpu.utils import eventlog
        return [e["attrs"] for e in eventlog.JOURNAL.recent(
            classes={"device.decline"})]


def cache_entries(path: str) -> set[str]:
    try:
        return set(os.listdir(path))
    except FileNotFoundError:
        return set()


# ---------------------------------------------------------------------------
# one deployment
# ---------------------------------------------------------------------------

class Pass:
    def __init__(self, name: str, *, parity, n_puts: int, n_degraded: int,
                 lose, select: bool, args, ev: Evidence, report: dict):
        self.name, self.parity = name, parity
        self.n_puts, self.n_degraded = n_puts, n_degraded
        self.lose = lose            # k -> shard indices to remove
        self.select = select
        self.args, self.ev = args, ev
        self.report = report        # shared summary (phases appended)
        self.node = None
        self.root = ""
        self.bodies: dict[str, bytes] = {}
        self.removed: dict[str, list] = {}     # key -> [(drive, idx)]

    # -- phase bookkeeping -------------------------------------------------

    @contextlib.contextmanager
    def phase(self, what: str):
        c0, n0 = self.ev.compile_mark()
        t0 = time.perf_counter()
        rec = {"pass": self.name, "phase": what, "ok": False}
        self.report["phases"].append(rec)
        try:
            yield rec
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 — reported, then re-raised
            rec["error"] = f"{type(e).__name__}: {e}"[:400]
            raise
        finally:
            c1, n1 = self.ev.compile_mark()
            rec["wall_s"] = round(time.perf_counter() - t0, 3)
            rec["compile_s"] = round(c1 - c0, 3)
            rec["compiles"] = n1 - n0
            print(f"[{self.name}] {what}: "
                  f"{'ok' if rec['ok'] else 'FAILED'} "
                  f"{rec['wall_s']}s (compile {rec['compile_s']}s in "
                  f"{rec['compiles']})"
                  + (f" — {rec.get('error')}" if not rec["ok"] else ""),
                  flush=True)

    # -- the deployment ----------------------------------------------------

    def run(self) -> None:
        from minio_tpu.cluster import start_single
        from minio_tpu.s3.credentials import Credentials
        args = self.args
        base = "/dev/shm" if os.path.isdir("/dev/shm") \
            else tempfile.gettempdir()
        self.root = tempfile.mkdtemp(prefix="chip_smoke_", dir=base)
        creds = Credentials("chipsmokekey", "chipsmokesecret123")
        try:
            with self.phase("boot") as rec:
                kw = {"parity": self.parity}
                if args.dry_run_cpu:
                    kw["block_size"] = args.block_size
                # the call cli.main makes for `server /data/d{1...16}`
                self.node = start_single(
                    [os.path.join(self.root, "d{1...16}")],
                    "127.0.0.1", 0, creds, **kw)
                self.k = self.node.set_drive_count - self.node.parity
                rec["geometry"] = f"{self.k}+{self.node.parity}"
                rec["drive_root"] = base
            self.c = Client("127.0.0.1", self.node.s3.port, creds)
            self.creds = creds
            self._put()
            self._get()
            self._lose_shards()
            self._degraded_get()
            self._heal()
            if self.select:
                self._select()
            self._close_out()
        finally:
            if self.node is not None:
                self.node.shutdown()
            shutil.rmtree(self.root, ignore_errors=True)

    def _get_object(self, key: str) -> tuple[int, bytes]:
        """GET with an S3 client's retry on the one 5xx this path is
        known to give: a heal that holds the object's namespace lock
        through a cold compile outlasts the 30 s lock timeout (ROADMAP
        A5). Retried like an SDK would, and counted — not hidden."""
        for _attempt in range(4):
            st, got = self.c.request("GET", f"/{BUCKET}/{key}")
            if st != 500 or b"lock acquisition timed out" not in got:
                break
            self.report["lock_timeout_retries"] += 1
        return st, got

    def _payload(self, i: int) -> bytes:
        import numpy as np
        rng = np.random.default_rng([self.args.seed, ord(self.name), i])
        return rng.integers(0, 256, self.args.object_size,
                            dtype=np.uint8).tobytes()

    def _put(self) -> None:
        with self.phase("put") as rec:
            st, body = self.c.request("PUT", f"/{BUCKET}")
            check(st == 200, f"make bucket -> {st} {body[:200]!r}")
            self.bodies = {f"obj-{i:02d}": self._payload(i)
                           for i in range(self.n_puts)}
            before = self.ev.compute_observations()["encode"]
            barrier = threading.Barrier(self.n_puts)
            errors: list[str] = []
            secs: dict[str, float] = {}

            def put(key: str, body: bytes) -> None:
                try:
                    barrier.wait(60)
                    t0 = time.perf_counter()
                    st, out = self.c.request("PUT", f"/{BUCKET}/{key}",
                                             body=body)
                    secs[key] = round(time.perf_counter() - t0, 3)
                    if st != 200:
                        errors.append(f"PUT {key} -> {st} {out[:200]!r}")
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(f"PUT {key}: {type(e).__name__}: {e}")

            ts = [threading.Thread(target=put, args=kv)
                  for kv in self.bodies.items()]
            for t in ts:
                t.start()
            for t in ts:
                t.join(DEADLINE_S)
            check(not any(t.is_alive() for t in ts), "a PUT never returned")
            check(not errors, "; ".join(errors[:3]))
            rec["request_s"] = secs
            rec["bytes"] = self.n_puts * self.args.object_size
            rec["encode_dispatches"] = \
                self.ev.compute_observations()["encode"] - before
            check(rec["encode_dispatches"] > 0,
                  "no encode dispatch reached the device")
            # writes the quorum ack left degraded (0 on a quiet host)
            rec["mrf_queued_by_puts"] = \
                self.node.sets.mrf_stats().get("queued", 0)

    def _get(self) -> None:
        with self.phase("get") as rec:
            # a write the quorum ack left degraded is the MRF healer's
            # to repair, and a cold heal holds the object's namespace
            # lock through its compile: read a settled tree
            check(self.node.sets.drain_mrf(timeout=DEADLINE_S),
                  "MRF heal queue did not drain")
            before = self.ev.compute_observations()
            for key, body in self.bodies.items():
                st, got = self._get_object(key)
                check(st == 200, f"GET {key} -> {st} {got[:300]!r}")
                check(got == body, f"GET {key}: bytes differ")
            # a healthy GET has no device verb (ROADMAP A6): bitrot
            # verifies on the host. A route, not a failure.
            moved = {v: n - before[v] for v, n in
                     self.ev.compute_observations().items()
                     if n != before[v]}
            rec["route"] = "host verify (no device verb)" if not moved \
                else f"device {moved}"

    def _remove_shards(self, key: str, lose: set) -> list:
        """Delete the part files of the drives that hold shard indices
        `lose` of this object; returns [(drive path, shard index)]."""
        from minio_tpu.storage import errors as serr
        gone = []
        for path, d in self.node.local_drives.items():
            try:
                fi = d.read_version(BUCKET, key)
            except serr.StorageError:
                continue
            idx = fi.erasure.index - 1
            if idx in lose:
                for part in fi.parts:
                    os.remove(os.path.join(path, BUCKET, key, fi.data_dir,
                                           f"part.{part.number}"))
                gone.append((path, idx))
        check(len(gone) == len(lose),
              f"{key}: found {len(gone)} of {len(lose)} shards")
        return gone

    def _lose_shards(self) -> None:
        """Per object, from ITS erasure distribution, so that a DATA
        shard is always among the lost (a lost parity shard gives the
        decode verb nothing to do and the GET would pass on the host)."""
        with self.phase("lose-shards") as rec:
            # damage a settled tree (see _get), and say what settled
            check(self.node.sets.drain_mrf(timeout=DEADLINE_S),
                  "MRF heal queue did not drain")
            rec["mrf_before_damage"] = self.mrf_before_damage = \
                self.node.sets.mrf_stats()
            lose = self.lose(self.k)
            check(any(i < self.k for i in lose), "no data shard chosen")
            for key in list(self.bodies)[:self.n_degraded]:
                self.removed[key] = self._remove_shards(key, lose)
            rec["lost_shard_indices"] = sorted(lose)
            rec["objects"] = list(self.removed)

    def _degraded_get(self) -> None:
        with self.phase("degraded-get") as rec:
            before = self.ev.compute_observations()["decode"]
            secs = {}
            for key in self.removed:
                t0 = time.perf_counter()
                st, got = self._get_object(key)
                secs[key] = round(time.perf_counter() - t0, 3)
                check(st == 200,
                      f"degraded GET {key} -> {st} {got[:300]!r}")
                check(got == self.bodies[key],
                      f"degraded GET {key}: bytes differ")
            rec["request_s"] = secs
            rec["decode_dispatches"] = \
                self.ev.compute_observations()["decode"] - before
            check(rec["decode_dispatches"] > 0,
                  "degraded GET was served without the decode verb")

    def _verify_shards(self, key: str) -> None:
        for path, idx in self.removed[key]:
            d = self.node.local_drives[path]
            fi = d.read_version(BUCKET, key)
            check(fi.erasure.index - 1 == idx,
                  f"{key}: drive {path} healed to the wrong index")
            d.check_parts(BUCKET, key, fi)
            d.verify_file(BUCKET, key, fi)      # full bitrot scan

    def _heal(self) -> None:
        with self.phase("mrf-heal") as rec:
            # the degraded GETs queued every damaged object on the MRF
            # healer — the program's own repair. Let it finish (it
            # shares the chip), then hold every removed shard to
            # check_parts + a full bitrot scan.
            before = self.ev.compute_observations()["recover"]
            # a degraded GET hands its hint to the healer AFTER its last
            # body byte, so the client can be here before the hint is:
            # wait (briefly) until every damaged object has been queued,
            # then drain
            want = self.mrf_before_damage["queued"] + len(self.removed)
            deadline = time.monotonic() + 30
            while self.node.sets.mrf_stats()["queued"] < want \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            check(self.node.sets.drain_mrf(timeout=DEADLINE_S),
                  "MRF heal queue did not drain")
            rec["mrf"] = self.node.sets.mrf_stats()
            for key in self.removed:
                self._verify_shards(key)
            rec["recover_dispatches"] = \
                self.ev.compute_observations()["recover"] - before
            check(rec["recover_dispatches"] > 0,
                  "MRF heal ran without the recover verb")
        with self.phase("admin-heal") as rec:
            # the operator's entry point, on damage nobody has read
            # through yet (so the MRF healer does not race it for the
            # object's namespace lock): same shards gone again
            from minio_tpu.madmin import AdminClient
            before = self.ev.compute_observations()["recover"]
            key = next(iter(self.removed))
            self._remove_shards(key, {i for _p, i in self.removed[key]})
            adm = AdminClient("127.0.0.1", self.node.s3.port,
                              self.creds.access_key,
                              self.creds.secret_key, timeout=DEADLINE_S)
            token = adm.heal_start(BUCKET, key)
            while True:
                stt = adm.heal_status(token)
                if stt["status"] != "running":
                    break
                time.sleep(0.2)
            rec["admin_heal"] = stt
            check(stt["status"] == "done" and stt["failures"] == 0
                  and stt["items_scanned"] == 1, f"admin heal: {stt}")
            self._verify_shards(key)
            rec["recover_dispatches"] = \
                self.ev.compute_observations()["recover"] - before
            check(rec["recover_dispatches"] > 0,
                  "admin heal ran without the recover verb")

    def _select(self) -> None:
        with self.phase("select") as rec:
            import numpy as np
            from minio_tpu.s3select import SelectRequest
            from minio_tpu.s3select.select import event_stream
            rng = np.random.default_rng([self.args.seed, 7])
            n = self.args.select_rows
            price = rng.integers(0, 100000, n) / 100.0
            qty = rng.integers(0, 100, n)
            csv = ("id,price,qty,sku\n" + "".join(
                f"{i},{price[i]!r},{qty[i]},sku-{i % 977:03d}\n"
                for i in range(n))).encode()
            st, out = self.c.request("PUT", f"/{BUCKET}/table.csv",
                                     body=csv)
            check(st == 200, f"PUT table.csv -> {st}")
            xml = ('<?xml version="1.0" encoding="UTF-8"?>'
                   "<SelectObjectContentRequest>"
                   "<Expression>SELECT id, price FROM S3Object WHERE "
                   "price &gt; 500.25 AND qty &lt; 10</Expression>"
                   "<ExpressionType>SQL</ExpressionType>"
                   "<InputSerialization><CSV><FileHeaderInfo>USE"
                   "</FileHeaderInfo></CSV></InputSerialization>"
                   "<OutputSerialization><CSV/></OutputSerialization>"
                   "</SelectObjectContentRequest>").encode()
            scan = self.node.s3.api.scan
            s0 = scan.stats()
            st, got = self.c.request(
                "POST", f"/{BUCKET}/table.csv",
                {"select": "", "select-type": "2"}, body=xml)
            check(st == 200, f"Select -> {st} {got[:200]!r}")
            want = b"".join(event_stream(SelectRequest.from_xml(xml), csv))
            check(got == want, "Select answer differs from the CPU "
                  f"evaluator's ({len(got)} vs {len(want)} bytes)")
            s1 = scan.stats()
            reasons = {r: n for r, n in s1["fallback_reasons"].items()
                       if n != s0["fallback_reasons"].get(r, 0)}
            rec["csv_bytes"] = len(csv)
            rec["response_bytes"] = len(got)
            bad = {r for r in reasons if "error" in r}
            check(not bad, f"scan plane fell back on an error: {reasons}")
            if s1["device_serves"] > s0["device_serves"]:
                rec["outcome"] = "device"
            else:
                check(reasons, "Select took the CPU evaluator and the "
                      "plane named no reason")
                rec["outcome"] = "declined up front: " + ", ".join(reasons)
            self.report["select"] = rec["outcome"]

    def _close_out(self) -> None:
        """The scheduler's own account, read before shutdown."""
        with self.phase("evidence") as rec:
            st = self.node.scheduler.stats()
            rec["scheduler"] = st["verbs"]
            self.report["dispatch_errors"] += sum(st["errors"].values())
            check(not any(st["errors"].values()),
                  f"device dispatch errors: {st['errors']}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny sizes, device route forced onto XLA-CPU; "
                         "prints DRY RUN and can never pass")
    args = ap.parse_args(argv)
    args.object_size = 64 << 20
    args.block_size = 1 << 22
    args.select_rows = 280_000            # ~8 MiB of CSV
    t_start = time.perf_counter()

    try:
        import jax
        import jaxlib
        from minio_tpu.utils import device, native
    except ImportError as e:
        print(f"chip_smoke: the program is not importable here: {e}",
              file=sys.stderr)
        return EXIT_NO_CHIP

    dp = device.probe()                   # the program's one probe
    if args.dry_run_cpu:
        print(f"DRY RUN platform={dp.platform or 'none'}", flush=True)
        if dp.is_tpu:
            print("chip_smoke: --dry-run-cpu is for machines without a "
                  "chip; run without the flag here", file=sys.stderr)
            return 1
        from minio_tpu.object import codec as codec_mod
        codec_mod._device_is_tpu = lambda: True
        codec_mod.DEVICE_MIN_BYTES = 0
        args.block_size = 1 << 16
        args.object_size = 16 << 16
        args.select_rows = 3000
    elif not dp.is_tpu:
        print(f"chip_smoke: no chip found — {dp.reason}", file=sys.stderr)
        return EXIT_NO_CHIP

    # never outlive the driver's limit: a hung dispatch fails the run
    def expire() -> None:
        print(f"chip_smoke: still running after {DEADLINE_S}s — "
              "giving up", file=sys.stderr, flush=True)
        os._exit(1)
    watchdog = threading.Timer(DEADLINE_S, expire)
    watchdog.daemon = True
    watchdog.start()

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    dev = {"platform": dp.platform, "kind": dp.device_kind,
           "count": dp.count}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version,
                "python": sys.version.split()[0]}
    cache_dir, cache_ours = device.compile_cache_dir()
    cache_before = cache_entries(cache_dir)
    from minio_tpu.object.codec import data_path_line
    banner = data_path_line()
    print(f"device {dev}  versions {versions}  "
          f"native.available()={native.available()}  "
          f"compile cache {cache_dir}\n{banner}", flush=True)

    report: dict = {"ok": False, "device": dev, "data_path": banner,
                    "versions": versions,
                    "native": native.available(), "seed": args.seed,
                    "phases": [], "dispatch_errors": 0,
                    "lock_timeout_retries": 0, "select": None}
    ev = Evidence()
    failed: list[str] = []
    passes = (
        Pass("A", parity=4, n_puts=2 if args.dry_run_cpu else 8,
             n_degraded=2 if args.dry_run_cpu else 4,
             lose=lambda k: {0, min(5, k - 1), k + 1}, select=True,
             args=args, ev=ev, report=report),
        Pass("B", parity=None, n_puts=2, n_degraded=1,
             lose=lambda k: {0}, select=False,
             args=args, ev=ev, report=report),
    )
    for p in passes:
        try:
            p.run()
        except Exception as e:  # noqa: BLE001 — every failure is final
            failed.append(f"pass {p.name}: {type(e).__name__}: {e}")
            traceback.print_exc()

    # -- verdict from the program's own evidence ---------------------------
    from minio_tpu.ops import rs_tpu
    from minio_tpu.parallel import mesh as pmesh
    obs = ev.compute_observations()
    declines = ev.declines()
    for verb in ("encode", "decode", "recover"):
        if obs[verb] < 1:
            failed.append(f"no stage=compute observation for {verb}")
    bad = [d for d in declines if d.get("reason") in ("no-device", "error")]
    if bad and not args.dry_run_cpu:
        failed.append(f"device.decline events: {bad}")
    if native.available() is False and shutil.which("g++"):
        failed.append("native library unavailable although g++ exists: "
                      "every host-side number would be the numpy codec's")
    pallas = rs_tpu.default_use_pallas() and not dp.reason
    if not pallas and not args.dry_run_cpu:
        failed.append("the single-device route is not served by Pallas")
    mem = jax.devices()[0].memory_stats() or {}
    new = cache_entries(cache_dir) - cache_before
    report.update({
        "compute_observations": obs,
        "dispatch_stages": ev.dispatch_stages(),
        "gray_lane": ev.gray_lane(),
        "declines": declines,
        "pallas": pallas,
        "routes": {"single_device_dispatches": sum(
            obs[v] for v in ("encode", "decode", "recover"))
            - pmesh.DISPATCHES.value,
            "mesh_dispatches": pmesh.DISPATCHES.value,
            "scan_dispatches": obs["scan"]},
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "compile": {"seconds": round(ev.compile_s, 3),
                    "programs": ev.compiles,
                    "cache_dir": cache_dir,
                    "cache_placed_by": "program" if cache_ours
                    else "JAX_COMPILATION_CACHE_DIR",
                    "persistent_hits": ev.cache_hits,
                    "persistent_misses": ev.cache_misses,
                    "new_entries": len(new),
                    "new_erasure_entries": sorted(
                        e for e in new
                        if any(s in e for s in ERASURE_STEPS))},
        "wall_s": round(time.perf_counter() - t_start, 3),
        "failed": failed,
    })
    watchdog.cancel()
    if args.dry_run_cpu:
        report["dry_run"] = True          # ok stays False: not a pass
        print("DRY RUN platform=cpu — not a chip result", flush=True)
    else:
        report["ok"] = not failed
    report["claim"] = None
    for f in failed:
        print("FAILED:", f, file=sys.stderr)
    # the summary, then — last — the verdict in the driver's exact shape:
    # "ok" and the device as JAX reports it, nothing else
    print(REPORT_PREFIX + json.dumps(report), flush=True)
    d0 = jax.devices()[0]
    print(json.dumps({"ok": report["ok"], "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    if failed:
        return 1
    return EXIT_DRY_RUN_OK if args.dry_run_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
