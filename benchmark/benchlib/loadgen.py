"""Closed-loop S3 clients, one per process, and the harness's handle on
them.

Run as a script this file IS one client: it never imports JAX or the
program, signs with the benchmark's own SigV4, and sends its next
request when the last one has answered. It records, per request,
start and end on CLOCK_MONOTONIC (system-wide on Linux, so the
harness's window marks are on the same clock), body bytes, status and
whether the answer was right; checks run after the end stamp, outside
every timed interval. Protocol, one line each way:

  child  -> "ready"            bodies, hashes and keys are prepared
  parent -> "go <port>"        start the loop
  child  -> "warm"             two requests have answered
  parent -> "stop"             finish the request in flight, then
  child  -> "records <json>"   {"records": [[t0, t1, bytes, status, ok,
                               key], ...], "errors": [...]}

Clients are processes because the server is Python too: threads in the
harness would share its GIL and the measurement would time the load
generator (PERF.md section 6, PR 21's smoke).
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import subprocess
import sys
import threading
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchlib import sigv4, traffic  # noqa: E402

REQUEST_TIMEOUT_S = 120.0
ACCESS_KEY, SECRET_KEY = "benchaccesskey", "benchsecretkey123"


# ---------------------------------------------------------------------------
# the client process
# ---------------------------------------------------------------------------

class Http:
    """One keep-alive connection, reopened after an error."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.conn = None

    def request(self, method: str, path: str, body: bytes, sha: str):
        hdrs = sigv4.sign(method, path, {}, f"{self.host}:{self.port}",
                          sha, ACCESS_KEY, SECRET_KEY)
        if self.conn is None:
            self.conn = http.client.HTTPConnection(
                self.host, self.port, timeout=REQUEST_TIMEOUT_S)
        try:
            self.conn.request(method, path, body=body, headers=hdrs)
            resp = self.conn.getresponse()
            data = resp.read()
            return resp.status, data, resp.getheader("ETag", "")
        except Exception:
            self.conn.close()
            self.conn = None
            raise


EMPTY_SHA = hashlib.sha256(b"").hexdigest()


def client_main(spec: dict) -> None:
    seed, me, mix = spec["seed"], spec["client"], spec["mix"]
    nbytes, pool_n = mix["object_bytes"], mix["distinct_bodies"]
    if mix["op"] == "PUT":
        bodies = [traffic.body(seed, me, i, nbytes) for i in range(pool_n)]
        shas = [hashlib.sha256(b).hexdigest() for b in bodies]
    else:
        bodies = [traffic.body(seed, -1, i, nbytes) for i in range(pool_n)]
        keys = traffic.populated_keys(seed, mix, spec["drives"])
        order = traffic.get_order(seed, me, len(keys))
    etags = ['"' + hashlib.md5(b).hexdigest() + '"' for b in bodies]
    print("ready", flush=True)
    go = sys.stdin.readline().split()
    if not go or go[0] != "go":
        return
    stop = threading.Event()
    threading.Thread(target=lambda: (sys.stdin.readline(), stop.set()),
                     daemon=True).start()
    http_ = Http("127.0.0.1", int(go[1]))
    records, errors, n = [], [], 0
    while not stop.is_set():
        if mix["op"] == "PUT":
            i = n % pool_n
            key = traffic.put_key(seed, me, n)
            args = ("PUT", f"/{traffic.BUCKET}/{key}", bodies[i], shas[i])
        else:
            j = next(order)
            i = j % pool_n
            key = keys[j]
            args = ("GET", f"/{traffic.BUCKET}/{key}", b"", EMPTY_SHA)
        t0 = time.monotonic()
        try:
            status, data, etag = http_.request(*args)
            t1 = time.monotonic()
            if mix["op"] == "PUT":
                ok = status == 200 and etag == etags[i]
                moved = nbytes
            else:
                ok = status == 200 and etag == etags[i] \
                    and data == bodies[i]
                moved = len(data)
            if not ok and len(errors) < 5:
                errors.append(f"{args[0]} {key} -> {status} "
                              f"{data[:160]!r} etag={etag}")
        except Exception as e:  # noqa: BLE001 — a failed request is a
            # result (counted), not the end of the client
            t1 = time.monotonic()
            status, ok, moved = -1, False, 0
            if len(errors) < 5:
                errors.append(f"{args[0]} {key}: {type(e).__name__}: {e}")
        records.append([t0, t1, moved, status, ok, key, i])
        n += 1
        if n == 2:
            print("warm", flush=True)
    print("records " + json.dumps({"records": records, "errors": errors}),
          flush=True)


# ---------------------------------------------------------------------------
# the harness's side
# ---------------------------------------------------------------------------

class Clients:
    """Starts the client processes (early: they prepare their bodies
    while the node boots and warms), runs the window, collects."""

    def __init__(self, mix: dict, seed: int, drives: int):
        self.procs = []
        for c in range(mix["clients"]):
            spec = {"seed": seed, "client": c, "mix": mix,
                    "drives": drives}
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 json.dumps(spec)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                bufsize=1))

    def _expect(self, word: str) -> None:
        for p in self.procs:
            line = p.stdout.readline().strip()
            if line != word:
                raise RuntimeError(f"client said {line[:200]!r}, "
                                   f"expected {word!r}")

    def _tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write(line + "\n")
            p.stdin.flush()

    def wait_ready(self) -> None:
        self._expect("ready")

    def start(self, port: int) -> None:
        """Loops start; returns when every client has had two answers."""
        self._tell(f"go {port}")
        self._expect("warm")

    def signal_stop(self) -> None:
        """Each client finishes the request in flight and sends no more."""
        self._tell("stop")

    def collect(self) -> tuple[list, list]:
        """-> (all records, first errors), clients gone."""
        records, errors = [], []
        for c, p in enumerate(self.procs):
            line = p.stdout.readline()
            if not line.startswith("records "):
                raise RuntimeError(f"client {c} gave no records: "
                                   f"{line[:200]!r}")
            got = json.loads(line[len("records "):])
            records += [r + [c] for r in got["records"]]
            errors += got["errors"]
        self.close()
        return records, errors

    def close(self) -> None:
        for p in self.procs:
            for f in (p.stdin, p.stdout):
                try:
                    f.close()
                except OSError:
                    pass
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        self.procs = []


if __name__ == "__main__":
    client_main(json.loads(sys.argv[1]))
