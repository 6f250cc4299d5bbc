"""Real-subprocess cluster harness for the crash matrix.

Every prior fault test killed THREADS inside one live process; the
durability claim ("an acknowledged write survives anything short of
losing quorum drives") is about PROCESS death. This harness spawns
actual ``python -m minio_tpu server`` processes over the HTTP edge,
seeds a crashpoint env per node (``MINIO_TPU_CRASHPOINT=<name>[:n]``
→ ``os._exit(137)`` at the Nth hit — see utils/crashpoint.py),
SIGKILLs, restarts, waits healthy, and hands back SigV4 S3/admin
clients bound to the node.

Fsync discipline (``MINIO_TPU_FSYNC=on``) is on by default so the
matrix exercises the barriers it exists to test. Drive directories
persist across restarts — that IS the point.
"""

from __future__ import annotations

import http.client
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Optional

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ACCESS_KEY = "harness"
SECRET_KEY = "harness-secret-key"
CRASH_EXIT_CODE = 137


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ProcTimeout(AssertionError):
    pass


class ProcNode:
    """One server process over a persistent drive directory."""

    def __init__(self, workdir: str, n_drives: int = 4,
                 port: Optional[int] = None, name: str = "node",
                 fsync: bool = True, pools: int = 1,
                 cluster_nodes: Optional[list[str]] = None,
                 this: int = 0,
                 extra_args: Optional[list[str]] = None):
        self.workdir = str(workdir)
        self.name = name
        self.n_drives = n_drives
        self.pools = pools
        self.port = port or free_port()
        self.fsync = fsync
        # multi-node form: the full --node spec list (identical on
        # every node) + this node's index; empty = single-node server
        self.cluster_nodes = list(cluster_nodes or [])
        self.this = this
        self.extra_args = list(extra_args or [])
        self.proc: Optional[subprocess.Popen] = None
        self.log_path = os.path.join(self.workdir, f"{name}.log")
        os.makedirs(self.workdir, exist_ok=True)

    @property
    def addr(self) -> str:
        """The node id this process speaks as on the cluster wire."""
        return f"127.0.0.1:{self.port}"

    # -- lifecycle ---------------------------------------------------------

    def drives(self, pool: int = 0) -> list[str]:
        tag = "" if pool == 0 else f"p{pool}"
        return [os.path.join(self.workdir, f"{self.name}{tag}d{i}")
                for i in range(self.n_drives)]

    def _env(self, crashpoint: Optional[str], extra_env: Optional[dict]
             ) -> dict:
        env = dict(os.environ)
        env.update({
            "MINIO_ACCESS_KEY": ACCESS_KEY,
            "MINIO_SECRET_KEY": SECRET_KEY,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                                  if env.get("PYTHONPATH") else ""),
            "MINIO_TPU_FSYNC": "on" if self.fsync else "off",
        })
        env.pop("MINIO_TPU_CRASHPOINT", None)
        if crashpoint:
            env["MINIO_TPU_CRASHPOINT"] = crashpoint
        env.update(extra_env or {})
        return env

    def start(self, crashpoint: Optional[str] = None,
              extra_env: Optional[dict] = None,
              wait: bool = True, timeout: float = 90.0) -> "ProcNode":
        assert self.proc is None or self.proc.poll() is not None, \
            "node already running"
        if self.cluster_nodes:
            cmd = [sys.executable, "-m", "minio_tpu", "server"]
            for spec in self.cluster_nodes:
                cmd += ["--node", spec]
            cmd += ["--this", str(self.this)]
        else:
            cmd = [sys.executable, "-m", "minio_tpu", "server",
                   *self.drives(0), "--address", f"127.0.0.1:{self.port}"]
        cmd += self.extra_args
        for p in range(1, self.pools):
            base = os.path.join(self.workdir, f"{self.name}p{p}d")
            cmd += ["--pool",
                    base + "{0..." + str(self.n_drives - 1) + "}"]
        self._log = open(self.log_path, "ab")
        self._log.write(f"\n==== start crashpoint={crashpoint!r} "
                        f"====\n".encode())
        self._log.flush()
        self.proc = subprocess.Popen(
            cmd, env=self._env(crashpoint, extra_env),
            stdout=self._log, stderr=subprocess.STDOUT,
            cwd=self.workdir)
        if wait:
            self.wait_healthy(timeout)
            if self.pools > 1:
                self._wait_pools(timeout)
        return self

    def _wait_pools(self, timeout: float = 90.0) -> None:
        """Health goes ready BEFORE the CLI's --pool attach runs; a
        multi-pool scenario must not race the expansion."""
        from minio_tpu.madmin import AdminClientError
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                topo = self.admin().topology()
                if len(topo.get("pools", [])) >= self.pools:
                    return
            except (OSError, AdminClientError):
                pass
            time.sleep(0.2)
        raise ProcTimeout(
            f"{self.name}: {self.pools} pools never attached:\n"
            + self.tail_log())

    def wait_healthy(self, timeout: float = 90.0) -> None:
        """Ready = health endpoint green AND the late-boot subsystems
        (replication plane, tier registry — the LAST things cluster
        boot wires) answer their admin verbs: /minio/health/ready goes
        green as soon as the object layer mounts, well before the
        admin surface the crash triggers drive exists."""
        from minio_tpu.madmin import AdminClientError
        deadline = time.monotonic() + timeout
        healthy = False
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                raise AssertionError(
                    f"{self.name} exited rc={rc} during boot:\n"
                    + self.tail_log())
            try:
                if not healthy:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=2)
                    conn.request("GET", "/minio/health/ready")
                    healthy = conn.getresponse().status == 200
                    conn.close()
                if healthy:
                    self.admin().replicate_status()
                    self.admin().list_tiers()
                    return
            except OSError:
                pass
            except AdminClientError as e:
                if e.status != 501:
                    return      # wired, just unhappy — boot is done
            time.sleep(0.2)
        raise ProcTimeout(f"{self.name} not healthy in {timeout}s:\n"
                          + self.tail_log())

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def wait_exit(self, timeout: float = 60.0) -> int:
        """Block until the process dies (an armed crashpoint fired) —
        returns the exit code (137 for a fired crashpoint)."""
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise ProcTimeout(
                f"{self.name} still alive after {timeout}s waiting "
                f"for a crash:\n" + self.tail_log()) from None

    def kill(self) -> None:
        """SIGKILL — no shutdown hooks, no flushes."""
        if self.alive():
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(30)

    def pause(self) -> None:
        """SIGSTOP — the process freezes mid-flight (a GC-pause /
        overloaded-VM stand-in): sockets stay open, peers see
        timeouts, not resets. Pair with resume()."""
        if self.alive():
            self.proc.send_signal(signal.SIGSTOP)

    def resume(self) -> None:
        """SIGCONT a paused node."""
        if self.alive():
            self.proc.send_signal(signal.SIGCONT)

    def stop(self, timeout: float = 30.0) -> None:
        """Graceful SIGTERM stop (for seeding phases)."""
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.kill()

    def close(self) -> None:
        self.kill()
        try:
            self._log.close()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass

    def tail_log(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(max(os.path.getsize(self.log_path) - n, 0))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"

    # -- clients -----------------------------------------------------------

    def s3(self):
        from minio_tpu.s3.credentials import Credentials
        from minio_tpu.utils.s3client import S3Client
        return S3Client("127.0.0.1", self.port,
                        Credentials(ACCESS_KEY, SECRET_KEY),
                        timeout=30.0)

    def admin(self):
        from minio_tpu.madmin import AdminClient
        return AdminClient("127.0.0.1", self.port, ACCESS_KEY,
                           SECRET_KEY)

    # -- harness verbs -----------------------------------------------------

    def put(self, bucket: str, key: str, body: bytes) -> str:
        return self.s3().put_object(bucket, key, body)

    def get(self, bucket: str, key: str) -> bytes:
        _h, stream = self.s3().get_object(bucket, key)
        return b"".join(stream)

    def exists(self, bucket: str, key: str) -> bool:
        from minio_tpu.utils.s3client import S3ClientError
        try:
            self.s3().head_object(bucket, key)
            return True
        except S3ClientError as e:
            if e.status in (404, 410):
                return False
            raise

    def multipart(self, bucket: str, key: str, parts: list[bytes]
                  ) -> None:
        """Raw multipart flow over the wire (the S3Client has no MPU
        verbs; crash tests need the real HTTP surface)."""
        cli = self.s3()
        _h, body = cli._request("POST", f"/{bucket}/{key}",
                                query={"uploads": ""})
        import xml.etree.ElementTree as ET
        root = ET.fromstring(body)
        uid = None
        for el in root.iter():
            if el.tag.endswith("UploadId"):
                uid = el.text
        assert uid, body
        etags = []
        for i, part in enumerate(parts, start=1):
            h, _ = cli._request(
                "PUT", f"/{bucket}/{key}",
                query={"partNumber": str(i), "uploadId": uid},
                body=part)
            etags.append(h.get("etag", "").strip('"'))
        xml = "<CompleteMultipartUpload>" + "".join(
            f"<Part><PartNumber>{i}</PartNumber><ETag>{e}</ETag></Part>"
            for i, e in enumerate(etags, start=1)
        ) + "</CompleteMultipartUpload>"
        cli._request("POST", f"/{bucket}/{key}",
                     query={"uploadId": uid}, body=xml.encode())

    def fsck(self, repair: bool = True) -> dict:
        return self.admin().fsck(repair=repair, tmp_age_s=0)

    def naughtynet(self, payload: dict) -> dict:
        """Drive this node's in-process network fault injector (the
        node must run with MINIO_TPU_NAUGHTYNET=on in extra_env)."""
        return self.admin().naughtynet(payload)

    def list_keys(self, bucket: str) -> list[str]:
        objs, _prefixes, _token = self.s3().list_objects_v2(bucket)
        return sorted(o["key"] for o in objs)

    def listing(self, bucket: str) -> list[tuple[str, int, str]]:
        """(key, size, etag) rows — the convergence-comparison form."""
        objs, _prefixes, _token = self.s3().list_objects_v2(bucket)
        return sorted((o["key"], o["size"], o["etag"]) for o in objs)


def make_cluster(workdir: str, n_nodes: int = 2, n_drives: int = 4,
                 parity: Optional[int] = None,
                 set_drive_count: int = 0,
                 extra_args: Optional[list[str]] = None
                 ) -> list[ProcNode]:
    """Build (without starting) a real-subprocess multi-node cluster:
    every node gets the same --node spec list and its own --this index.
    Drives live under workdir/<name>d<i> exactly like single-node
    harness runs, so logs and data are inspectable after a failure."""
    nodes = [ProcNode(workdir, n_drives=n_drives, name=f"n{i}")
             for i in range(n_nodes)]
    specs = []
    for n in nodes:
        spec = ",".join(n.drives(0))
        specs.append(f"127.0.0.1:{n.port}={spec}")
    args = list(extra_args or [])
    if parity is not None:
        args += ["--parity", str(parity)]
    if set_drive_count:
        args += ["--set-drive-count", str(set_drive_count)]
    for i, n in enumerate(nodes):
        n.cluster_nodes = specs
        n.this = i
        n.extra_args = args
    return nodes


def partition(a: ProcNode, b: ProcNode, oneway: bool = False) -> None:
    """Sever the a<->b link on BOTH processes' injectors (each side
    blocks its own outbound AND refuses the other's inbound — the
    partition holds regardless of which side initiates a call).
    ``oneway=True`` models an asymmetric failure: a can reach b, b
    cannot reach a."""
    if not oneway:
        a.naughtynet({"op": "partition", "src": a.addr, "dst": b.addr})
        b.naughtynet({"op": "partition", "src": a.addr, "dst": b.addr})
        return
    # one-way b->a dead: b blocks its outbound to a, a refuses b's
    # inbound; the a->b direction stays untouched on both sides
    b.naughtynet({"op": "partition", "src": b.addr, "dst": a.addr,
                  "oneway": True})
    a.naughtynet({"op": "partition", "src": b.addr, "dst": a.addr,
                  "oneway": True})


def heal(*nodes: ProcNode) -> None:
    """Clear every partition rule on the given nodes."""
    for n in nodes:
        if n.alive():
            n.naughtynet({"op": "heal"})


def expect_request_death(fn) -> None:
    """Run a client call whose server is armed to die mid-request:
    any connection-level error (reset, EOF, refused on retry) is the
    EXPECTED outcome; a clean success is allowed only when the crash
    fires after the response commit (callers assert the process died
    separately)."""
    from minio_tpu.utils.s3client import S3ClientError
    try:
        fn()
    except (OSError, http.client.HTTPException, S3ClientError,
            ConnectionError):
        return

