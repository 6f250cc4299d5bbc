"""E2E device-path proof through the LIVE S3 server (VERDICT r2 item 6).

The default DEVICE_MIN_BYTES gate means a default-config server on a
CPU-only host never routes to the device in e2e; this test forces the
device route (XLA-CPU backend in tests — same code path as TPU) through
the FULL stack: HTTP SigV4 PUT -> handlers -> engine -> shared
BatchScheduler -> fused encode+digest device program -> bitrot-framed
shard writes, then HTTP GET (device-routed verify) and byte identity.
Scheduler coalescing counters prove concurrent streams shared device
dispatches (the cross-request batching of BASELINE config #2).

On a machine with a chip the same path is driven at real sizes by
chip_smoke.py; THIS test pins the integration's correctness in tier-1.
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

from minio_tpu.object import codec as codec_mod
from minio_tpu.object.sets import ErasureSets
from minio_tpu.parallel.scheduler import BatchScheduler
from minio_tpu.s3.server import S3Server

from tests.test_s3 import CREDS, REGION, S3TestClient

BLOCK = 1 << 16


@pytest.fixture()
def device_server(monkeypatch, tmp_path):
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    sched = BatchScheduler(max_wait=0.2)
    drives = [str(tmp_path / f"d{i}") for i in range(6)]
    sets = ErasureSets.from_drives(drives, set_count=1, set_drive_count=6,
                                   parity=2, block_size=BLOCK,
                                   scheduler=sched)
    srv = S3Server(sets, creds=CREDS, region=REGION).start()
    yield srv, sched
    srv.stop()
    sets.close()


def test_live_server_device_path_concurrent_puts(device_server):
    """16 concurrent PUT streams through the live server must ride the
    device path, coalesce into shared dispatches, and round-trip
    byte-identically."""
    srv, sched = device_server
    n_streams = 16
    payloads = {
        f"obj{i}": np.random.default_rng(i).integers(
            0, 256, 3 * BLOCK + i * 17, dtype=np.uint8).tobytes()
        for i in range(n_streams)}

    c0 = S3TestClient("127.0.0.1", srv.port)
    assert c0.request("PUT", "/devbkt")[0] == 200

    barrier = threading.Barrier(n_streams)
    errors: list = []

    def put(name: str, body: bytes) -> None:
        try:
            client = S3TestClient("127.0.0.1", srv.port)
            barrier.wait(30)
            st, _, _ = client.request("PUT", f"/devbkt/{name}", body=body)
            assert st == 200, f"PUT {name} -> {st}"
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=put, args=(n, b))
          for n, b in payloads.items()]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errors, errors[:3]
    assert sched.batches > 0

    # the shared scheduler must coalesce concurrent streams into shared
    # dispatches (the whole point of the cross-request batch former).
    # Thread overlap is load-dependent, so allow extra volleys before
    # calling it a failure.
    for round_ in range(5):
        if sched.coalesced > 0:
            break
        vb = threading.Barrier(n_streams)
        vs = []

        def volley(name):
            client = S3TestClient("127.0.0.1", srv.port)
            vb.wait(30)
            client.request("PUT", f"/devbkt/{name}",
                           body=payloads[name])

        vs = [threading.Thread(target=volley, args=(n,))
              for n in payloads]
        for t in vs:
            t.start()
        for t in vs:
            t.join(60)
    assert sched.coalesced > 0, \
        f"no coalescing across {n_streams} concurrent streams"

    # GET every object back byte-identically (device-routed verify)
    for name, body in payloads.items():
        st, _, got = c0.request("GET", f"/devbkt/{name}")
        assert st == 200 and got == body, f"roundtrip diverged: {name}"
