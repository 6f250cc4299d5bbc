"""Device-fused SSE data path: engine PUT byte-identity vs the CPU
cipher oracle, fallback discipline (knob off / deviceless / dispatch
error), host-side tag authentication of device output, and cross-path
e2e (device-written read by CPU and vice versa) over the live S3
server. (Cross-request coalescing of encrypted PUTs under different
keys: tests/test_scheduler.py, the fused programs' parametrised test.)"""

from __future__ import annotations

import base64
import hashlib
import http.client
import io
import os
import urllib.parse

import numpy as np
import pytest

from minio_tpu.features import crypto as sse
from minio_tpu.object import ErasureSetObjects
from minio_tpu.object import codec as codec_mod
from minio_tpu.object import engine as engine_mod
from minio_tpu.ops import chacha20_ref as c20
from minio_tpu.parallel.scheduler import BatchScheduler
from minio_tpu.storage import XLStorage, new_format_erasure_v3

K, M = 4, 2
NDISKS = K + M
BLOCK = 1 << 16
PKG = sse.PKG_SIZE


@pytest.fixture
def device_on(monkeypatch):
    """Run the device route on the CPU JAX backend: the fused programs
    jit and execute identically; only placement differs."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE_MIN_BYTES", "0")
    monkeypatch.setenv("MINIO_TPU_SSE_CIPHER", "chacha20")


def make_engine(tmp_path, sub="", scheduler=None):
    fmts = new_format_erasure_v3(1, NDISKS)
    disks = []
    for j in range(NDISKS):
        d = XLStorage(str(tmp_path / f"{sub}d{j}"))
        d.write_format(fmts[0][j])
        disks.append(d)
    e = ErasureSetObjects(disks, K, M, block_size=BLOCK,
                          scheduler=scheduler)
    e.make_bucket("b")
    return e


def payload(n, seed=7):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def read_stored(eng, name):
    _, it = eng.get_object("b", name)
    return b"".join(it)


def cpu_oracle(pt, oek, base):
    enc = sse.ChaChaEncryptor(oek, base)
    return enc.update(pt) + enc.finalize()


OEK = bytes(range(32))
BASE = bytes(range(100, 112))


# ---------------------------------------------------------------------------
# engine PUT byte-identity: fused device path == CPU cipher oracle
# ---------------------------------------------------------------------------

def test_fused_put_stored_bytes_match_cpu_oracle(tmp_path, device_on):
    eng = make_engine(tmp_path)
    assert eng.supports_sse_device
    for i, n in enumerate((0, 100, BLOCK, 3 * BLOCK + 17)):
        pt = payload(n, seed=i)
        opts = engine_mod.PutOptions(sse_spec=sse.DeviceSSE(OEK, BASE))
        info = eng.put_object("b", f"o{i}", pt, opts=opts)
        want = cpu_oracle(pt, OEK, BASE)
        assert info.size == sse.encrypted_size(n)
        assert read_stored(eng, f"o{i}") == want, n


def test_fused_put_pipelined_unknown_size(tmp_path, device_on):
    eng = make_engine(tmp_path)
    n = 5 * BLOCK + PKG + 123
    pt = payload(n, seed=42)
    opts = engine_mod.PutOptions(sse_spec=sse.DeviceSSE(OEK, BASE))
    eng.put_object("b", "o", io.BytesIO(pt), size=-1, opts=opts)
    assert read_stored(eng, "o") == cpu_oracle(pt, OEK, BASE)


def test_fused_put_through_scheduler(tmp_path, device_on):
    sched = BatchScheduler()
    try:
        eng = make_engine(tmp_path, scheduler=sched)
        n = 2 * BLOCK + 99
        pt = payload(n, seed=3)
        opts = engine_mod.PutOptions(sse_spec=sse.DeviceSSE(OEK, BASE))
        eng.put_object("b", "o", pt, opts=opts)
        assert read_stored(eng, "o") == cpu_oracle(pt, OEK, BASE)
        assert sched.verb_stats["encode"]["batches"] >= 1
    finally:
        sched.close()


def test_device_tags_verify_with_scalar_reference(tmp_path, device_on):
    """No laundered auth: the trailer committed by the DEVICE path must
    open every package under the independent scalar AEAD reference —
    the tags were computed host-side over the ciphertext actually
    written, before commit."""
    eng = make_engine(tmp_path)
    n = 2 * BLOCK + 500
    pt = payload(n, seed=9)
    eng.put_object("b", "o", pt,
                   opts=engine_mod.PutOptions(
                       sse_spec=sse.DeviceSSE(OEK, BASE)))
    stored = read_stored(eng, "o")
    ct_len, npkg = sse.chacha_ct_len(len(stored))
    assert ct_len == n
    got = b""
    for seq in range(npkg):
        pkg_ct = stored[seq * PKG:min((seq + 1) * PKG, ct_len)]
        tag = stored[ct_len + seq * 16:ct_len + (seq + 1) * 16]
        got += c20.open_detached(OEK, sse._pkg_nonce(BASE, seq),
                                 sse._pkg_aad(seq), pkg_ct, tag)
    assert got == pt


# ---------------------------------------------------------------------------
# fallback discipline
# ---------------------------------------------------------------------------

def test_knob_off_disables_device_path(monkeypatch, device_on):
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")
    assert not sse.device_sse_allowed(1 << 20)


def test_deviceless_declines(monkeypatch):
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: False)
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE_MIN_BYTES", "0")
    assert not sse.device_sse_allowed(1 << 20)


def test_size_window_gates(monkeypatch, device_on):
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE_MIN_BYTES", str(1 << 20))
    assert not sse.device_sse_allowed((1 << 20) - 1)
    assert sse.device_sse_allowed(1 << 20)
    assert not sse.device_sse_allowed(-1)    # unknown size: CPU path
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE_MAX_BYTES", str(1 << 21))
    assert not sse.device_sse_allowed(1 << 22)


def test_dispatch_error_falls_back_to_cpu_cipher(tmp_path, device_on,
                                                 monkeypatch):
    """ANY device dispatch error must drop the batch to the in-place
    CPU cipher — stored bytes stay byte-identical to the oracle."""
    def boom(self, *a, **k):
        raise RuntimeError("injected dispatch failure")
    monkeypatch.setattr(codec_mod.Codec, "encrypt_encode_and_hash_batch",
                        boom)
    eng = make_engine(tmp_path)
    n = 2 * BLOCK + 1234
    pt = payload(n, seed=5)
    eng.put_object("b", "o", pt,
                   opts=engine_mod.PutOptions(
                       sse_spec=sse.DeviceSSE(OEK, BASE)))
    assert read_stored(eng, "o") == cpu_oracle(pt, OEK, BASE)


def test_dispatch_error_through_scheduler_falls_back(tmp_path, device_on,
                                                     monkeypatch):
    def boom(self, *a, **k):
        raise RuntimeError("injected dispatch failure")
    monkeypatch.setattr(codec_mod.Codec, "encrypt_encode_and_hash_batch",
                        boom)
    sched = BatchScheduler()
    try:
        eng = make_engine(tmp_path, scheduler=sched)
        pt = payload(BLOCK + 77, seed=6)
        eng.put_object("b", "o", pt,
                       opts=engine_mod.PutOptions(
                           sse_spec=sse.DeviceSSE(OEK, BASE)))
        assert read_stored(eng, "o") == cpu_oracle(pt, OEK, BASE)
    finally:
        sched.close()


def test_setup_put_transforms_gates_spec(monkeypatch, device_on):
    """spec only when chacha + device_sse + gate; otherwise the cipher
    stays a CPU transform and the stream carries ciphertext."""
    from minio_tpu.features.kms import StaticKMS
    from minio_tpu.object.hash_reader import HashReader
    kms = StaticKMS(hashlib.sha256(b"m").digest())

    def setup(**over):
        md = {}
        kw = dict(key_name="k", raw_reader=HashReader(io.BytesIO(b"x"), 1),
                  raw_size=1, metadata=md, ssec_key=None, sse_s3=True,
                  kms=kms, compress=False, device_sse=True)
        kw.update(over)
        return sse.setup_put_transforms(**kw), md

    (_, size, spec), md = setup()
    assert isinstance(spec, sse.DeviceSSE)
    assert size == sse.encrypted_size(1)
    assert md[sse.MK_CIPHER] == sse.CIPHER_CHACHA

    (_, _, spec), _ = setup(device_sse=False)
    assert spec is None
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")
    (_, _, spec), _ = setup()
    assert spec is None
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "on")
    monkeypatch.setenv("MINIO_TPU_SSE_CIPHER", "aes-gcm")
    try:
        (_, _, spec), _ = setup()
    except ModuleNotFoundError:
        pytest.skip("cryptography not installed: AES seal path "
                    "environmentally untestable")
    assert spec is None


# ---------------------------------------------------------------------------
# cross-path e2e over the live S3 server
# ---------------------------------------------------------------------------

from minio_tpu.object.sets import ErasureSets
from minio_tpu.s3 import signature as sig
from minio_tpu.s3.credentials import Credentials
from minio_tpu.s3.server import S3Server

CREDS = Credentials("ssedevkey1", "ssedevsecret1")
REGION = "us-east-1"


@pytest.fixture()
def server(tmp_path):
    sets = ErasureSets.from_drives(
        [str(tmp_path / f"d{i}") for i in range(NDISKS)],
        set_count=1, set_drive_count=NDISKS, parity=M,
        block_size=BLOCK)
    srv = S3Server(sets, creds=CREDS, region=REGION).start()
    from minio_tpu.features.kms import StaticKMS
    srv.api.kms = StaticKMS(hashlib.sha256(b"m").digest())
    yield srv
    srv.stop()
    sets.close()


def _req(srv, method, path, query=None, body=b"", headers=None):
    query = {k: [v] for k, v in (query or {}).items()}
    qs = urllib.parse.urlencode({k: v[0] for k, v in query.items()})
    hdrs = {k.lower(): v for k, v in (headers or {}).items()}
    hdrs["host"] = f"127.0.0.1:{srv.port}"
    ph = hashlib.sha256(body).hexdigest()
    hdrs = sig.sign_v4(method, urllib.parse.quote(path), query, hdrs,
                       ph, CREDS, REGION)
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request(method, urllib.parse.quote(path) +
                 (f"?{qs}" if qs else ""), body=body, headers=hdrs)
    r = conn.getresponse()
    data = r.read()
    out = {k.lower(): v for k, v in r.getheaders()}
    conn.close()
    return r.status, out, data


def test_device_written_cpu_read_and_vice_versa(server, device_on,
                                                monkeypatch):
    st, _, _ = _req(server, "PUT", "/xbb")
    assert st == 200
    pt = payload(2 * BLOCK + 4321, seed=8)
    enc_hdr = {"x-amz-server-side-encryption": "AES256"}

    # device-fused PUT …
    st, _, _ = _req(server, "PUT", "/xbb/dev", body=pt, headers=enc_hdr)
    assert st == 200
    # … read back through the pure-CPU decrypt path
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "off")
    st, _, got = _req(server, "GET", "/xbb/dev")
    assert st == 200 and got == pt
    st, _, got = _req(server, "GET", "/xbb/dev",
                      headers={"range": f"bytes={PKG + 10}-{PKG + 200}"})
    assert st == 206 and got == pt[PKG + 10:PKG + 201]

    # CPU-transform PUT (device off) …
    st, _, _ = _req(server, "PUT", "/xbb/cpu", body=pt, headers=enc_hdr)
    assert st == 200
    # … read back with the device decipher batches enabled
    monkeypatch.setenv("MINIO_TPU_SSE_DEVICE", "on")
    st, _, got = _req(server, "GET", "/xbb/cpu")
    assert st == 200 and got == pt


def test_ssec_chacha_over_server(server, device_on):
    st, _, _ = _req(server, "PUT", "/xbb")
    assert st == 200
    key = os.urandom(32)
    hdrs = {
        "x-amz-server-side-encryption-customer-algorithm": "AES256",
        "x-amz-server-side-encryption-customer-key":
            base64.b64encode(key).decode(),
        "x-amz-server-side-encryption-customer-key-md5":
            base64.b64encode(hashlib.md5(key).digest()).decode(),
    }
    pt = payload(BLOCK + 999, seed=10)
    st, _, _ = _req(server, "PUT", "/xbb/sc", body=pt, headers=hdrs)
    assert st == 200
    st, _, got = _req(server, "GET", "/xbb/sc", headers=hdrs)
    assert st == 200 and got == pt
    st, _, _ = _req(server, "GET", "/xbb/sc")
    assert st in (400, 403)
