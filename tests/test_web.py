"""Web JSON-RPC control surface (reference cmd/web-handlers.go,
VERDICT r3 item 3): login→JWT, bucket/object RPCs with IAM
enforcement, URL tokens, presigned share URLs, upload/download web
paths, and the zip-of-prefix download — all over a live S3Server."""

from __future__ import annotations

import http.client
import io
import json
import time
import urllib.parse
import zipfile

import pytest

from minio_tpu.iam.sys import IAMSys
from minio_tpu.object.sets import ErasureSets
from minio_tpu.s3.server import S3Server
from minio_tpu.s3.web import jwt_encode, mount
from tests.test_s3 import CREDS, REGION


@pytest.fixture(scope="module")
def web_server(tmp_path_factory):
    root = tmp_path_factory.mktemp("webdrives")
    sets = ErasureSets.from_drives(
        [str(root / f"d{i}") for i in range(4)], 1, 4, 2,
        block_size=1 << 16)
    iam = IAMSys(sets, root_cred=CREDS)
    srv = S3Server(sets, creds=CREDS, region=REGION, iam=iam).start()
    mount(srv)
    yield srv, iam
    srv.stop()
    sets.close()


def _call(port, method, params=None, token="", rid=1, path="/minio/webrpc"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    hdrs = {"Content-Type": "application/json"}
    if token:
        hdrs["Authorization"] = f"Bearer {token}"
    conn.request("POST", path, body=json.dumps(
        {"jsonrpc": "2.0", "id": rid, "method": f"Web.{method}",
         "params": params or {}}), headers=hdrs)
    resp = conn.getresponse()
    out = json.loads(resp.read())
    conn.close()
    return out


def _http(port, method, path, body=b"", headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request(method, path, body=body, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    hdrs = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, hdrs, data


def _login(port, user=None, pwd=None):
    out = _call(port, "Login", {"username": user or CREDS.access_key,
                                "password": pwd or CREDS.secret_key})
    assert "result" in out, out
    return out["result"]["token"]


def test_login_and_failure_modes(web_server):
    srv, _iam = web_server
    token = _login(srv.port)
    assert token.count(".") == 2

    # wrong password
    out = _call(srv.port, "Login", {"username": CREDS.access_key,
                                    "password": "nope"})
    assert out["error"]["code"] == 403
    # unknown user
    out = _call(srv.port, "Login", {"username": "ghost",
                                    "password": "whatever"})
    assert out["error"]["code"] == 403
    # no token on an authenticated method
    out = _call(srv.port, "ListBuckets")
    assert "error" in out
    # garbage token
    out = _call(srv.port, "ListBuckets", token="aa.bb.cc")
    assert "error" in out
    # token signed with the wrong secret
    forged = jwt_encode({"sub": CREDS.access_key, "typ": "web",
                         "exp": time.time() + 600}, "wrong-secret")
    out = _call(srv.port, "ListBuckets", token=forged)
    assert "error" in out
    # expired token
    expired = jwt_encode({"sub": CREDS.access_key, "typ": "web",
                          "exp": time.time() - 5}, CREDS.secret_key)
    out = _call(srv.port, "ListBuckets", token=expired)
    assert "error" in out
    # URL token must not work as a session token
    out = _call(srv.port, "CreateURLToken", token=token)
    url_token = out["result"]["token"]
    out = _call(srv.port, "ListBuckets", token=url_token)
    assert "error" in out
    # unknown method
    out = _call(srv.port, "NoSuchThing", token=token)
    assert out["error"]["code"] == -32601


def test_malformed_inputs_get_json_errors(web_server):
    """Review r4: non-object JSON bodies/params and hostile object keys
    must produce JSON-RPC errors / sanitized headers, never aborted
    connections or header injection."""
    srv, _iam = web_server
    token = _login(srv.port)
    # non-dict request body
    st, _, data = _http(srv.port, "POST", "/minio/webrpc", body=b"[1]",
                        headers={"Content-Type": "application/json"})
    assert st == 200 and json.loads(data)["error"]["code"] == -32600
    # non-dict params
    out = _call(srv.port, "ListBuckets", params=None, token=token)
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request("POST", "/minio/webrpc", body=json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": "Web.ListBuckets",
         "params": "nope"}),
        headers={"Authorization": f"Bearer {token}"})
    resp = conn.getresponse()
    assert json.loads(resp.read())["error"]["code"] == -32602
    conn.close()
    # token with a non-dict payload segment
    bad = "e30.MTIz.e30"
    out = _call(srv.port, "ListBuckets", token=bad)
    assert "error" in out
    # a key with CRLF + quote must come back with sanitized
    # Content-Disposition (no header splitting)
    _call(srv.port, "MakeBucket", {"bucketName": "hostile"}, token=token)
    evil_key = 'a\r\nSet-Cookie: x="1'
    quoted = urllib.parse.quote(evil_key)
    st, _, _ = _http(srv.port, "PUT",
                     f"/minio/web/upload/hostile/{quoted}", body=b"v",
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": "1"})
    assert st == 200
    st, hdrs, data = _http(
        srv.port, "GET",
        f"/minio/web/download/hostile/{quoted}?token={token}")
    assert st == 200 and data == b"v"
    assert "set-cookie" not in hdrs
    assert "\r" not in hdrs["content-disposition"]


def test_bucket_and_object_rpcs(web_server):
    srv, _iam = web_server
    token = _login(srv.port)
    assert "result" in _call(srv.port, "MakeBucket",
                             {"bucketName": "webbucket"}, token=token)
    names = [b["name"] for b in _call(
        srv.port, "ListBuckets", token=token)["result"]["buckets"]]
    assert "webbucket" in names

    # upload two objects over the web path
    st, hdrs, _ = _http(srv.port, "PUT",
                        "/minio/web/upload/webbucket/dir/a.txt",
                        body=b"alpha",
                        headers={"Authorization": f"Bearer {token}",
                                 "Content-Type": "text/plain",
                                 "Content-Length": "5"})
    assert st == 200 and hdrs.get("etag")
    st, _, _ = _http(srv.port, "PUT",
                     "/minio/web/upload/webbucket/dir/b.bin",
                     body=b"beta!",
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": "5"})
    assert st == 200

    out = _call(srv.port, "ListObjects",
                {"bucketName": "webbucket", "prefix": "dir/"},
                token=token)["result"]
    assert [o["name"] for o in out["objects"]] == ["dir/a.txt",
                                                   "dir/b.bin"]

    # delimiter listing at the root shows the prefix
    out = _call(srv.port, "ListObjects", {"bucketName": "webbucket"},
                token=token)["result"]
    assert {o["name"] for o in out["objects"]} == {"dir/"}

    # download with a URL token (?token=, no headers)
    url_token = _call(srv.port, "CreateURLToken",
                      token=token)["result"]["token"]
    st, hdrs, data = _http(
        srv.port, "GET",
        f"/minio/web/download/webbucket/dir/a.txt?token={url_token}")
    assert st == 200 and data == b"alpha"
    assert "attachment" in hdrs.get("content-disposition", "")
    # no token -> denied
    st, _, _ = _http(srv.port, "GET",
                     "/minio/web/download/webbucket/dir/a.txt")
    assert st == 403

    # RemoveObject with a trailing-slash prefix removes recursively
    out = _call(srv.port, "RemoveObject",
                {"bucketName": "webbucket", "objects": ["dir/"]},
                token=token)
    assert "result" in out
    out = _call(srv.port, "ListObjects", {"bucketName": "webbucket"},
                token=token)["result"]
    assert out["objects"] == []


def test_zip_download_roundtrip(web_server):
    srv, _iam = web_server
    token = _login(srv.port)
    _call(srv.port, "MakeBucket", {"bucketName": "zipbucket"},
          token=token)
    payloads = {"docs/one.txt": b"one" * 1000,
                "docs/sub/two.txt": b"two" * 2000,
                "docs/three.bin": bytes(range(256)) * 64}
    for k, v in payloads.items():
        st, _, _ = _http(srv.port, "PUT",
                         f"/minio/web/upload/zipbucket/{k}", body=v,
                         headers={"Authorization": f"Bearer {token}",
                                  "Content-Length": str(len(v))})
        assert st == 200

    st, hdrs, data = _http(
        srv.port, "POST", f"/minio/web/zip?token={token}",
        body=json.dumps({"bucketName": "zipbucket", "prefix": "docs/",
                         "objects": [""]}).encode(),
        headers={"Content-Type": "application/json"})
    assert st == 200, data
    assert hdrs.get("content-type") == "application/zip"
    zf = zipfile.ZipFile(io.BytesIO(data))
    assert sorted(zf.namelist()) == ["one.txt", "sub/two.txt",
                                     "three.bin"] or \
        sorted(zf.namelist()) == sorted(
            k[len("docs/"):] for k in payloads)
    for k, v in payloads.items():
        assert zf.read(k[len("docs/"):]) == v

    # explicit object selection
    st, _, data = _http(
        srv.port, "POST", f"/minio/web/zip?token={token}",
        body=json.dumps({"bucketName": "zipbucket", "prefix": "docs/",
                         "objects": ["one.txt"]}).encode())
    assert st == 200
    zf = zipfile.ZipFile(io.BytesIO(data))
    assert zf.namelist() == ["one.txt"]


def test_iam_user_scoping_and_setauth(web_server):
    srv, iam = web_server
    root_token = _login(srv.port)
    _call(srv.port, "MakeBucket", {"bucketName": "rootonly"},
          token=root_token)

    iam.add_user("webuser", "webuser-secret-1")
    iam.attach_policy("readonly", user="webuser")
    utoken = _login(srv.port, "webuser", "webuser-secret-1")

    # readonly user: list allowed, create denied
    out = _call(srv.port, "ListBuckets", token=utoken)
    assert "result" in out
    out = _call(srv.port, "MakeBucket", {"bucketName": "userbucket"},
                token=utoken)
    assert out["error"]["code"] == 403
    # upload denied for readonly
    st, _, _ = _http(srv.port, "PUT",
                     "/minio/web/upload/rootonly/x",
                     body=b"x",
                     headers={"Authorization": f"Bearer {utoken}",
                              "Content-Length": "1"})
    assert st == 403

    # owner can't SetAuth, user can; old token dies with the rotation
    out = _call(srv.port, "SetAuth",
                {"currentSecretKey": CREDS.secret_key,
                 "newSecretKey": "irrelevant1"}, token=root_token)
    assert out["error"]["code"] == 403
    out = _call(srv.port, "SetAuth",
                {"currentSecretKey": "wrong",
                 "newSecretKey": "newsecret99"}, token=utoken)
    assert out["error"]["code"] == 403
    out = _call(srv.port, "SetAuth",
                {"currentSecretKey": "webuser-secret-1",
                 "newSecretKey": "newsecret99"}, token=utoken)
    assert "result" in out, out
    new_token = out["result"]["token"]
    assert "result" in _call(srv.port, "ListBuckets", token=new_token)
    # the pre-rotation token no longer verifies
    out = _call(srv.port, "ListBuckets", token=utoken)
    assert "error" in out
    assert _login(srv.port, "webuser", "newsecret99")


def test_web_download_transformed_objects(web_server):
    """ADVICE r4: web download/zip must route through the same
    SSE/compression seam as the S3 GET path — a compressed or SSE-S3
    object downloads as plaintext with the plaintext Content-Length;
    SSE-C downloads are rejected (no client key headers on a browser
    navigation)."""
    import hashlib
    import os
    from minio_tpu.features import crypto as sse
    from minio_tpu.features.kms import StaticKMS
    from minio_tpu.object.engine import PutOptions
    from minio_tpu.object.hash_reader import HashReader

    srv, _iam = web_server
    token = _login(srv.port)
    _call(srv.port, "MakeBucket", {"bucketName": "xform"}, token=token)
    old_kms = srv.api.kms
    srv.api.kms = StaticKMS(hashlib.sha256(b"web-master").digest())
    try:
        payload = b"web-plaintext " * 4096

        def put(key, ssec_key=None, sse_s3=False, compress=False):
            md = {}
            reader, size, _spec = sse.setup_put_transforms(
                key_name=key,
                raw_reader=HashReader(io.BytesIO(payload), len(payload)),
                raw_size=len(payload), metadata=md, ssec_key=ssec_key,
                sse_s3=sse_s3, kms=srv.api.kms, compress=compress)
            srv.api.obj.put_object("xform", key, reader, size,
                                   PutOptions(metadata=md))

        put("comp.txt", compress=True)
        put("enc.txt", sse_s3=True)
        put("both.txt", sse_s3=True, compress=True)
        put("ssec.txt", ssec_key=os.urandom(32))

        for k in ("comp.txt", "enc.txt", "both.txt"):
            st, hdrs, data = _http(
                srv.port, "GET",
                f"/minio/web/download/xform/{k}?token={token}")
            assert st == 200 and data == payload, k
            assert hdrs["content-length"] == str(len(payload))
        st, _, _ = _http(
            srv.port, "GET",
            f"/minio/web/download/xform/ssec.txt?token={token}")
        assert st == 403

        # the zip path decodes through the same seam
        st, _, data = _http(
            srv.port, "POST", f"/minio/web/zip?token={token}",
            body=json.dumps({"bucketName": "xform", "prefix": "",
                             "objects": ["comp.txt",
                                         "enc.txt"]}).encode())
        assert st == 200
        zf = zipfile.ZipFile(io.BytesIO(data))
        assert zf.read("comp.txt") == payload
        assert zf.read("enc.txt") == payload
    finally:
        srv.api.kms = old_kms


def test_url_token_scope_and_malformed_exp(web_server):
    """ADVICE r4: CreateURLToken tokens must not authorize uploads, and
    a token with a non-numeric exp claim is AccessDenied, not a 500."""
    srv, _iam = web_server
    token = _login(srv.port)
    _call(srv.port, "MakeBucket", {"bucketName": "scope"}, token=token)
    url_token = _call(srv.port, "CreateURLToken",
                      token=token)["result"]["token"]
    st, _, _ = _http(srv.port, "PUT", "/minio/web/upload/scope/x",
                     body=b"x",
                     headers={"Authorization": f"Bearer {url_token}",
                              "Content-Length": "1"})
    assert st == 403
    st, _, _ = _http(srv.port, "PUT", "/minio/web/upload/scope/x",
                     body=b"x",
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": "1"})
    assert st == 200
    # the url token's actual purpose still works
    st, _, data = _http(
        srv.port, "GET",
        f"/minio/web/download/scope/x?token={url_token}")
    assert st == 200 and data == b"x"
    bad = jwt_encode({"sub": CREDS.access_key, "typ": "web",
                      "exp": "never"}, CREDS.secret_key)
    out = _call(srv.port, "ListBuckets", token=bad)
    assert "error" in out
    assert out["error"].get("code") != 500


def test_presigned_get_and_policy_rpcs(web_server):
    srv, _iam = web_server
    token = _login(srv.port)
    _call(srv.port, "MakeBucket", {"bucketName": "sharebucket"},
          token=token)
    st, _, _ = _http(srv.port, "PUT",
                     "/minio/web/upload/sharebucket/shared.txt",
                     body=b"shared-payload",
                     headers={"Authorization": f"Bearer {token}",
                              "Content-Length": "14"})
    assert st == 200

    out = _call(srv.port, "PresignedGet",
                {"bucketName": "sharebucket",
                 "objectName": "shared.txt",
                 "hostName": f"127.0.0.1:{srv.port}", "expiry": 3600},
                token=token)["result"]
    url = out["url"]
    # the presigned URL works unauthenticated over plain HTTP
    path = url.split(str(srv.port), 1)[1]
    st, _, data = _http(srv.port, "GET", path)
    assert st == 200 and data == b"shared-payload"

    # canned bucket policy set + readback
    out = _call(srv.port, "SetBucketPolicy",
                {"bucketName": "sharebucket", "prefix": "",
                 "policy": "readonly"}, token=token)
    assert "result" in out
    out = _call(srv.port, "GetBucketPolicy",
                {"bucketName": "sharebucket", "prefix": ""},
                token=token)["result"]
    assert out["policy"] == "readonly"
    out = _call(srv.port, "ListAllBucketPolicies",
                {"bucketName": "sharebucket"}, token=token)["result"]
    assert {"prefix": "sharebucket/*", "policy": "readonly"} in \
        out["policies"]
    # anonymous GET now allowed by the bucket policy
    st, _, data = _http(srv.port, "GET", "/sharebucket/shared.txt")
    assert st == 200 and data == b"shared-payload"
    # back to none
    _call(srv.port, "SetBucketPolicy",
          {"bucketName": "sharebucket", "prefix": "", "policy": "none"},
          token=token)
    out = _call(srv.port, "GetBucketPolicy",
                {"bucketName": "sharebucket", "prefix": ""},
                token=token)["result"]
    assert out["policy"] == "none"
    st, _, _ = _http(srv.port, "GET", "/sharebucket/shared.txt")
    assert st == 403
