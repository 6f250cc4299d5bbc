"""GCS gateway, JSON API mode (VERDICT r4 #6): an in-process GCS fake
speaking the storage/v1 JSON API (+ OAuth token endpoint) exercises
object CRUD, listing, error mapping, and the compose-based multipart —
matching cmd/gateway/gcs/gateway-gcs.go behavior."""

from __future__ import annotations

import base64
import hashlib
import http.server
import json
import re
import threading
import urllib.parse

import pytest

from minio_tpu.gateway import new_gateway
from minio_tpu.gateway import gcs as gcs_mod
from minio_tpu.object import api_errors
from minio_tpu.object.engine import PutOptions
from minio_tpu.object.multipart import CompletePart


class FakeGCS(http.server.BaseHTTPRequestHandler):
    """storage/v1 JSON API subset + OAuth2 token endpoint."""

    buckets: dict = {}          # name -> {objects: {name: obj}}
    tokens_issued: int = 0
    compose_calls: list = []
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    # -- helpers -----------------------------------------------------------

    def _json(self, status: int, payload: dict) -> None:
        body = json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _err(self, status: int, reason: str, msg: str = "") -> None:
        self._json(status, {"error": {
            "code": status, "message": msg or reason,
            "errors": [{"reason": reason, "message": msg or reason}]}})

    def _authed(self) -> bool:
        auth = self.headers.get("Authorization", "")
        if auth != "Bearer fake-gcs-token":
            self._err(401, "authError", "bad token")
            return False
        return True

    def _body(self) -> bytes:
        n = int(self.headers.get("Content-Length", 0) or 0)
        return self.rfile.read(n)

    @staticmethod
    def _obj_json(name: str, obj: dict) -> dict:
        out = {"name": name, "bucket": obj["bucket"],
               "size": str(len(obj["data"])),
               "etag": obj["etag"],
               "contentType": obj.get("contentType", ""),
               "metadata": obj.get("metadata", {}),
               "updated": "2026-07-30T12:00:00Z",
               "timeCreated": "2026-07-30T12:00:00Z"}
        if obj.get("md5") is not None:
            out["md5Hash"] = base64.b64encode(obj["md5"]).decode()
        return out

    def _route(self):
        u = urllib.parse.urlsplit(self.path)
        q = {k: v[0] for k, v in
             urllib.parse.parse_qs(u.query).items()}
        return u.path, q

    # -- verbs -------------------------------------------------------------

    def do_POST(self):
        path, q = self._route()
        if path == "/token":
            body = urllib.parse.parse_qs(self._body().decode())
            assertion = body.get("assertion", [""])[0]
            grant = body.get("grant_type", [""])[0]
            if grant != "urn:ietf:params:oauth:grant-type:jwt-bearer" \
                    or assertion.count(".") != 2:
                return self._err(400, "invalid_grant")
            # validate the JWT claims are well-formed (unverified)
            claims = json.loads(base64.urlsafe_b64decode(
                assertion.split(".")[1] + "=="))
            if not claims.get("iss") or not claims.get("scope"):
                return self._err(400, "invalid_grant")
            type(self).tokens_issued += 1
            return self._json(200, {"access_token": "fake-gcs-token",
                                    "expires_in": 3600})
        if not self._authed():
            return
        if path == "/storage/v1/b":
            name = json.loads(self._body()).get("name", "")
            if name in self.buckets:
                return self._err(409, "conflict", "bucket exists")
            self.buckets[name] = {}
            return self._json(200, {"name": name,
                                    "timeCreated":
                                        "2026-07-30T12:00:00Z"})
        m = re.match(r"^/upload/storage/v1/b/([^/]+)/o$", path)
        if m and q.get("uploadType") == "multipart":
            bucket = urllib.parse.unquote(m.group(1))
            if bucket not in self.buckets:
                return self._err(404, "notFound", "no bucket")
            ctype = self.headers.get("Content-Type", "")
            bm = re.search(r'boundary=([^\s;]+)', ctype)
            parts = self._body().split(
                b"--" + bm.group(1).encode())
            # parts[1] = json meta, parts[2] = media
            def _payload(raw: bytes) -> bytes:
                return raw.split(b"\r\n\r\n", 1)[1].rsplit(
                    b"\r\n", 1)[0]
            meta = json.loads(_payload(parts[1]))
            data = _payload(parts[2])
            mt = re.search(rb"Content-Type:\s*([^\r\n]+)", parts[2])
            obj = {"bucket": bucket, "data": data,
                   "md5": hashlib.md5(data).digest(),
                   "etag": f"W/\"{hashlib.md5(data).hexdigest()}\"",
                   "contentType": meta.get(
                       "contentType",
                       mt.group(1).decode() if mt else ""),
                   "metadata": meta.get("metadata", {})}
            self.buckets[bucket][meta["name"]] = obj
            return self._json(200, self._obj_json(meta["name"], obj))
        m = re.match(r"^/storage/v1/b/([^/]+)/o/(.+)/compose$", path)
        if m:
            bucket = urllib.parse.unquote(m.group(1))
            dst = urllib.parse.unquote(m.group(2))
            if bucket not in self.buckets:
                return self._err(404, "notFound", "no bucket")
            req = json.loads(self._body())
            sources = [s["name"] for s in req.get("sourceObjects", [])]
            if len(sources) > 32:
                return self._err(400, "invalid",
                                 "too many compose components")
            type(self).compose_calls.append((dst, list(sources)))
            data = b""
            for s in sources:
                src = self.buckets[bucket].get(s)
                if src is None:
                    return self._err(404, "notFound", f"missing {s}")
                data += src["data"]
            dest_meta = req.get("destination", {})
            obj = {"bucket": bucket, "data": data, "md5": None,
                   "etag": f"W/\"composite-{len(data)}\"",
                   "contentType": dest_meta.get("contentType", ""),
                   "metadata": dest_meta.get("metadata", {})}
            self.buckets[bucket][dst] = obj
            return self._json(200, self._obj_json(dst, obj))
        return self._err(404, "notFound", path)

    def do_GET(self):
        if not self._authed():
            return
        path, q = self._route()
        if path == "/storage/v1/b":
            return self._json(200, {"items": [
                {"name": b, "timeCreated": "2026-07-30T12:00:00Z"}
                for b in sorted(self.buckets)]})
        m = re.match(r"^/storage/v1/b/([^/]+)$", path)
        if m:
            b = urllib.parse.unquote(m.group(1))
            if b not in self.buckets:
                return self._err(404, "notFound", "no bucket")
            return self._json(200, {
                "name": b, "timeCreated": "2026-07-30T12:00:00Z"})
        m = re.match(r"^/storage/v1/b/([^/]+)/o$", path)
        if m:
            return self._list(urllib.parse.unquote(m.group(1)), q)
        m = re.match(r"^/storage/v1/b/([^/]+)/o/([^/]+)$", path)
        if m:
            bucket = urllib.parse.unquote(m.group(1))
            name = urllib.parse.unquote(m.group(2))
            obj = self.buckets.get(bucket, {}).get(name)
            if obj is None:
                return self._err(404, "notFound", "no object")
            if q.get("alt") == "media":
                data = obj["data"]
                status = 200
                rng = self.headers.get("Range", "")
                rm = re.match(r"bytes=(\d+)-(\d*)$", rng)
                if rm:
                    lo = int(rm.group(1))
                    hi = int(rm.group(2)) if rm.group(2) else \
                        len(data) - 1
                    data = data[lo:hi + 1]
                    status = 206
                self.send_response(status)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
                return
            return self._json(200, self._obj_json(name, obj))
        return self._err(404, "notFound", path)

    def _list(self, bucket: str, q: dict) -> None:
        if bucket not in self.buckets:
            return self._err(404, "notFound", "no bucket")
        prefix = q.get("prefix", "")
        delim = q.get("delimiter", "")
        start = q.get("startOffset", "")
        maxr = int(q.get("maxResults", 1000))
        token = int(q.get("pageToken", 0) or 0)
        names = sorted(n for n in self.buckets[bucket]
                       if n.startswith(prefix) and n >= start)
        items, prefixes = [], []
        for n in names:
            if delim:
                rest = n[len(prefix):]
                if delim in rest:
                    p = prefix + rest.split(delim, 1)[0] + delim
                    if p not in prefixes:
                        prefixes.append(p)
                    continue
            items.append(n)
        page = items[token:token + maxr]
        out = {"items": [self._obj_json(n, self.buckets[bucket][n])
                         for n in page],
               "prefixes": prefixes}
        if token + maxr < len(items):
            out["nextPageToken"] = str(token + maxr)
        self._json(200, out)

    def do_DELETE(self):
        if not self._authed():
            return
        path, _q = self._route()
        m = re.match(r"^/storage/v1/b/([^/]+)$", path)
        if m:
            b = urllib.parse.unquote(m.group(1))
            if b not in self.buckets:
                return self._err(404, "notFound", "no bucket")
            if self.buckets[b]:
                return self._err(409, "conflict",
                                 "The bucket you tried to delete is "
                                 "not empty.")
            del self.buckets[b]
            self.send_response(204)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        m = re.match(r"^/storage/v1/b/([^/]+)/o/([^/]+)$", path)
        if m:
            bucket = urllib.parse.unquote(m.group(1))
            name = urllib.parse.unquote(m.group(2))
            if self.buckets.get(bucket, {}).pop(name, None) is None:
                return self._err(404, "notFound", "no object")
            self.send_response(204)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        return self._err(404, "notFound", path)

    def do_PATCH(self):
        if not self._authed():
            return
        path, _q = self._route()
        m = re.match(r"^/storage/v1/b/([^/]+)/o/([^/]+)$", path)
        if not m:
            return self._err(404, "notFound", path)
        bucket = urllib.parse.unquote(m.group(1))
        name = urllib.parse.unquote(m.group(2))
        obj = self.buckets.get(bucket, {}).get(name)
        if obj is None:
            return self._err(404, "notFound", "no object")
        obj["metadata"] = json.loads(self._body()).get("metadata", {})
        self._json(200, self._obj_json(name, obj))


@pytest.fixture()
def gcs_fake():
    FakeGCS.buckets = {}
    FakeGCS.tokens_issued = 0
    FakeGCS.compose_calls = []
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), FakeGCS)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1]
    srv.shutdown()


def _rsa_sa_json(port: int) -> str:
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    key = rsa.generate_private_key(public_exponent=65537,
                                   key_size=2048)
    pem = key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()).decode()
    return json.dumps({
        "type": "service_account",
        "project_id": "test-project",
        "client_email": "svc@test-project.iam.gserviceaccount.com",
        "private_key": pem,
        "token_uri": f"http://127.0.0.1:{port}/token"})


@pytest.fixture()
def gw(gcs_fake):
    layer = new_gateway("gcs", credentials_json=_rsa_sa_json(gcs_fake),
                        host="127.0.0.1", port=gcs_fake, secure=False)
    assert isinstance(layer, gcs_mod.GCSJsonGatewayObjects)
    return layer


def test_oauth_jwt_grant_flow(gw):
    """The service-account JWT-bearer grant runs against the token
    endpoint once and the token is reused."""
    gw.make_bucket("authb")
    gw.list_buckets()
    gw.bucket_exists("authb")
    assert FakeGCS.tokens_issued == 1
    assert gw.storage_info()["backend"] == "gateway-gcs"


def test_bucket_and_object_crud(gw):
    gw.make_bucket("jb")
    with pytest.raises(api_errors.BucketExists):
        gw.make_bucket("jb")
    assert [v.name for v in gw.list_buckets()] == ["jb"]
    with pytest.raises(api_errors.BucketNotFound):
        gw.get_bucket_info("ghost")

    payload = b"json-api object body " * 100
    info = gw.put_object(
        "jb", "dir/obj.bin", payload,
        opts=PutOptions(metadata={"content-type": "application/x-t",
                                  "x-amz-meta-k": "v"}))
    assert info.etag == hashlib.md5(payload).hexdigest()
    assert info.size == len(payload)

    got = gw.get_object_info("jb", "dir/obj.bin")
    assert got.size == len(payload)
    assert got.content_type == "application/x-t"
    assert got.user_defined.get("x-amz-meta-k") == "v"

    _, stream = gw.get_object("jb", "dir/obj.bin")
    assert b"".join(stream) == payload
    _, stream = gw.get_object("jb", "dir/obj.bin", offset=10,
                              length=50)
    assert b"".join(stream) == payload[10:60]

    gw.update_object_metadata("jb", "dir/obj.bin",
                              {"x-amz-meta-k": "v2"})
    assert gw.get_object_info(
        "jb", "dir/obj.bin").user_defined["x-amz-meta-k"] == "v2"

    gw.delete_object("jb", "dir/obj.bin")
    with pytest.raises(api_errors.ObjectNotFound):
        gw.get_object_info("jb", "dir/obj.bin")
    with pytest.raises(api_errors.ObjectNotFound):
        gw.delete_object("jb", "dir/obj.bin")


def test_delete_nonempty_bucket_maps_to_bucket_not_empty(gw):
    gw.make_bucket("full")
    gw.put_object("full", "keep", b"x")
    with pytest.raises(api_errors.BucketNotEmpty):
        gw.delete_bucket("full")
    gw.delete_object("full", "keep")
    gw.delete_bucket("full")
    assert not gw.bucket_exists("full")


def test_bucket_exists_propagates_auth_failures(gw):
    """A revoked token must surface as an error, never as 'the bucket
    does not exist' (which tricks callers into re-creating it)."""
    gw.make_bucket("realb")
    gw.c._token = "revoked"
    gw.c._token_exp = __import__("time").time() + 3600
    try:
        with pytest.raises(api_errors.ObjectApiError):
            gw.bucket_exists("realb")
    finally:
        gw.c._token = ""
        gw.c._token_exp = 0.0
    assert gw.bucket_exists("realb")


def test_listing_delimiter_marker_and_sys_tmp_hidden(gw):
    gw.make_bucket("lb")
    for name in ("a.txt", "b/one", "b/two", "c.txt",
                 "minio.sys.tmp/multipart/v1/u1/gcs.json"):
        gw.put_object("lb", name, b"x")
    objs, prefixes, _ = gw.list_objects("lb", delimiter="/")
    assert [o.name for o in objs] == ["a.txt", "c.txt"]
    assert prefixes == ["b/"]               # staging area hidden
    objs, _, _ = gw.list_objects("lb", prefix="b/")
    assert [o.name for o in objs] == ["b/one", "b/two"]
    objs, _, _ = gw.list_objects("lb", marker="b/one")
    assert [o.name for o in objs] == ["b/two", "c.txt"]


def test_multipart_compose_roundtrip(gw, monkeypatch):
    monkeypatch.setattr(gcs_mod, "MIN_PART_SIZE", 1)
    gw.make_bucket("mb")
    uid = gw.new_multipart_upload(
        "mb", "big.bin",
        PutOptions(metadata={"content-type": "application/x-big",
                             "x-amz-meta-tag": "mpu"}))
    # the session meta object exists in the reference's staging path
    assert gw.c.get_object_meta(
        "mb", f"minio.sys.tmp/multipart/v1/{uid}/gcs.json")

    chunks = [b"A" * 1000, b"B" * 2000, b"C" * 300]
    parts = []
    for i, chunk in enumerate(chunks, start=1):
        p = gw.put_object_part("mb", "big.bin", uid, i, chunk)
        parts.append(CompletePart(i, p.etag))
    listed = gw.list_object_parts("mb", "big.bin", uid)
    assert [p.number for p in listed] == [1, 2, 3]
    assert [u["upload_id"] for u in
            gw.list_multipart_uploads("mb")] == [uid]

    info = gw.complete_multipart_upload("mb", "big.bin", uid, parts)
    md5s = b"".join(bytes.fromhex(cp.etag) for cp in parts)
    assert info.etag == f"{hashlib.md5(md5s).hexdigest()}-3"
    _, stream = gw.get_object("mb", "big.bin")
    assert b"".join(stream) == b"".join(chunks)
    got = gw.get_object_info("mb", "big.bin")
    assert got.content_type == "application/x-big"
    assert got.user_defined.get("x-amz-meta-tag") == "mpu"
    # staging fully cleaned up
    assert FakeGCS.buckets["mb"].keys() == {"big.bin"}

    with pytest.raises(api_errors.InvalidUploadID):
        gw.put_object_part("mb", "big.bin", uid, 4, b"late")


def test_multipart_over_32_parts_composes_in_groups(gw, monkeypatch):
    """33+ parts exceed the GCS compose limit: groups of <= 32 compose
    into intermediates, then the intermediates compose into the final
    object (gateway-gcs.go:1339)."""
    monkeypatch.setattr(gcs_mod, "MIN_PART_SIZE", 1)
    gw.make_bucket("gb")
    uid = gw.new_multipart_upload("gb", "huge.bin", PutOptions())
    parts = []
    want = b""
    for i in range(1, 34):
        chunk = bytes([i]) * 10
        want += chunk
        p = gw.put_object_part("gb", "huge.bin", uid, i, chunk)
        parts.append(CompletePart(i, p.etag))
    FakeGCS.compose_calls = []
    gw.complete_multipart_upload("gb", "huge.bin", uid, parts)
    # every compose respected the 32-source limit; the final compose
    # consumed the two intermediates
    assert all(len(srcs) <= 32 for _, srcs in FakeGCS.compose_calls)
    dsts = [d for d, _ in FakeGCS.compose_calls]
    assert dsts[-1] == "huge.bin"
    assert len(FakeGCS.compose_calls) == 3      # 32 + 1, then final
    assert len(FakeGCS.compose_calls[-1][1]) == 2
    _, stream = gw.get_object("gb", "huge.bin")
    assert b"".join(stream) == want
    assert FakeGCS.buckets["gb"].keys() == {"huge.bin"}


def test_multipart_part_too_small_and_abort(gw, monkeypatch):
    gw.make_bucket("sb")
    uid = gw.new_multipart_upload("sb", "o", PutOptions())
    p1 = gw.put_object_part("sb", "o", uid, 1, b"tiny")
    p2 = gw.put_object_part("sb", "o", uid, 2, b"tail")
    with pytest.raises(api_errors.PartTooSmall):
        gw.complete_multipart_upload(
            "sb", "o", uid,
            [CompletePart(1, p1.etag), CompletePart(2, p2.etag)])
    # bad part etag -> InvalidPart
    monkeypatch.setattr(gcs_mod, "MIN_PART_SIZE", 1)
    with pytest.raises(api_errors.InvalidPart):
        gw.complete_multipart_upload(
            "sb", "o", uid,
            [CompletePart(1, "0" * 32), CompletePart(2, p2.etag)])
    gw.abort_multipart_upload("sb", "o", uid)
    assert FakeGCS.buckets["sb"] == {}
    with pytest.raises(api_errors.InvalidUploadID):
        gw.abort_multipart_upload("sb", "o", uid)


@pytest.mark.parametrize("size", [0, 1000])
def test_put_into_missing_bucket_is_bucket_not_found(gw, size):
    """The S3 handler does not check the bucket before a PUT: the
    gateway answers a missing bucket itself, and writes nothing."""
    with pytest.raises(api_errors.BucketNotFound):
        gw.put_object("ghost", "k", b"z" * size)
    assert not gw.bucket_exists("ghost")
