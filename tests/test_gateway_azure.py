"""Azure Blob gateway (VERDICT r2 item 9; reference
cmd/gateway/azure/gateway-azure.go): the whole gateway runs against an
in-process blob server that verifies SharedKey signatures and
implements the container/blob/block REST subset — tests cover the
shared gateway matrix (buckets, roundtrip, ranged get, metadata,
listing with delimiter, deletes) plus azure-native block multipart.
"""

from __future__ import annotations

import base64
import http.server
import re
import threading
import time
import urllib.parse
import xml.etree.ElementTree as ET

import pytest

from minio_tpu.gateway import new_gateway
from minio_tpu.object import api_errors
from minio_tpu.object.engine import PutOptions
from minio_tpu.utils.azureclient import (AzureClientError,
                                         shared_key_signature)

ACCOUNT = "testaccount"
KEY_B64 = base64.b64encode(b"azure-test-key-0123456789abcdef0").decode()


class FakeAzureBlob(http.server.BaseHTTPRequestHandler):
    """Azurite-style in-process blob service subset with SharedKey
    signature verification on every request."""

    store: dict = {}      # container -> {"blobs": {...}, "blocks": {...}}
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    # -- helpers -----------------------------------------------------------

    def _fail(self, status: int, code: str):
        body = (f"<?xml version='1.0'?><Error><Code>{code}</Code>"
                "</Error>").encode()
        self.send_response(status)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _ok(self, status: int = 200, body: bytes = b"",
            headers: dict | None = None):
        self.send_response(status)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        if self.command != "HEAD" and body:
            self.wfile.write(body)

    def _check_sig(self, path: str, query: dict) -> bool:
        auth = self.headers.get("Authorization", "")
        m = re.match(rf"SharedKey {ACCOUNT}:(.+)", auth)
        if not m:
            return False
        hdrs = {k.lower(): v for k, v in self.headers.items()}
        want = shared_key_signature(ACCOUNT, KEY_B64, self.command,
                                    path, query, hdrs)
        return m.group(1) == want

    def _dispatch(self):
        parsed = urllib.parse.urlsplit(self.path)
        path = urllib.parse.unquote(parsed.path)
        query = {k: v[0] for k, v in
                 urllib.parse.parse_qs(parsed.query,
                                       keep_blank_values=True).items()}
        # Real Azure canonicalizes the *escaped* request path, so the
        # fake verifies the signature over the raw (still-encoded)
        # request-line path — a client signing the unencoded path fails.
        if not self._check_sig(parsed.path, query):
            return self._fail(403, "AuthenticationFailed")
        n = int(self.headers.get("Content-Length", 0) or 0)
        body = self.rfile.read(n) if n else b""
        parts = path.lstrip("/").split("/", 1)
        container = parts[0]
        blob = parts[1] if len(parts) > 1 else ""
        m = self.command

        if not container and query.get("comp") == "list":
            xml = "<EnumerationResults><Containers>" + "".join(
                f"<Container><Name>{c}</Name></Container>"
                for c in sorted(self.store)) + \
                "</Containers></EnumerationResults>"
            return self._ok(200, xml.encode())

        if query.get("restype") == "container" and not blob:
            if m == "PUT":
                if container in self.store:
                    return self._fail(409, "ContainerAlreadyExists")
                self.store[container] = {"blobs": {}, "blocks": {}}
                return self._ok(201)
            if container not in self.store:
                return self._fail(404, "ContainerNotFound")
            if m == "DELETE":
                del self.store[container]
                return self._ok(202)
            if m == "HEAD":
                return self._ok(200)
            if m == "GET" and query.get("comp") == "list":
                return self._list_blobs(container, query)
            return self._fail(400, "InvalidQueryParameterValue")

        if container not in self.store:
            return self._fail(404, "ContainerNotFound")
        c = self.store[container]

        if m == "PUT" and query.get("comp") == "block":
            c["blocks"].setdefault(blob, {})[query["blockid"]] = body
            return self._ok(201)
        if m == "PUT" and query.get("comp") == "blocklist":
            ids = [el.text or "" for el in
                   ET.fromstring(body).iter("Uncommitted")]
            staged = c["blocks"].get(blob, {})
            if any(i not in staged for i in ids):
                return self._fail(400, "InvalidBlockList")
            data = b"".join(staged[i] for i in ids)
            meta = {k.lower()[len("x-ms-meta-"):]: v
                    for k, v in self.headers.items()
                    if k.lower().startswith("x-ms-meta-")}
            ctype = self.headers.get("x-ms-blob-content-type", "")
            c["blobs"][blob] = (data, meta, ctype, time.time())
            c["blocks"].pop(blob, None)
            return self._ok(201, headers={"ETag": f'"bl-{len(data)}"'})
        if m == "PUT":
            if self.headers.get("x-ms-blob-type") != "BlockBlob":
                return self._fail(400, "InvalidHeaderValue")
            meta = {k.lower()[len("x-ms-meta-"):]: v
                    for k, v in self.headers.items()
                    if k.lower().startswith("x-ms-meta-")}
            ctype = self.headers.get("Content-Type", "")
            c["blobs"][blob] = (body, meta, ctype, time.time())
            return self._ok(201, headers={"ETag": f'"e-{len(body)}"'})

        if blob not in c["blobs"]:
            return self._fail(404, "BlobNotFound")
        data, meta, ctype, mtime = c["blobs"][blob]

        if m == "DELETE":
            del c["blobs"][blob]
            return self._ok(202)
        hdrs = {"ETag": f'"e-{len(data)}"',
                "Last-Modified": time.strftime(
                    "%a, %d %b %Y %H:%M:%S GMT", time.gmtime(mtime)),
                "Content-Type": ctype or "application/octet-stream"}
        for k, v in meta.items():
            hdrs[f"x-ms-meta-{k}"] = v
        if m == "HEAD":
            hdrs["Content-Length"] = str(len(data))
            self.send_response(200)
            for k, v in hdrs.items():
                self.send_header(k, v)
            self.end_headers()
            return None
        if m == "GET":
            rng = self.headers.get("x-ms-range", "")
            mm = re.match(r"bytes=(\d+)-(\d*)", rng)
            if mm:
                lo = int(mm.group(1))
                hi = int(mm.group(2)) if mm.group(2) else len(data) - 1
                return self._ok(206, data[lo:hi + 1], hdrs)
            return self._ok(200, data, hdrs)
        return self._fail(400, "UnsupportedVerb")

    def _list_blobs(self, container: str, query: dict):
        """Opaque continuation tokens ('tok:<name>'): a key name passed
        as marker is rejected like real Azure — this is what catches a
        gateway that forwards S3 markers verbatim."""
        prefix = query.get("prefix", "")
        delim = query.get("delimiter", "")
        marker = query.get("marker", "")
        maxr = int(query.get("maxresults", "5000"))
        if marker and not marker.startswith("tok:"):
            return self._fail(400, "OutOfRangeInput")
        after = marker[4:] if marker else ""
        blobs = self.store[container]["blobs"]
        include_meta = "metadata" in query.get("include", "")
        out, prefixes = [], set()
        next_marker = ""
        n = 0
        for name in sorted(blobs):
            if not name.startswith(prefix) or (after and name <= after):
                continue
            if n >= maxr:
                next_marker = f"tok:{last}"          # noqa: F821
                break
            last = name
            n += 1
            if delim:
                rest = name[len(prefix):]
                d = rest.find(delim)
                if d >= 0:
                    prefixes.add(prefix + rest[:d + len(delim)])
                    continue
            data, meta, _ct, mtime = blobs[name]
            lm = time.strftime("%a, %d %b %Y %H:%M:%S GMT",
                               time.gmtime(mtime))
            meta_xml = ""
            if include_meta and meta:
                meta_xml = "<Metadata>" + "".join(
                    f"<{k}>{v}</{k}>" for k, v in meta.items()) \
                    + "</Metadata>"
            out.append(
                f"<Blob><Name>{name}</Name><Properties>"
                f"<Content-Length>{len(data)}</Content-Length>"
                f"<Etag>\"e-{len(data)}\"</Etag>"
                f"<Last-Modified>{lm}</Last-Modified>"
                f"</Properties>{meta_xml}</Blob>")
        xml = ("<EnumerationResults><Blobs>" + "".join(out)
               + "".join(f"<BlobPrefix><Name>{p}</Name></BlobPrefix>"
                         for p in sorted(prefixes))
               + f"</Blobs><NextMarker>{next_marker}</NextMarker>"
               "</EnumerationResults>")
        return self._ok(200, xml.encode())

    do_GET = do_PUT = do_DELETE = do_HEAD = _dispatch


@pytest.fixture()
def azure_server():
    FakeAzureBlob.store = {}
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                          FakeAzureBlob)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv.server_address[1]
    srv.shutdown()


@pytest.fixture()
def gw(azure_server):
    return new_gateway("azure", account=ACCOUNT, key_b64=KEY_B64,
                       host="127.0.0.1", port=azure_server)


def test_azure_bucket_lifecycle(gw):
    gw.make_bucket("cont")
    assert gw.bucket_exists("cont")
    assert "cont" in [v.name for v in gw.list_buckets()]
    with pytest.raises(api_errors.BucketExists):
        gw.make_bucket("cont")
    gw.delete_bucket("cont")
    assert not gw.bucket_exists("cont")
    with pytest.raises(api_errors.BucketNotFound):
        gw.get_bucket_info("nope")


def test_azure_object_roundtrip_and_range(gw):
    import os as _os
    gw.make_bucket("cont")
    payload = _os.urandom(100_000)
    info = gw.put_object("cont", "dir/obj", payload, opts=PutOptions(
        metadata={"x-amz-meta-color": "blue",
                  "content-type": "app/x-test"}))
    assert info.size == len(payload)

    got = gw.get_object_info("cont", "dir/obj")
    assert got.size == len(payload)
    assert got.content_type == "app/x-test"
    assert got.user_defined.get("x-amz-meta-color") == "blue"

    _i, stream = gw.get_object("cont", "dir/obj")
    assert b"".join(stream) == payload
    _i, stream = gw.get_object("cont", "dir/obj", offset=100,
                               length=500)
    assert b"".join(stream) == payload[100:600]

    with pytest.raises(api_errors.ObjectNotFound):
        gw.get_object_info("cont", "missing")
    gw.delete_object("cont", "dir/obj")
    with pytest.raises(api_errors.ObjectNotFound):
        gw.get_object_info("cont", "dir/obj")


def test_azure_listing_with_delimiter(gw):
    gw.make_bucket("cont")
    for k in ("a/1", "a/2", "b/1", "top"):
        gw.put_object("cont", k, b"x")
    objs, prefixes, _t = gw.list_objects("cont", delimiter="/")
    assert [o.name for o in objs] == ["top"]
    assert sorted(prefixes) == ["a/", "b/"]
    objs, _p, _t = gw.list_objects("cont", prefix="a/")
    assert [o.name for o in objs] == ["a/1", "a/2"]


def test_azure_multipart_block_commit(gw, azure_server):
    """Parts stage as uncommitted blocks on the service (never buffered
    in the gateway) and commit in part order via Put Block List."""
    gw.make_bucket("cont")
    uid = gw.new_multipart_upload("cont", "big", PutOptions(
        metadata={"x-amz-meta-kind": "mp"}))
    p2 = gw.put_object_part("cont", "big", uid, 2, b"BBBB" * 1000)
    p1 = gw.put_object_part("cont", "big", uid, 1, b"AAAA" * 1000)
    # blocks staged server-side, blob not yet visible
    with pytest.raises(api_errors.ObjectNotFound):
        gw.get_object_info("cont", "big")
    assert [p.number for p in
            gw.list_object_parts("cont", "big", uid)] == [1, 2]

    from minio_tpu.object import CompletePart
    info = gw.complete_multipart_upload(
        "cont", "big", uid,
        [CompletePart(1, p1.etag), CompletePart(2, p2.etag)])
    assert info.etag.endswith("-2")
    _i, stream = gw.get_object("cont", "big")
    assert b"".join(stream) == b"AAAA" * 1000 + b"BBBB" * 1000
    got = gw.get_object_info("cont", "big")
    assert got.user_defined.get("x-amz-meta-kind") == "mp"

    # wrong part etag refuses to commit
    uid2 = gw.new_multipart_upload("cont", "bad", None)
    gw.put_object_part("cont", "bad", uid2, 1, b"zz")
    with pytest.raises(api_errors.InvalidPart):
        gw.complete_multipart_upload("cont", "bad", uid2,
                                     [CompletePart(1, "wrong")])


def test_azure_special_char_names_sign_encoded_path(gw):
    """Advisor r3 (medium): SharedKey must sign the percent-encoded
    request path. Names that quote() rewrites (space, '#', unicode,
    '+') only authenticate when client and service canonicalize the
    same escaped string — the fake verifies over the raw request-line
    path, so signing the unencoded path would 403 here."""
    gw.make_bucket("cont")
    for key in ("dir with space/a b", "hash#frag", "uni-ü-ß",
                "plus+sign"):
        gw.put_object("cont", key, key.encode())
        _i, stream = gw.get_object("cont", key)
        assert b"".join(stream) == key.encode()
        assert gw.get_object_info("cont", key).size == len(key.encode())
        gw.delete_object("cont", key)


def test_azure_bad_signature_rejected(azure_server):
    from minio_tpu.utils.azureclient import AzureBlobClient
    bad = AzureBlobClient(ACCOUNT,
                          base64.b64encode(b"wrong-key").decode(),
                          "127.0.0.1", azure_server)
    with pytest.raises(AzureClientError) as ei:
        bad.create_container("x")
    assert ei.value.status == 403


def test_azure_gateway_behind_live_s3_server(azure_server, tmp_path):
    """The azure gateway serves as the ObjectLayer of a full S3 server:
    SigV4 clients read/write Azure-backed objects."""
    from minio_tpu.s3.server import S3Server
    from tests.test_s3 import CREDS, REGION, S3TestClient
    gw = new_gateway("azure", account=ACCOUNT, key_b64=KEY_B64,
                     host="127.0.0.1", port=azure_server)
    srv = S3Server(gw, creds=CREDS, region=REGION).start()
    try:
        c = S3TestClient("127.0.0.1", srv.port)
        assert c.request("PUT", "/azbucket")[0] == 200
        assert c.request("PUT", "/azbucket/o", body=b"via-s3")[0] == 200
        st, _, got = c.request("GET", "/azbucket/o")
        assert st == 200 and got == b"via-s3"
        st, _, got = c.request(
            "GET", "/azbucket/o", headers={"Range": "bytes=1-3"})
        assert st == 206 and got == b"ia-"
    finally:
        srv.stop()


def test_azure_zero_byte_and_etag_stability(gw):
    """Review r3: zero-byte GETs must not send 'bytes=0--1'; the ETag a
    PUT returns must be the one HEAD and listings report (pinned md5,
    not the service ETag)."""
    gw.make_bucket("cont")
    info = gw.put_object("cont", "empty", b"")
    _i, stream = gw.get_object("cont", "empty")
    assert b"".join(stream) == b""

    info = gw.put_object("cont", "obj", b"stable etag")
    head = gw.get_object_info("cont", "obj")
    assert head.etag == info.etag
    objs, _p, _t = gw.list_objects("cont", prefix="obj")
    assert objs[0].etag == info.etag


def test_azure_control_metadata_roundtrip(gw):
    """Tagging / object-lock metadata keys must survive the gateway
    (review r3: only x-amz-meta-* survived before)."""
    gw.make_bucket("cont")
    md = {"X-Amz-Tagging": "k=v&a=b",
          "x-amz-object-lock-mode": "GOVERNANCE",
          "x-amz-meta-plain": "p"}
    gw.put_object("cont", "locked", b"d", opts=PutOptions(metadata=md))
    got = gw.get_object_info("cont", "locked").user_defined
    assert got.get("x-amz-tagging") == "k=v&a=b"
    assert got.get("x-amz-object-lock-mode") == "GOVERNANCE"
    assert got.get("x-amz-meta-plain") == "p"


def test_azure_listing_pagination_opaque_tokens(gw):
    """Continuation across pages uses Azure tokens, never raw S3 key
    markers (the fake server 400s on a non-token marker)."""
    gw.make_bucket("cont")
    for i in range(25):
        gw.put_object("cont", f"k{i:03d}", b"x")
    seen = []
    marker = ""
    for _ in range(10):
        objs, _p, trunc = gw.list_objects("cont", marker=marker,
                                          max_keys=10)
        seen.extend(o.name for o in objs)
        if not trunc or not objs:
            break
        marker = objs[-1].name
    assert seen == [f"k{i:03d}" for i in range(25)]


def test_azure_streamed_put_constant_memory(gw, monkeypatch):
    """Above the stream threshold, PUT stages blocks instead of one
    whole-body blob (review r3: docstring promised it)."""
    import io as _io
    from minio_tpu.gateway.azure import AzureGatewayObjects
    monkeypatch.setattr(AzureGatewayObjects, "STREAM_THRESHOLD", 1024)
    monkeypatch.setattr(AzureGatewayObjects, "STAGE_CHUNK", 1024)
    gw.make_bucket("cont")
    payload = bytes(range(256)) * 40          # 10240 B -> 10 blocks
    info = gw.put_object("cont", "streamed", _io.BytesIO(payload),
                         size=len(payload))
    assert info.size == len(payload)
    import hashlib as _hl
    assert info.etag == _hl.md5(payload).hexdigest()
    _i, stream = gw.get_object("cont", "streamed")
    assert b"".join(stream) == payload
    assert gw.get_object_info("cont", "streamed").etag == info.etag


@pytest.mark.parametrize("size", [0, 1000])
def test_azure_put_into_missing_container_is_bucket_not_found(gw, size):
    """The S3 handler does not check the bucket before a PUT: the
    gateway answers a missing container itself, and writes nothing."""
    with pytest.raises(api_errors.BucketNotFound):
        gw.put_object("ghost", "k", b"z" * size)
    assert not gw.bucket_exists("ghost")
