"""S3 HTTP frontend tests: signed requests end-to-end against a live
server over a real erasure object layer (the reference's
ExecObjectLayerAPITest pattern, cmd/test-utils_test.go:1812)."""

from __future__ import annotations

import hashlib
import http.client
import urllib.parse
import xml.etree.ElementTree as ET

import pytest

from minio_tpu.object.sets import ErasureSets
from minio_tpu.s3 import signature as sig
from minio_tpu.s3.credentials import Credentials
from minio_tpu.s3.server import S3Server

CREDS = Credentials("testadminkey", "testadminsecretkey")
REGION = "us-east-1"


class S3TestClient:
    """Minimal SigV4-signing HTTP client."""

    def __init__(self, host: str, port: int,
                 creds: Credentials = CREDS):
        self.host, self.port, self.creds = host, port, creds

    def request(self, method: str, path: str, query: dict | None = None,
                body: bytes = b"", headers: dict | None = None,
                sign: bool = True, streaming: bool = False):
        query = {k: [v] for k, v in (query or {}).items()}
        qs = urllib.parse.urlencode({k: v[0] for k, v in query.items()})
        hdrs = {k.lower(): v for k, v in (headers or {}).items()}
        hdrs["host"] = f"{self.host}:{self.port}"
        if sign:
            payload_hash = hashlib.sha256(body).hexdigest()
            hdrs = sig.sign_v4(method, urllib.parse.quote(path), query,
                               hdrs, payload_hash, self.creds, REGION)
        conn = http.client.HTTPConnection(self.host, self.port, timeout=60)
        url = urllib.parse.quote(path) + (f"?{qs}" if qs else "")
        conn.request(method, url, body=body, headers=hdrs)
        resp = conn.getresponse()
        data = resp.read()
        out_headers = {k.lower(): v for k, v in resp.getheaders()}
        conn.close()
        return resp.status, out_headers, data


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    root = tmp_path_factory.mktemp("s3drives")
    drives = [str(root / f"d{i}") for i in range(8)]
    sets = ErasureSets.from_drives(drives, set_count=1, set_drive_count=8,
                                   parity=2, block_size=1 << 18)
    srv = S3Server(sets, creds=CREDS, region=REGION).start()
    yield srv
    srv.stop()
    sets.close()


@pytest.fixture(scope="module")
def client(server):
    return S3TestClient("127.0.0.1", server.port)


@pytest.fixture(scope="module")
def bucket(client):
    status, _, _ = client.request("PUT", "/testbucket")
    assert status == 200
    return "testbucket"


def test_unauthenticated_rejected(client):
    status, _, body = client.request("GET", "/", sign=False)
    assert status == 403
    assert b"<Error>" in body


def test_bad_signature_rejected(server):
    bad = S3TestClient("127.0.0.1", server.port,
                       Credentials(CREDS.access_key, "wrongsecret000"))
    status, _, body = bad.request("GET", "/")
    assert status == 403
    assert b"SignatureDoesNotMatch" in body


def test_unknown_access_key(server):
    bad = S3TestClient("127.0.0.1", server.port,
                       Credentials("nosuchaccesskey", "whatever12345"))
    status, _, body = bad.request("GET", "/")
    assert status == 403
    assert b"InvalidAccessKeyId" in body


def test_make_and_list_buckets(client, bucket):
    status, headers, body = client.request("GET", "/")
    assert status == 200
    root = ET.fromstring(body)
    names = [e.text for e in root.iter(
        f"{{{ 'http://s3.amazonaws.com/doc/2006-03-01/' }}}Name")]
    assert bucket in names


def test_bucket_lifecycle_of_missing(client):
    status, _, body = client.request("GET", "/nosuchbucket123",
                                     query={"location": ""})
    assert status == 404
    assert b"NoSuchBucket" in body


def test_invalid_bucket_name(client):
    status, _, body = client.request("PUT", "/UPPER_CASE_BAD")
    assert status == 400


def test_head_bucket(client, bucket):
    status, _, _ = client.request("HEAD", f"/{bucket}")
    assert status == 200
    status, _, _ = client.request("HEAD", "/absent-bucket-xyz")
    assert status == 404


def test_put_get_object_roundtrip(client, bucket):
    data = b"hello tpu object store" * 1000
    status, headers, _ = client.request("PUT", f"/{bucket}/obj1",
                                        body=data)
    assert status == 200
    etag = headers["etag"].strip('"')
    assert etag == hashlib.md5(data).hexdigest()

    status, headers, got = client.request("GET", f"/{bucket}/obj1")
    assert status == 200
    assert got == data
    assert headers["etag"].strip('"') == etag
    assert headers["content-length"] == str(len(data))


def test_head_object(client, bucket):
    data = b"head me"
    client.request("PUT", f"/{bucket}/headobj", body=data)
    status, headers, body = client.request("HEAD", f"/{bucket}/headobj")
    assert status == 200
    assert headers["content-length"] == str(len(data))
    assert body == b""


def test_get_missing_object(client, bucket):
    status, _, body = client.request("GET", f"/{bucket}/absent-key")
    assert status == 404
    assert b"NoSuchKey" in body


def test_ranged_get(client, bucket):
    data = bytes(range(256)) * 64
    client.request("PUT", f"/{bucket}/ranged", body=data)
    status, headers, got = client.request(
        "GET", f"/{bucket}/ranged", headers={"Range": "bytes=100-199"})
    assert status == 206
    assert got == data[100:200]
    assert headers["content-range"] == f"bytes 100-199/{len(data)}"
    # suffix range
    status, _, got = client.request(
        "GET", f"/{bucket}/ranged", headers={"Range": "bytes=-50"})
    assert status == 206
    assert got == data[-50:]
    # unsatisfiable
    status, _, _ = client.request(
        "GET", f"/{bucket}/ranged",
        headers={"Range": f"bytes={len(data)}-"})
    assert status == 416


def test_conditional_get(client, bucket):
    data = b"conditional body"
    _, headers, _ = client.request("PUT", f"/{bucket}/cond", body=data)
    etag = headers["etag"]
    status, _, _ = client.request("GET", f"/{bucket}/cond",
                                  headers={"If-None-Match": etag})
    assert status == 304
    status, _, _ = client.request("GET", f"/{bucket}/cond",
                                  headers={"If-Match": '"deadbeef"'})
    assert status == 412


def test_content_md5_verified(client, bucket):
    import base64
    data = b"md5 checked payload"
    good = base64.b64encode(hashlib.md5(data).digest()).decode()
    status, _, _ = client.request("PUT", f"/{bucket}/md5ok", body=data,
                                  headers={"Content-MD5": good})
    assert status == 200
    bad = base64.b64encode(hashlib.md5(b"other").digest()).decode()
    status, _, body = client.request("PUT", f"/{bucket}/md5bad",
                                     body=data,
                                     headers={"Content-MD5": bad})
    assert status == 400


def test_delete_object(client, bucket):
    client.request("PUT", f"/{bucket}/todelete", body=b"x")
    status, _, _ = client.request("DELETE", f"/{bucket}/todelete")
    assert status == 204
    status, _, _ = client.request("GET", f"/{bucket}/todelete")
    assert status == 404
    # deleting a missing key is still 204
    status, _, _ = client.request("DELETE", f"/{bucket}/never-existed")
    assert status == 204


def test_list_objects_v1_and_v2(client, bucket):
    for i in range(3):
        client.request("PUT", f"/{bucket}/list/a{i}", body=b"d")
    client.request("PUT", f"/{bucket}/list/sub/deep", body=b"d")
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"prefix": "list/",
                                            "delimiter": "/"})
    assert status == 200
    ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
    root = ET.fromstring(body)
    keys = [c.find(f"{ns}Key").text for c in root.iter(f"{ns}Contents")]
    prefixes = [p.find(f"{ns}Prefix").text
                for p in root.iter(f"{ns}CommonPrefixes")]
    assert keys == ["list/a0", "list/a1", "list/a2"]
    assert prefixes == ["list/sub/"]

    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"list-type": "2",
                                            "prefix": "list/",
                                            "delimiter": "/"})
    root = ET.fromstring(body)
    assert root.find(f"{ns}KeyCount").text == "4"


def test_multipart_roundtrip(client, bucket):
    status, _, body = client.request("POST", f"/{bucket}/mpobj",
                                     query={"uploads": ""})
    assert status == 200
    ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
    upload_id = ET.fromstring(body).find(f"{ns}UploadId").text

    part1 = b"A" * (5 << 20)
    part2 = b"B" * 1024
    etags = []
    for num, part in ((1, part1), (2, part2)):
        status, headers, _ = client.request(
            "PUT", f"/{bucket}/mpobj",
            query={"partNumber": str(num), "uploadId": upload_id},
            body=part)
        assert status == 200
        etags.append(headers["etag"].strip('"'))

    status, _, body = client.request(
        "GET", f"/{bucket}/mpobj", query={"uploadId": upload_id})
    assert status == 200
    assert body.count(b"<Part>") == 2

    complete = (
        '<CompleteMultipartUpload>'
        + "".join(f"<Part><PartNumber>{n}</PartNumber>"
                  f"<ETag>\"{e}\"</ETag></Part>"
                  for n, e in zip((1, 2), etags))
        + '</CompleteMultipartUpload>').encode()
    status, _, body = client.request(
        "POST", f"/{bucket}/mpobj", query={"uploadId": upload_id},
        body=complete)
    assert status == 200
    assert b"CompleteMultipartUploadResult" in body

    status, _, got = client.request("GET", f"/{bucket}/mpobj")
    assert status == 200
    assert got == part1 + part2


def test_multipart_abort(client, bucket):
    ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
    _, _, body = client.request("POST", f"/{bucket}/abortobj",
                                query={"uploads": ""})
    upload_id = ET.fromstring(body).find(f"{ns}UploadId").text
    client.request("PUT", f"/{bucket}/abortobj",
                   query={"partNumber": "1", "uploadId": upload_id},
                   body=b"data")
    status, _, _ = client.request("DELETE", f"/{bucket}/abortobj",
                                  query={"uploadId": upload_id})
    assert status == 204
    status, _, body = client.request(
        "GET", f"/{bucket}/abortobj", query={"uploadId": upload_id})
    assert status == 404
    assert b"NoSuchUpload" in body


def test_copy_object(client, bucket):
    data = b"copy source data" * 100
    client.request("PUT", f"/{bucket}/copysrc", body=data)
    status, _, body = client.request(
        "PUT", f"/{bucket}/copydst",
        headers={"x-amz-copy-source": f"/{bucket}/copysrc"})
    assert status == 200
    assert b"CopyObjectResult" in body
    status, _, got = client.request("GET", f"/{bucket}/copydst")
    assert got == data


def test_delete_multiple_objects(client, bucket):
    for i in range(3):
        client.request("PUT", f"/{bucket}/bulk{i}", body=b"x")
    doc = ("<Delete>" +
           "".join(f"<Object><Key>bulk{i}</Key></Object>"
                   for i in range(3)) +
           "<Object><Key>bulk-missing</Key></Object></Delete>").encode()
    status, _, body = client.request("POST", f"/{bucket}",
                                     query={"delete": ""}, body=doc)
    assert status == 200
    assert body.count(b"<Deleted>") == 4
    for i in range(3):
        status, _, _ = client.request("GET", f"/{bucket}/bulk{i}")
        assert status == 404


def test_versioning_cycle(client, bucket):
    cfg = (b'<VersioningConfiguration>'
           b'<Status>Enabled</Status></VersioningConfiguration>')
    status, _, _ = client.request("PUT", f"/{bucket}",
                                  query={"versioning": ""}, body=cfg)
    assert status == 200
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"versioning": ""})
    assert status == 200
    assert b"Enabled" in body

    # two PUTs -> two versions
    _, h1, _ = client.request("PUT", f"/{bucket}/vobj", body=b"v1")
    _, h2, _ = client.request("PUT", f"/{bucket}/vobj", body=b"v2")
    v1, v2 = h1.get("x-amz-version-id"), h2.get("x-amz-version-id")
    assert v1 and v2 and v1 != v2

    _, _, got = client.request("GET", f"/{bucket}/vobj")
    assert got == b"v2"
    _, _, got = client.request("GET", f"/{bucket}/vobj",
                               query={"versionId": v1})
    assert got == b"v1"

    # delete -> marker; latest GET 404s, old version still readable
    status, headers, _ = client.request("DELETE", f"/{bucket}/vobj")
    assert status == 204
    assert headers.get("x-amz-delete-marker") == "true"
    status, _, _ = client.request("GET", f"/{bucket}/vobj")
    assert status == 404
    _, _, got = client.request("GET", f"/{bucket}/vobj",
                               query={"versionId": v2})
    assert got == b"v2"

    # list versions shows marker + 2 versions
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"versions": "",
                                            "prefix": "vobj"})
    assert status == 200
    assert body.count(b"<Version>") == 2
    assert body.count(b"<DeleteMarker>") == 1
    # suspend versioning again for later tests
    cfg = (b'<VersioningConfiguration>'
           b'<Status>Suspended</Status></VersioningConfiguration>')
    client.request("PUT", f"/{bucket}", query={"versioning": ""},
                   body=cfg)


def test_bucket_policy_cycle(client, bucket):
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"policy": ""})
    assert status == 404
    policy = (b'{"Version":"2012-10-17","Statement":[{"Effect":"Allow",'
              b'"Principal":{"AWS":["*"]},"Action":["s3:GetObject"],'
              b'"Resource":["arn:aws:s3:::%s/*"]}]}' % bucket.encode())
    status, _, _ = client.request("PUT", f"/{bucket}",
                                  query={"policy": ""}, body=policy)
    assert status == 204
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"policy": ""})
    assert status == 200
    assert b"s3:GetObject" in body
    status, _, _ = client.request("DELETE", f"/{bucket}",
                                  query={"policy": ""})
    assert status == 204


def test_bucket_tagging_cycle(client, bucket):
    doc = (b"<Tagging><TagSet>"
           b"<Tag><Key>team</Key><Value>tpu</Value></Tag>"
           b"</TagSet></Tagging>")
    status, _, _ = client.request("PUT", f"/{bucket}",
                                  query={"tagging": ""}, body=doc)
    assert status == 200
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"tagging": ""})
    assert status == 200
    assert b"<Key>team</Key>" in body and b"<Value>tpu</Value>" in body
    status, _, _ = client.request("DELETE", f"/{bucket}",
                                  query={"tagging": ""})
    assert status == 204


def test_presigned_get(server, client, bucket):
    data = b"presigned content"
    client.request("PUT", f"/{bucket}/presigned", body=data)
    qs = sig.presign_v4("GET", f"/{bucket}/presigned", {}, {
        "host": f"127.0.0.1:{server.port}"}, CREDS, REGION, 600)
    conn = http.client.HTTPConnection("127.0.0.1", server.port)
    conn.request("GET", f"/{bucket}/presigned?{qs}")
    resp = conn.getresponse()
    got = resp.read()
    assert resp.status == 200
    assert got == data
    conn.close()


def test_streaming_signed_put(server, bucket):
    """Streaming chunked V4 upload (aws-chunked payload)."""
    import datetime
    import hashlib as h
    import hmac as hm

    host = f"127.0.0.1:{server.port}"
    path = f"/{bucket}/streamed"
    data = b"S" * 70000
    chunk_size = 65536
    t = datetime.datetime.now(datetime.timezone.utc)
    amz_date = t.strftime(sig.ISO8601_FORMAT)
    date = t.strftime(sig.YYYYMMDD)
    scope = f"{date}/{REGION}/s3/aws4_request"

    decoded_len = len(data)
    chunks = [data[i:i + chunk_size]
              for i in range(0, len(data), chunk_size)] + [b""]
    # encoded length: sum over chunks of header+payload+crlf
    enc_len = 0
    for c in chunks:
        header = f"{len(c):x};chunk-signature={'0' * 64}\r\n"
        enc_len += len(header) + len(c) + 2

    headers = {
        "host": host,
        "x-amz-date": amz_date,
        "x-amz-content-sha256": sig.STREAMING_CONTENT_SHA256,
        "x-amz-decoded-content-length": str(decoded_len),
        "content-length": str(enc_len),
    }
    signed = sorted(["host", "x-amz-content-sha256", "x-amz-date",
                     "x-amz-decoded-content-length"])
    canon = sig.canonical_request("PUT", path, "", headers, signed,
                                  sig.STREAMING_CONTENT_SHA256)
    sts = sig.string_to_sign(canon, amz_date, scope)
    key = sig.signing_key(CREDS.secret_key, date, REGION)
    seed_sig = hm.new(key, sts.encode(), h.sha256).hexdigest()
    headers["authorization"] = (
        f"{sig.SIGN_V4_ALGORITHM} Credential={CREDS.access_key}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={seed_sig}")

    # build chunked body with chained chunk signatures
    body = b""
    prev = seed_sig
    for c in chunks:
        chunk_sts = "\n".join([
            "AWS4-HMAC-SHA256-PAYLOAD", amz_date, scope, prev,
            sig.EMPTY_SHA256, h.sha256(c).hexdigest()])
        csig = hm.new(key, chunk_sts.encode(), h.sha256).hexdigest()
        body += f"{len(c):x};chunk-signature={csig}\r\n".encode()
        body += c + b"\r\n"
        prev = csig
    assert len(body) == enc_len

    conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                      timeout=60)
    conn.request("PUT", path, body=body, headers=headers)
    resp = conn.getresponse()
    resp.read()
    assert resp.status == 200
    conn.close()

    cl = S3TestClient("127.0.0.1", server.port)
    status, _, got = cl.request("GET", path)
    assert status == 200
    assert got == data


def test_object_tagging_cycle(client, bucket):
    client.request("PUT", f"/{bucket}/tagobj", body=b"x")
    doc = (b"<Tagging><TagSet>"
           b"<Tag><Key>k1</Key><Value>v1</Value></Tag>"
           b"</TagSet></Tagging>")
    status, _, _ = client.request("PUT", f"/{bucket}/tagobj",
                                  query={"tagging": ""}, body=doc)
    assert status == 200
    status, _, body = client.request("GET", f"/{bucket}/tagobj",
                                     query={"tagging": ""})
    assert status == 200
    assert b"<Key>k1</Key>" in body
    status, _, _ = client.request("DELETE", f"/{bucket}/tagobj",
                                  query={"tagging": ""})
    assert status == 204


def test_delete_bucket_not_empty_then_empty(client):
    client.request("PUT", "/delbucket")
    client.request("PUT", "/delbucket/obj", body=b"x")
    status, _, body = client.request("DELETE", "/delbucket")
    assert status == 409
    client.request("DELETE", "/delbucket/obj")
    status, _, _ = client.request("DELETE", "/delbucket")
    assert status == 204


def test_keepalive_after_unread_body(server, bucket):
    """An errored PUT whose body the handler never read must not poison
    the next request on the same persistent connection."""
    conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                      timeout=30)
    body = b"Z" * 4096
    # unsigned PUT with a body -> 403 before the handler touches rfile
    conn.request("PUT", f"/{bucket}/poison", body=body,
                 headers={"Host": f"127.0.0.1:{server.port}"})
    resp = conn.getresponse()
    assert resp.status == 403
    resp.read()
    # same socket: a signed GET must still parse cleanly
    cl = S3TestClient("127.0.0.1", server.port)
    import urllib.parse as up
    hdrs = sig.sign_v4("GET", "/", {}, {
        "host": f"127.0.0.1:{server.port}"},
        hashlib.sha256(b"").hexdigest(), CREDS, REGION)
    conn.request("GET", "/", headers=hdrs)
    resp = conn.getresponse()
    assert resp.status == 200
    resp.read()
    conn.close()


def test_list_multipart_uploads_reports_keys(client, bucket):
    ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
    _, _, body = client.request("POST", f"/{bucket}/listmp/realkey",
                                query={"uploads": ""})
    upload_id = ET.fromstring(body).find(f"{ns}UploadId").text
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"uploads": ""})
    assert status == 200
    root = ET.fromstring(body)
    entries = {(u.find(f"{ns}Key").text, u.find(f"{ns}UploadId").text)
               for u in root.iter(f"{ns}Upload")}
    assert ("listmp/realkey", upload_id) in entries
    client.request("DELETE", f"/{bucket}/listmp/realkey",
                   query={"uploadId": upload_id})


def test_max_keys_zero(client, bucket):
    status, _, body = client.request("GET", f"/{bucket}",
                                     query={"max-keys": "0"})
    assert status == 200
    assert b"<Contents>" not in body
    assert b"<IsTruncated>false</IsTruncated>" in body


def test_delete_multiple_on_missing_bucket(client):
    doc = b"<Delete><Object><Key>k</Key></Object></Delete>"
    status, _, body = client.request("POST", "/absent-bucket-zz",
                                     query={"delete": ""}, body=doc)
    assert status == 404
    assert b"NoSuchBucket" in body


def test_signed_body_sha_mismatch_rejected(client, bucket):
    """A signed request whose body doesn't match the signed
    x-amz-content-sha256 must be rejected (isReqAuthenticated analog)."""
    policy = (b'{"Version":"2012-10-17","Statement":[{"Effect":"Allow",'
              b'"Principal":{"AWS":["*"]},"Action":["s3:GetObject"],'
              b'"Resource":["arn:aws:s3:::testbucket/*"]}]}')
    # sign over DIFFERENT bytes than we send
    wrong_hash = hashlib.sha256(b"something else entirely").hexdigest()
    hdrs = {"host": f"{client.host}:{client.port}"}
    hdrs = sig.sign_v4("PUT", f"/{bucket}", {"policy": [""]}, hdrs,
                       wrong_hash, client.creds, REGION)
    conn = http.client.HTTPConnection(client.host, client.port, timeout=60)
    conn.request("PUT", f"/{bucket}?policy=", body=policy, headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    assert resp.status == 400
    assert b"XAmzContentSHA256Mismatch" in data


def test_put_bucket_notification_validated_by_plane(client, server):
    """With a NotificationPlane attached, PUT ?notification rejects
    configs naming unknown target ARNs or event names (the reference's
    ErrARNNotification / ErrEventNotification), accepts valid ones, and
    keeps the legacy accept-anything behavior when no plane is wired."""
    status, _, _ = client.request("PUT", "/notifyval")
    assert status == 200
    arn = "arn:minio:sqs::hook1:webhook"

    def xml(target_arn=arn, event="s3:ObjectCreated:*"):
        return (f"<NotificationConfiguration><QueueConfiguration>"
                f"<Queue>{target_arn}</Queue><Event>{event}</Event>"
                f"</QueueConfiguration></NotificationConfiguration>"
                ).encode()

    # no plane attached: any well-formed doc passes (legacy behavior)
    assert server.api.notify is None
    status, _, _ = client.request(
        "PUT", "/notifyval", query={"notification": ""},
        body=xml("arn:minio:sqs::ghost:webhook"))
    assert status == 200

    class _Registry:
        def arns(self):
            return {arn}

    class _Plane:
        registry = _Registry()

    server.api.notify = _Plane()
    try:
        status, _, body = client.request(
            "PUT", "/notifyval", query={"notification": ""},
            body=xml("arn:minio:sqs::ghost:webhook"))
        assert status == 400
        assert b"InvalidArgument" in body and b"ghost" in body

        status, _, body = client.request(
            "PUT", "/notifyval", query={"notification": ""},
            body=xml(event="s3:ObjectTypo:*"))
        assert status == 400
        assert b"InvalidArgument" in body and b"ObjectTypo" in body

        # a rule with no events is structurally invalid, not unknown
        doc = (f"<NotificationConfiguration><QueueConfiguration>"
               f"<Queue>{arn}</Queue>"
               f"</QueueConfiguration></NotificationConfiguration>")
        status, _, body = client.request(
            "PUT", "/notifyval", query={"notification": ""},
            body=doc.encode())
        assert status == 400
        assert b"MalformedXML" in body

        status, _, body = client.request(
            "PUT", "/notifyval", query={"notification": ""},
            body=b"<NotificationConfiguration")
        assert status == 400
        assert b"MalformedXML" in body

        status, _, _ = client.request(
            "PUT", "/notifyval", query={"notification": ""}, body=xml())
        assert status == 200
        status, _, body = client.request(
            "GET", "/notifyval", query={"notification": ""})
        assert status == 200
        assert arn.encode() in body
    finally:
        server.api.notify = None


# ---------------------------------------------------------------------------
# PutObject into a missing bucket: the object layer answers, not a stat
# ---------------------------------------------------------------------------

def _drive_roots(server) -> list[str]:
    return [d.root for s in server.api.obj.sets for d in s.disks]


def _tmp_entries(server) -> list[str]:
    """Everything left under `.minio.sys/tmp` on any drive of the node."""
    import os
    return [os.path.join(dp, n)
            for root in _drive_roots(server)
            for dp, dn, fn in os.walk(os.path.join(root, ".minio.sys",
                                                   "tmp"))
            for n in dn + fn]


def _bucket_missing_total() -> float:
    from minio_tpu.utils import telemetry
    return telemetry.REGISTRY.counter(
        "minio_tpu_put_bucket_missing_total", "").value()


@pytest.mark.parametrize("size", [0, 1000, 3 * (1 << 18) + 4321],
                         ids=["empty", "small", "multi-block"])
def test_put_into_missing_bucket_is_no_such_bucket(server, client, size):
    """The body is read and encoded, the commit's rename fan-out finds
    no volume on any drive: 404 NoSuchBucket, the staging directories
    gone, and the counter says the body was read for nothing."""
    before = _bucket_missing_total()
    status, _, body = client.request(
        "PUT", f"/ghost-bucket-{size}/k", body=b"\x5a" * size)
    assert status == 404
    assert b"NoSuchBucket" in body
    assert _tmp_entries(server) == []
    assert _bucket_missing_total() - before == 1
    status, _, _ = client.request("HEAD", f"/ghost-bucket-{size}")
    assert status == 404


def test_put_into_existing_bucket_stats_no_drive(client, bucket,
                                                 monkeypatch):
    """A PUT into a bucket that is there asks no drive whether it
    exists: not one `stat_vol` of its volume."""
    from minio_tpu.storage.xl_storage import XLStorage
    stats = []
    real = XLStorage.stat_vol

    def counting(self, volume):
        if volume == bucket:
            stats.append(volume)
        return real(self, volume)

    monkeypatch.setattr(XLStorage, "stat_vol", counting)
    before = _bucket_missing_total()
    data = b"no stat before the body" * 100
    status, headers, _ = client.request("PUT", f"/{bucket}/nostat",
                                        body=data)
    assert status == 200
    assert headers["etag"].strip('"') == hashlib.md5(data).hexdigest()
    assert stats == []
    assert _bucket_missing_total() == before
    status, _, got = client.request("GET", f"/{bucket}/nostat")
    assert status == 200 and got == data


def test_refused_put_leaves_no_stale_bucket_metadata(server, client):
    """A refused PUT drops the defaults the handler cached for the
    missing bucket: once the bucket is made and versioning is enabled
    elsewhere (a peer's write, no notice to this node), the next PUT is
    versioned."""
    from minio_tpu.object.bucket_metadata import BucketMetadataSys
    status, _, _ = client.request("PUT", "/late-bucket/k", body=b"one")
    assert status == 404
    status, _, _ = client.request("PUT", "/late-bucket")
    assert status == 200
    BucketMetadataSys(server.api.obj).update("late-bucket",
                                             versioning="Enabled")
    status, headers, _ = client.request("PUT", "/late-bucket/k",
                                        body=b"two")
    assert status == 200
    assert headers.get("x-amz-version-id", "") not in ("", "null")


def test_replication_apply_still_checks_the_bucket(client, bucket):
    """The replication-apply branch checks the bucket before it parses
    the spec header: a missing bucket is NoSuchBucket, an existing one
    reaches the spec (here malformed)."""
    hdr = {"x-minio-tpu-repl-spec": "not-a-spec"}
    status, _, body = client.request("PUT", "/ghost-repl-bucket/k",
                                     body=b"x", headers=hdr)
    assert status == 404
    assert b"NoSuchBucket" in body
    status, _, body = client.request("PUT", f"/{bucket}/repl-k",
                                     body=b"x", headers=hdr)
    assert status == 400
    assert b"InvalidArgument" in body
