"""GCS gateway: ObjectLayer over Google Cloud Storage's JSON API
(reference cmd/gateway/gcs/gateway-gcs.go, 1508 LoC: OAuth2 JSON API,
compose-based multipart, GCS error mapping).

Two modes:

* **JSON API** (the reference's mode, default here when a service
  account or token is given): hand-rolled REST client over
  ``/storage/v1`` + ``/upload/storage/v1`` with OAuth2 service-account
  JWT-bearer grants (RS256 via `cryptography`, no SDK). Multipart
  uploads mirror the reference's durable scheme — parts live as
  ``minio.sys.tmp/multipart/v1/<uploadID>/<NNNNN>.<etag>`` objects with
  a ``gcs.json`` session meta, and completion COMPOSES them (groups of
  <= 32, the GCS compose limit) into intermediate objects and then the
  final key (gateway-gcs.go:1267 CompleteMultipartUpload).
* **XML interop** (fallback, `hmac_key`/`hmac_secret`): GCS's S3-dialect
  surface over the existing S3 client — useful where only HMAC
  interoperability keys exist.
"""

from __future__ import annotations

import base64
import hashlib
import io
import json
import time
import urllib.parse
import uuid as _uuid
from typing import Iterator, Optional

from ..object import api_errors
from ..object.engine import GetOptions, PutOptions
from ..object.hash_reader import HashReader
from ..storage.datatypes import ObjectInfo, ObjectPartInfo, VolInfo, single_version_page
from ..s3.credentials import Credentials
from ..utils.s3client import S3Client
from .s3 import S3GatewayObjects

GCS_SYS_TMP = "minio.sys.tmp/"
_MPU_PATH = GCS_SYS_TMP + "multipart/v1"
_MPU_META = "gcs.json"
_MPU_META_VERSION = "1"
MAX_COMPONENTS = 32                    # GCS compose limit
MIN_PART_SIZE = 5 << 20                # parts except last (reference)
_SCOPE = "https://www.googleapis.com/auth/devstorage.read_write"


class GCSError(Exception):
    def __init__(self, status: int, reason: str, message: str):
        super().__init__(f"{status} {reason}: {message}")
        self.status = status
        self.reason = reason


def _map_err(e: GCSError, bucket: str, key: str = "",
             upload_id: str = "", deleting: bool = False) -> Exception:
    """gcsToObjectError (gateway-gcs.go:131) by status/reason. GCS uses
    409 both for "bucket exists" (insert) and "bucket not empty"
    (delete) — `deleting` disambiguates like the reference's
    per-message switch."""
    if e.reason in ("required", "keyInvalid", "forbidden") or \
            e.status == 403:
        return api_errors.ObjectApiError(f"gcs denied: {e}")
    if e.status == 404 or e.reason == "notFound":
        if upload_id:
            return api_errors.InvalidUploadID(upload_id)
        if key:
            return api_errors.ObjectNotFound(bucket, key)
        return api_errors.BucketNotFound(bucket)
    if e.status == 409 or e.reason == "conflict":
        if deleting:
            return api_errors.BucketNotEmpty(bucket)
        return api_errors.BucketExists(bucket)
    if e.reason == "invalid" or e.status == 400:
        return api_errors.ObjectApiError(f"gcs invalid argument: {e}")
    return api_errors.ObjectApiError(f"gcs error: {e}")


# ---------------------------------------------------------------------------
# OAuth2: service-account JWT-bearer grant
# ---------------------------------------------------------------------------

def _b64url(raw: bytes) -> str:
    return base64.urlsafe_b64encode(raw).rstrip(b"=").decode()


def sa_token_source(client_email: str, private_key_pem: bytes,
                    token_uri: str, scope: str = _SCOPE):
    """Callable -> (access_token, expires_at): signs an RS256 JWT with
    the service-account key and exchanges it at the token endpoint
    (the google-oauth flow the reference's SDK performs)."""
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import padding
    key = serialization.load_pem_private_key(private_key_pem,
                                             password=None)

    def fetch() -> tuple[str, float]:
        now = time.time()
        header = _b64url(json.dumps({"alg": "RS256",
                                     "typ": "JWT"}).encode())
        claims = _b64url(json.dumps({
            "iss": client_email, "scope": scope, "aud": token_uri,
            "iat": int(now), "exp": int(now) + 3600}).encode())
        signing_input = f"{header}.{claims}".encode()
        sig = key.sign(signing_input, padding.PKCS1v15(),
                       hashes.SHA256())
        assertion = f"{header}.{claims}.{_b64url(sig)}"
        body = urllib.parse.urlencode({
            "grant_type": "urn:ietf:params:oauth:grant-type:jwt-bearer",
            "assertion": assertion}).encode()
        import http.client
        u = urllib.parse.urlsplit(token_uri)
        conn_cls = http.client.HTTPSConnection if u.scheme == "https" \
            else http.client.HTTPConnection
        conn = conn_cls(u.hostname, u.port, timeout=30)
        try:
            conn.request("POST", u.path or "/", body=body, headers={
                "Content-Type": "application/x-www-form-urlencoded"})
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                raise GCSError(resp.status, "oauth",
                               data[:200].decode("utf-8", "replace"))
            out = json.loads(data)
        finally:
            conn.close()
        return out["access_token"], now + float(
            out.get("expires_in", 3600))

    return fetch


def static_token_source(token: str):
    return lambda: (token, time.time() + 10 * 365 * 86400)


# ---------------------------------------------------------------------------
# JSON API client
# ---------------------------------------------------------------------------

class GCSJsonClient:
    """Minimal GCS JSON API client (storage/v1) over http.client."""

    def __init__(self, token_source, project: str = "",
                 host: str = "storage.googleapis.com", port: int = 443,
                 secure: bool = True):
        self.token_source = token_source
        self.project = project
        self.host, self.port, self.secure = host, port, secure
        self._token = ""
        self._token_exp = 0.0

    def _auth(self) -> str:
        if not self._token or time.time() > self._token_exp - 60:
            self._token, self._token_exp = self.token_source()
        return f"Bearer {self._token}"

    def _conn(self):
        import http.client
        cls = http.client.HTTPSConnection if self.secure else \
            http.client.HTTPConnection
        return cls(self.host, self.port, timeout=60)

    def _request(self, method: str, path: str, query: dict = None,
                 body=b"", headers: dict = None, stream: bool = False):
        qs = urllib.parse.urlencode(query or {})
        url = path + (f"?{qs}" if qs else "")
        hdrs = {"Authorization": self._auth()}
        hdrs.update(headers or {})
        if body and "Content-Length" not in hdrs:
            hdrs["Content-Length"] = str(len(body))
        conn = self._conn()
        try:
            conn.request(method, url, body=body or None, headers=hdrs)
            resp = conn.getresponse()
            if resp.status >= 300:
                raw = resp.read()
                conn.close()
                raise self._error(resp.status, raw)
            if stream:
                def gen():
                    try:
                        while True:
                            chunk = resp.read(1 << 20)
                            if not chunk:
                                return
                            yield chunk
                    finally:
                        conn.close()
                return resp, gen()
            data = resp.read()
            conn.close()
            return resp, data
        except GCSError:
            raise
        except OSError as e:
            conn.close()
            raise GCSError(0, "transport", str(e)) from e

    @staticmethod
    def _error(status: int, raw: bytes) -> GCSError:
        reason, message = "", raw[:200].decode("utf-8", "replace")
        try:
            err = json.loads(raw)["error"]
            message = err.get("message", message)
            errs = err.get("errors") or []
            if errs:
                reason = errs[0].get("reason", "")
        except (ValueError, KeyError, TypeError):
            pass
        return GCSError(status, reason, message)

    @staticmethod
    def _obj_path(bucket: str, name: str) -> str:
        return (f"/storage/v1/b/{urllib.parse.quote(bucket, safe='')}"
                f"/o/{urllib.parse.quote(name, safe='')}")

    # -- buckets -----------------------------------------------------------

    def list_buckets(self) -> list[dict]:
        items, token = [], ""
        while True:
            q = {"project": self.project}
            if token:
                q["pageToken"] = token
            _, data = self._request("GET", "/storage/v1/b", q)
            out = json.loads(data)
            items += out.get("items", [])
            token = out.get("nextPageToken", "")
            if not token:
                return items

    def insert_bucket(self, bucket: str) -> None:
        self._request(
            "POST", "/storage/v1/b", {"project": self.project},
            body=json.dumps({"name": bucket}).encode(),
            headers={"Content-Type": "application/json"})

    def get_bucket(self, bucket: str) -> dict:
        _, data = self._request(
            "GET", f"/storage/v1/b/{urllib.parse.quote(bucket)}")
        return json.loads(data)

    def delete_bucket(self, bucket: str) -> None:
        self._request(
            "DELETE", f"/storage/v1/b/{urllib.parse.quote(bucket)}")

    # -- objects -----------------------------------------------------------

    def list_objects(self, bucket: str, prefix: str = "",
                     delimiter: str = "", page_token: str = "",
                     max_results: int = 1000,
                     start_offset: str = "") -> dict:
        q: dict = {"maxResults": max_results}
        if prefix:
            q["prefix"] = prefix
        if delimiter:
            q["delimiter"] = delimiter
        if page_token:
            q["pageToken"] = page_token
        if start_offset:
            q["startOffset"] = start_offset
        _, data = self._request(
            "GET", f"/storage/v1/b/{urllib.parse.quote(bucket)}/o", q)
        return json.loads(data)

    def get_object_meta(self, bucket: str, name: str) -> dict:
        _, data = self._request("GET", self._obj_path(bucket, name))
        return json.loads(data)

    def download(self, bucket: str, name: str, offset: int = 0,
                 length: int = -1):
        headers = {}
        if offset or length >= 0:
            end = "" if length < 0 else str(offset + length - 1)
            headers["Range"] = f"bytes={offset}-{end}"
        return self._request("GET", self._obj_path(bucket, name),
                             {"alt": "media"}, headers=headers,
                             stream=True)[1]

    def upload(self, bucket: str, name: str, data: bytes,
               content_type: str = "",
               metadata: Optional[dict] = None) -> dict:
        """uploadType=multipart: JSON metadata + media in one call."""
        meta = {"name": name}
        if metadata:
            meta["metadata"] = dict(metadata)
        if content_type:
            meta["contentType"] = content_type
        boundary = f"mt_gcs_{_uuid.uuid4().hex}"
        body = io.BytesIO()
        body.write(f"--{boundary}\r\nContent-Type: application/json; "
                   f"charset=UTF-8\r\n\r\n".encode())
        body.write(json.dumps(meta).encode())
        body.write(f"\r\n--{boundary}\r\nContent-Type: "
                   f"{content_type or 'application/octet-stream'}"
                   f"\r\n\r\n".encode())
        body.write(data)
        body.write(f"\r\n--{boundary}--\r\n".encode())
        _, out = self._request(
            "POST",
            f"/upload/storage/v1/b/{urllib.parse.quote(bucket)}/o",
            {"uploadType": "multipart"}, body=body.getvalue(),
            headers={"Content-Type":
                     f"multipart/related; boundary={boundary}"})
        return json.loads(out)

    def delete_object(self, bucket: str, name: str) -> None:
        self._request("DELETE", self._obj_path(bucket, name))

    def compose(self, bucket: str, dst: str, sources: list[str],
                content_type: str = "",
                metadata: Optional[dict] = None) -> dict:
        dest: dict = {}
        if content_type:
            dest["contentType"] = content_type
        if metadata:
            dest["metadata"] = dict(metadata)
        body = json.dumps({
            "sourceObjects": [{"name": s} for s in sources],
            "destination": dest}).encode()
        _, out = self._request(
            "POST", self._obj_path(bucket, dst) + "/compose",
            body=body, headers={"Content-Type": "application/json"})
        return json.loads(out)

    def patch_metadata(self, bucket: str, name: str,
                       metadata: dict) -> dict:
        _, out = self._request(
            "PATCH", self._obj_path(bucket, name),
            body=json.dumps({"metadata": metadata}).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(out)


# ---------------------------------------------------------------------------
# ObjectLayer over the JSON API
# ---------------------------------------------------------------------------

def _rfc3339_ts(s: str) -> float:
    import datetime as _dt
    try:
        return _dt.datetime.fromisoformat(
            s.replace("Z", "+00:00")).timestamp()
    except (TypeError, ValueError):
        return 0.0


def _to_info(bucket: str, item: dict) -> ObjectInfo:
    md5_b64 = item.get("md5Hash", "")
    if md5_b64:
        etag = base64.b64decode(md5_b64).hex()
    else:                                # composite objects have no md5
        etag = item.get("etag", "").strip('"')
    user = {f"x-amz-meta-{k}": v
            for k, v in (item.get("metadata") or {}).items()}
    return ObjectInfo(
        bucket=bucket, name=item.get("name", ""),
        size=int(item.get("size", 0)), etag=etag,
        mod_time=_rfc3339_ts(item.get("updated",
                                      item.get("timeCreated", ""))),
        content_type=item.get("contentType", ""), user_defined=user)


def _mpu_meta_name(uid: str) -> str:
    return f"{_MPU_PATH}/{uid}/{_MPU_META}"


def _mpu_part_name(uid: str, part_number: int, etag: str) -> str:
    return f"{_MPU_PATH}/{uid}/{part_number:05d}.{etag}"


def _mpu_compose_name(uid: str, n: int) -> str:
    return f"{GCS_SYS_TMP}tmp/{uid}/composed-object-{n:05d}"


class GCSJsonGatewayObjects:
    """ObjectLayer over the GCS JSON API (the reference's gateway)."""

    supports_sse_multipart = False

    def __init__(self, client: GCSJsonClient):
        self.c = client

    # -- buckets -----------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        try:
            self.c.insert_bucket(bucket)
        except GCSError as e:
            raise _map_err(e, bucket) from None

    def bucket_exists(self, bucket: str) -> bool:
        try:
            self.c.get_bucket(bucket)
            return True
        except GCSError as e:
            # only "it is not there" reads as False — an auth failure
            # or outage must not look like a missing bucket (callers
            # auto-create on 404)
            if e.status == 404 or e.reason == "notFound":
                return False
            raise _map_err(e, bucket) from None

    def get_bucket_info(self, bucket: str) -> VolInfo:
        try:
            b = self.c.get_bucket(bucket)
        except GCSError as e:
            raise _map_err(e, bucket) from None
        return VolInfo(bucket, _rfc3339_ts(b.get("timeCreated", "")))

    def list_buckets(self) -> list[VolInfo]:
        try:
            return [VolInfo(b["name"],
                            _rfc3339_ts(b.get("timeCreated", "")))
                    for b in self.c.list_buckets()]
        except GCSError as e:
            raise _map_err(e, "") from None

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        try:
            self.c.delete_bucket(bucket)
        except GCSError as e:
            raise _map_err(e, bucket, deleting=True) from None

    def heal_bucket(self, bucket: str) -> None:
        self.get_bucket_info(bucket)

    # -- objects -----------------------------------------------------------

    def put_object(self, bucket: str, key: str, reader, size: int = -1,
                   opts: Optional[PutOptions] = None) -> ObjectInfo:
        opts = opts or PutOptions()
        self.get_bucket_info(bucket)    # the S3 handler does not check
        if isinstance(reader, (bytes, bytearray)):
            body = bytes(reader)
        else:
            if not isinstance(reader, HashReader):
                reader = HashReader(reader, size)
            body = reader.read() if size < 0 else reader.read(size)
            reader.verify()
            reader.close()
        ct = ""
        meta = {}
        for k, v in opts.metadata.items():
            lk = k.lower()
            if lk == "content-type":
                ct = v
            elif lk.startswith("x-amz-meta-"):
                meta[lk[len("x-amz-meta-"):]] = v
        try:
            item = self.c.upload(bucket, key, body, ct, meta)
        except GCSError as e:
            raise _map_err(e, bucket, key) from None
        return _to_info(bucket, item)

    def get_object_info(self, bucket: str, key: str,
                        opts: Optional[GetOptions] = None
                        ) -> ObjectInfo:
        try:
            return _to_info(bucket, self.c.get_object_meta(bucket,
                                                           key))
        except GCSError as e:
            raise _map_err(e, bucket, key) from None

    def get_object(self, bucket: str, key: str, offset: int = 0,
                   length: int = -1,
                   opts: Optional[GetOptions] = None
                   ) -> tuple[ObjectInfo, Iterator[bytes]]:
        info = self.get_object_info(bucket, key, opts)
        if length < 0:
            length = info.size - offset
        try:
            if info.size == 0 or length <= 0:
                return info, iter(())
            return info, self.c.download(bucket, key, offset, length)
        except GCSError as e:
            raise _map_err(e, bucket, key) from None

    def delete_object(self, bucket: str, key: str, version_id: str = "",
                      versioned: bool = False) -> ObjectInfo:
        try:
            self.c.delete_object(bucket, key)
        except GCSError as e:
            raise _map_err(e, bucket, key) from None
        return ObjectInfo(bucket=bucket, name=key)

    def delete_objects(self, bucket: str, objects: list[str]):
        out = []
        for o in objects:
            try:
                self.delete_object(bucket, o)
                out.append(None)
            except Exception as e:  # noqa: BLE001 — per-key result
                out.append(e)
        return out

    def update_object_metadata(self, bucket: str, key: str,
                               metadata: dict, version_id: str = ""):
        meta = {k[len("x-amz-meta-"):] if
                k.lower().startswith("x-amz-meta-") else k: v
                for k, v in metadata.items()
                if k.lower() != "content-type"}
        try:
            self.c.patch_metadata(bucket, key, meta)
        except GCSError as e:
            raise _map_err(e, bucket, key) from None

    def has_object_versions(self, bucket: str, key: str) -> bool:
        try:
            self.get_object_info(bucket, key)
            return True
        except api_errors.ObjectApiError:
            return False

    def heal_object(self, bucket: str, key: str, version_id: str = "",
                    deep_scan: bool = False, dry_run: bool = False):
        from ..object.healing import HealResultItem
        return HealResultItem(bucket=bucket, object=key, disks_total=0)

    # -- listing -----------------------------------------------------------

    def list_objects(self, bucket: str, prefix: str = "",
                     marker: str = "", delimiter: str = "",
                     max_keys: int = 1000):
        objs: list[ObjectInfo] = []
        prefixes: list[str] = []
        token = ""
        try:
            while True:
                out = self.c.list_objects(
                    bucket, prefix, delimiter, token,
                    max_keys + 1, start_offset=marker)
                for item in out.get("items", []):
                    name = item.get("name", "")
                    # the reference hides its own multipart staging
                    # area from listings (gateway-gcs.go ListObjects)
                    if name.startswith(GCS_SYS_TMP) and \
                            not prefix.startswith(GCS_SYS_TMP):
                        continue
                    if marker and name <= marker:
                        continue
                    objs.append(_to_info(bucket, item))
                for p in out.get("prefixes", []):
                    if p.startswith(GCS_SYS_TMP) and \
                            not prefix.startswith(GCS_SYS_TMP):
                        continue
                    if p not in prefixes:
                        prefixes.append(p)
                token = out.get("nextPageToken", "")
                if not token or len(objs) + len(prefixes) > max_keys:
                    break
        except GCSError as e:
            raise _map_err(e, bucket) from None
        truncated = bool(token) or len(objs) + len(prefixes) > max_keys
        combined = sorted(objs, key=lambda o: o.name)[:max_keys]
        return combined, sorted(prefixes), truncated

    def list_object_versions(self, bucket: str, prefix: str = "",
                             marker: str = "", max_keys: int = 1000,
                             version_marker: str = "",
                             delimiter: str = ""):
        objs, pfx, trunc = self.list_objects(bucket, prefix, marker,
                                             delimiter, max_keys)
        return single_version_page(objs, trunc, pfx)

    # -- multipart: compose-based (gateway-gcs.go:988-1380) ----------------

    def new_multipart_upload(self, bucket, key, opts=None) -> str:
        uid = _uuid.uuid4().hex
        meta = dict((opts or PutOptions()).metadata)
        session = {"version": _MPU_META_VERSION, "bucket": bucket,
                   "object": key, "metadata": meta}
        try:
            self.c.upload(bucket, _mpu_meta_name(uid),
                          json.dumps(session).encode(),
                          "application/json")
        except GCSError as e:
            raise _map_err(e, bucket, key) from None
        return uid

    def _session(self, bucket, key, uid) -> dict:
        try:
            stream = self.c.download(bucket, _mpu_meta_name(uid))
            session = json.loads(b"".join(stream))
        except (GCSError, ValueError):
            raise api_errors.InvalidUploadID(uid) from None
        if session.get("version") != _MPU_META_VERSION or \
                session.get("bucket") != bucket or \
                session.get("object") != key:
            raise api_errors.InvalidUploadID(uid)
        return session

    def get_multipart_info(self, bucket, key, uid) -> dict:
        return dict(self._session(bucket, key, uid).get("metadata",
                                                        {}))

    def put_object_part(self, bucket, key, uid, part_number, reader,
                        size=-1):
        self._session(bucket, key, uid)
        if isinstance(reader, (bytes, bytearray)):
            body = bytes(reader)
        else:
            if not isinstance(reader, HashReader):
                reader = HashReader(reader, size)
            body = reader.read() if size < 0 else reader.read(size)
            reader.close()
        etag = hashlib.md5(body).hexdigest()
        try:
            self.c.upload(bucket, _mpu_part_name(uid, part_number,
                                                 etag), body)
        except GCSError as e:
            raise _map_err(e, bucket, key, uid) from None
        return ObjectPartInfo(number=part_number, etag=etag,
                              size=len(body), actual_size=len(body))

    def _list_all(self, bucket: str, prefix: str) -> list[dict]:
        """Every item under a prefix, following page tokens (staging
        areas can exceed one page)."""
        items: list[dict] = []
        token = ""
        while True:
            out = self.c.list_objects(bucket, prefix=prefix,
                                      page_token=token,
                                      max_results=1000)
            items += out.get("items", [])
            token = out.get("nextPageToken", "")
            if not token:
                return items

    def list_object_parts(self, bucket, key, uid, part_marker=0,
                          max_parts=1000):
        self._session(bucket, key, uid)
        out = []
        try:
            items = self._list_all(bucket, f"{_MPU_PATH}/{uid}/")
        except GCSError as e:
            raise _map_err(e, bucket, key, uid) from None
        for item in items:
            base = item["name"].rsplit("/", 1)[-1]
            if base == _MPU_META or "." not in base:
                continue
            num_s, etag = base.split(".", 1)
            out.append(ObjectPartInfo(
                number=int(num_s), etag=etag,
                size=int(item.get("size", 0)),
                actual_size=int(item.get("size", 0))))
        out.sort(key=lambda p: p.number)
        return [p for p in out if p.number > part_marker][:max_parts]

    def list_multipart_uploads(self, bucket, key=""):
        try:
            items = self._list_all(bucket, f"{_MPU_PATH}/")
        except GCSError as e:
            raise _map_err(e, bucket) from None
        ups = []
        for item in items:
            name = item["name"]
            if not name.endswith("/" + _MPU_META):
                continue
            uid = name.split("/")[-2]
            try:
                session = json.loads(b"".join(
                    self.c.download(bucket, name)))
            except (GCSError, ValueError):
                continue
            if key and session.get("object") != key:
                continue
            ups.append({"object": session.get("object", ""),
                        "upload_id": uid,
                        "initiated": _rfc3339_ts(
                            item.get("timeCreated", ""))})
        return ups

    def _cleanup_mpu(self, bucket: str, uid: str) -> None:
        for prefix in (f"{_MPU_PATH}/{uid}/",
                       f"{GCS_SYS_TMP}tmp/{uid}/"):
            # re-list until empty: deletes invalidate page tokens, and
            # a staging area can exceed one page
            for _round in range(64):
                try:
                    items = self.c.list_objects(
                        bucket, prefix=prefix,
                        max_results=1000).get("items", [])
                except GCSError:
                    break
                if not items:
                    break
                for item in items:
                    try:
                        self.c.delete_object(bucket, item["name"])
                    except GCSError:
                        pass

    def abort_multipart_upload(self, bucket, key, uid) -> None:
        self._session(bucket, key, uid)
        self._cleanup_mpu(bucket, uid)

    def complete_multipart_upload(self, bucket, key, uid, parts):
        session = self._session(bucket, key, uid)
        meta = session.get("metadata", {})
        ct = ""
        user_meta = {}
        for k, v in meta.items():
            lk = k.lower()
            if lk == "content-type":
                ct = v
            elif lk.startswith("x-amz-meta-"):
                user_meta[lk[len("x-amz-meta-"):]] = v

        names = []
        sizes = []
        for cp in parts:
            name = _mpu_part_name(uid, cp.part_number,
                                  cp.etag.strip('"'))
            try:
                item = self.c.get_object_meta(bucket, name)
            except GCSError:
                raise api_errors.InvalidPart(cp.part_number) from None
            names.append(name)
            sizes.append(int(item.get("size", 0)))
        # parts except the last must be >= 5 MiB (gateway-gcs.go:1317)
        for i, size in enumerate(sizes[:-1]):
            if size < MIN_PART_SIZE:
                raise api_errors.PartTooSmall(
                    f"part {parts[i].part_number}: {size} bytes "
                    f"(parts except the last need "
                    f">= {MIN_PART_SIZE})")

        try:
            # compose in groups of <= 32, then compose the composes
            if len(names) > MAX_COMPONENTS:
                groups = []
                for i in range(0, len(names), MAX_COMPONENTS):
                    cname = _mpu_compose_name(uid, i // MAX_COMPONENTS)
                    self.c.compose(bucket, cname,
                                   names[i:i + MAX_COMPONENTS], ct,
                                   user_meta)
                    groups.append(cname)
                names = groups
            item = self.c.compose(bucket, key, names, ct, user_meta)
        except GCSError as e:
            raise _map_err(e, bucket, key, uid) from None
        self._cleanup_mpu(bucket, uid)
        info = _to_info(bucket, item)
        # S3 multipart ETags are <md5-of-md5s>-<n>; GCS composites
        # carry crc32c only, so synthesize the S3 shape like the
        # reference's minio.ComputeCompleteMultipartMD5
        md5s = b"".join(bytes.fromhex(cp.etag.strip('"'))
                        for cp in parts)
        info.etag = f"{hashlib.md5(md5s).hexdigest()}-{len(parts)}"
        return info

    def storage_info(self) -> dict:
        return {"total": 0, "free": 0, "used": 0, "online_disks": 1,
                "offline_disks": 0, "sets": 0, "drives_per_set": 0,
                "backend": "gateway-gcs"}

    def close(self) -> None:
        pass


# ---------------------------------------------------------------------------
# XML-interop fallback (the r4 dialect preset, kept behind hmac creds)
# ---------------------------------------------------------------------------

class GCSXmlGatewayObjects(S3GatewayObjects):
    """ObjectLayer over GCS's XML interoperability API (HMAC keys)."""

    def storage_info(self) -> dict:
        out = super().storage_info()
        out["backend"] = "gateway-gcs-xml"
        return out


class GCSGateway:
    """`minio gateway gcs` factory.

    JSON API mode (the reference's): pass `credentials_json` (a
    service-account key file's contents or path) or a pre-fetched
    `token`, plus `project`. XML interop mode: pass `hmac_key` +
    `hmac_secret` from the GCS interoperability settings (the r4
    `access_key`/`secret_key` names still work).
    """

    def __init__(self, project: str = "",
                 credentials_json: str = "", token: str = "",
                 hmac_key: str = "", hmac_secret: str = "",
                 host: str = "storage.googleapis.com", port: int = 443,
                 secure: bool = True, token_uri: str = "",
                 access_key: str = "", secret_key: str = "",
                 region: str = "auto"):
        hmac_key = hmac_key or access_key
        hmac_secret = hmac_secret or secret_key
        if credentials_json or token:
            if token:
                source = static_token_source(token)
            else:
                import os
                if os.path.exists(credentials_json):
                    with open(credentials_json) as f:
                        credentials_json = f.read()
                sa = json.loads(credentials_json)
                source = sa_token_source(
                    sa["client_email"],
                    sa["private_key"].encode(),
                    token_uri or sa.get(
                        "token_uri",
                        "https://oauth2.googleapis.com/token"))
                project = project or sa.get("project_id", "")
            self._client = GCSJsonClient(source, project, host, port,
                                         secure)
            self._mode = "json"
        elif hmac_key:
            self._client = S3Client(host, port,
                                    Credentials(hmac_key, hmac_secret),
                                    region, secure=secure)
            self._mode = "xml"
        else:
            raise ValueError(
                "gateway gcs needs credentials_json/token (JSON API) "
                "or hmac_key/hmac_secret (XML interop)")

    def object_layer(self):
        if self._mode == "json":
            return GCSJsonGatewayObjects(self._client)
        return GCSXmlGatewayObjects(self._client)
