"""S3 gateway: proxy ObjectLayer over an upstream S3 endpoint
(reference cmd/gateway/s3/gateway-s3.go): every ObjectLayer verb maps to
a client call against the backend; this node adds its own auth/IAM,
caching, and policy layers in front."""

from __future__ import annotations

import io
from typing import Iterator, Optional

from ..object import api_errors
from ..object.engine import GetOptions, PutOptions
from ..object.hash_reader import HashReader
from ..storage.datatypes import ObjectInfo, VolInfo, single_version_page
from ..s3.credentials import Credentials
from ..utils.s3client import S3Client, S3ClientError


def _map_err(e: S3ClientError, bucket: str, key: str = "") -> Exception:
    if e.code == "NoSuchBucket" or (e.status == 404 and not key):
        return api_errors.BucketNotFound(bucket)
    if e.code == "NoSuchKey" or e.status == 404:
        return api_errors.ObjectNotFound(bucket, key)
    if e.code == "BucketAlreadyOwnedByYou" or e.code == "BucketAlreadyExists":
        return api_errors.BucketExists(bucket)
    if e.status == 403:
        return api_errors.ObjectApiError(f"upstream denied: {e.code}")
    return api_errors.ObjectApiError(f"upstream error: {e}")


class S3GatewayObjects:
    """ObjectLayer over a remote S3 endpoint."""

    # parts are buffered and re-uploaded whole; local SSE would break
    # part-ETag semantics (the handler checks this capability)
    supports_sse_multipart = False

    def __init__(self, client: S3Client):
        self.c = client

    # -- buckets -----------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        try:
            self.c.make_bucket(bucket)
        except S3ClientError as e:
            raise _map_err(e, bucket) from None

    def bucket_exists(self, bucket: str) -> bool:
        return self.c.bucket_exists(bucket)

    def get_bucket_info(self, bucket: str) -> VolInfo:
        if not self.c.bucket_exists(bucket):
            raise api_errors.BucketNotFound(bucket)
        return VolInfo(bucket, 0.0)

    def list_buckets(self) -> list[VolInfo]:
        return [VolInfo(n, t) for n, t in self.c.list_buckets()]

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        try:
            self.c.delete_bucket(bucket)
        except S3ClientError as e:
            raise _map_err(e, bucket) from None

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
        md = {}
        for k, v in opts.metadata.items():
            lk = k.lower()
            if lk.startswith("x-amz-meta-") or lk in (
                    "content-type", "content-encoding", "cache-control"):
                md[k] = v
        try:
            etag = self.c.put_object(bucket, key, body, md)
        except S3ClientError as e:
            raise _map_err(e, bucket, key) from None
        return ObjectInfo(bucket=bucket, name=key, size=len(body),
                          etag=etag)

    def get_object_info(self, bucket: str, key: str,
                        opts: Optional[GetOptions] = None) -> ObjectInfo:
        try:
            h = self.c.head_object(bucket, key)
        except S3ClientError as e:
            raise _map_err(e, bucket, key) from None
        from email.utils import parsedate_to_datetime
        try:
            mt = parsedate_to_datetime(h.get("last-modified",
                                             "")).timestamp()
        except (TypeError, ValueError):
            mt = 0.0
        return ObjectInfo(
            bucket=bucket, name=key,
            size=int(h.get("content-length", 0) or 0),
            etag=h.get("etag", "").strip('"'), mod_time=mt,
            content_type=h.get("content-type", ""),
            user_defined={k: v for k, v in h.items()
                          if k.startswith("x-amz-meta-")})

    def get_object(self, bucket: str, key: str, offset: int = 0,
                   length: int = -1,
                   opts: Optional[GetOptions] = None
                   ) -> tuple[ObjectInfo, Iterator[bytes]]:
        info = self.get_object_info(bucket, key, opts)
        if length < 0:
            length = info.size - offset
        try:
            _, stream = self.c.get_object(bucket, key, offset, length)
        except S3ClientError as e:
            raise _map_err(e, bucket, key) from None
        return info, stream

    def delete_object(self, bucket: str, key: str, version_id: str = "",
                      versioned: bool = False) -> ObjectInfo:
        try:
            self.c.delete_object(bucket, key)
        except S3ClientError as e:
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
        raise api_errors.NotImplementedError_(
            "metadata update through the S3 gateway")

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
        try:
            objs, prefixes, _tok = self.c.list_objects_v2(
                bucket, prefix, delimiter, "", max_keys)
        except S3ClientError as e:
            raise _map_err(e, bucket) from None
        out = [ObjectInfo(bucket=bucket, name=o["key"], size=o["size"],
                          etag=o["etag"], mod_time=o["mod_time"])
               for o in objs if not marker or o["key"] > marker]
        return out, prefixes, bool(_tok)

    def list_object_versions(self, bucket: str, prefix: str = "",
                             marker: str = "", max_keys: int = 1000,
                             version_marker: str = "",
                             delimiter: str = ""):
        objs, pfx, trunc = self.list_objects(bucket, prefix, marker,
                                             delimiter, max_keys)
        return single_version_page(objs, trunc, pfx)

    # -- multipart (buffered passthrough) ----------------------------------

    def new_multipart_upload(self, bucket, key, opts=None) -> str:
        import uuid as _uuid
        self.get_bucket_info(bucket)
        uid = str(_uuid.uuid4())
        self._mpu = getattr(self, "_mpu", {})
        self._mpu[uid] = {"bucket": bucket, "key": key, "parts": {},
                          "metadata": dict((opts or PutOptions()).metadata)}
        return uid

    def get_multipart_info(self, bucket, key, uid) -> dict:
        return dict(self._up(bucket, key, uid).get("metadata", {}))

    def _up(self, bucket, key, uid):
        mpu = getattr(self, "_mpu", {}).get(uid)
        if mpu is None or mpu["bucket"] != bucket or mpu["key"] != key:
            raise api_errors.InvalidUploadID(uid)
        return mpu

    def put_object_part(self, bucket, key, uid, part_number, reader,
                        size=-1):
        import hashlib as _hl
        mpu = self._up(bucket, key, uid)
        if isinstance(reader, (bytes, bytearray)):
            body = bytes(reader)
        else:
            if not isinstance(reader, HashReader):
                reader = HashReader(reader, size)
            body = reader.read() if size < 0 else reader.read(size)
            reader.close()
        etag = _hl.md5(body).hexdigest()
        from ..storage.datatypes import ObjectPartInfo
        mpu["parts"][part_number] = (etag, body)
        return ObjectPartInfo(number=part_number, etag=etag,
                              size=len(body), actual_size=len(body))

    def list_object_parts(self, bucket, key, uid, part_marker=0,
                          max_parts=1000):
        from ..storage.datatypes import ObjectPartInfo
        mpu = self._up(bucket, key, uid)
        return [ObjectPartInfo(number=n, etag=e, size=len(b),
                               actual_size=len(b))
                for n, (e, b) in sorted(mpu["parts"].items())
                if n > part_marker][:max_parts]

    def list_multipart_uploads(self, bucket, key=""):
        return [{"object": m["key"], "upload_id": uid, "initiated": 0.0}
                for uid, m in getattr(self, "_mpu", {}).items()
                if m["bucket"] == bucket and (not key or m["key"] == key)]

    def abort_multipart_upload(self, bucket, key, uid) -> None:
        self._up(bucket, key, uid)
        self._mpu.pop(uid, None)

    def complete_multipart_upload(self, bucket, key, uid, parts):
        mpu = self._up(bucket, key, uid)
        body = b""
        for cp in parts:
            stored = mpu["parts"].get(cp.part_number)
            if stored is None or stored[0] != cp.etag.strip('"'):
                raise api_errors.InvalidPart(cp.part_number)
            body += stored[1]
        info = self.put_object(bucket, key, body,
                               opts=PutOptions(metadata=mpu["metadata"]))
        self._mpu.pop(uid, None)
        return info

    def storage_info(self) -> dict:
        return {"total": 0, "free": 0, "used": 0, "online_disks": 1,
                "offline_disks": 0, "sets": 0, "drives_per_set": 0,
                "backend": "gateway-s3"}

    def close(self) -> None:
        pass


class S3Gateway:
    def __init__(self, host: str, port: int, access_key: str,
                 secret_key: str, region: str = "us-east-1"):
        self.client = S3Client(host, port,
                               Credentials(access_key, secret_key),
                               region)

    def object_layer(self) -> S3GatewayObjects:
        return S3GatewayObjects(self.client)
