"""Azure Blob gateway: ObjectLayer over an Azure storage account
(reference cmd/gateway/azure/gateway-azure.go:1-1752): buckets map to
containers, objects to block blobs, multipart parts to staged
uncommitted blocks committed by Put Block List — the azure-native
multipart the reference uses, so an 8 GiB upload never buffers
server-side.

The REST transport (utils/azureclient.py) signs with SharedKey and has
an injectable connection factory; tests run the whole gateway against
an in-process blob server.
"""

from __future__ import annotations

import base64
import hashlib
import uuid as _uuid
from email.utils import parsedate_to_datetime
from typing import Iterator, Optional

from ..object import api_errors
from ..object.engine import GetOptions, PutOptions
from ..object.hash_reader import HashReader
from ..storage.datatypes import ObjectInfo, ObjectPartInfo, VolInfo, single_version_page
from ..utils.azureclient import AzureBlobClient, AzureClientError


def _map_err(e: AzureClientError, bucket: str, key: str = "") -> Exception:
    if e.code == "ContainerNotFound" or (e.status == 404 and not key):
        return api_errors.BucketNotFound(bucket)
    if e.code == "BlobNotFound" or e.status == 404:
        return api_errors.ObjectNotFound(bucket, key)
    if e.code == "ContainerAlreadyExists":
        return api_errors.BucketExists(bucket)
    if e.status == 403:
        return api_errors.ObjectApiError(f"azure denied: {e.code}")
    return api_errors.ObjectApiError(f"azure error: {e}")


def _block_id(upload_id: str, part_number: int, sub: int) -> str:
    """Deterministic sortable block id (the reference encodes part +
    sub-part into fixed-width base64 ids so Put Block List commits in
    part order)."""
    raw = f"{upload_id[:8]}-{part_number:05d}-{sub:05d}"
    return base64.b64encode(raw.encode()).decode()


def _http_date_ts(value: str) -> float:
    try:
        return parsedate_to_datetime(value).timestamp()
    except (TypeError, ValueError):
        return 0.0


class AzureGatewayObjects:
    """ObjectLayer over Azure Blob Storage."""

    supports_sse_multipart = False
    MAX_BLOCK = 100 << 20          # service max block size

    def __init__(self, client: AzureBlobClient):
        self.c = client
        # upload-id -> {bucket, key, metadata, parts: {n: (etag, [ids], size)}}
        self._mpu: dict[str, dict] = {}

    # -- buckets -----------------------------------------------------------

    def make_bucket(self, bucket: str) -> None:
        try:
            self.c.create_container(bucket)
        except AzureClientError as e:
            raise _map_err(e, bucket) from None

    def bucket_exists(self, bucket: str) -> bool:
        return self.c.container_exists(bucket)

    def get_bucket_info(self, bucket: str) -> VolInfo:
        if not self.c.container_exists(bucket):
            raise api_errors.BucketNotFound(bucket)
        return VolInfo(bucket, 0.0)

    def list_buckets(self) -> list[VolInfo]:
        return [VolInfo(n, 0.0) for n in self.c.list_containers()]

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        try:
            self.c.delete_container(bucket)
        except AzureClientError as e:
            raise _map_err(e, bucket) from None

    def heal_bucket(self, bucket: str) -> None:
        self.get_bucket_info(bucket)

    # -- objects -----------------------------------------------------------

    # bodies above this stage as blocks instead of one in-memory PUT
    STREAM_THRESHOLD = 16 << 20
    STAGE_CHUNK = 8 << 20

    @staticmethod
    def _encode_meta_key(key: str) -> str:
        """S3 metadata/control keys (x-amz-meta-*, X-Amz-Tagging,
        object-lock headers, etag) are not valid Azure metadata
        identifiers; base32 keeps them reversible without loss (the
        reference's s3MetaToAzureProperties does a lossier mangle)."""
        enc = base64.b32encode(key.lower().encode()).decode()
        return "k" + enc.rstrip("=").lower()

    @staticmethod
    def _decode_meta_key(name: str) -> Optional[str]:
        if not name.startswith("k"):
            return None
        enc = name[1:].upper()
        enc += "=" * (-len(enc) % 8)
        try:
            return base64.b32decode(enc).decode()
        except Exception:  # noqa: BLE001 — foreign metadata
            return None

    @classmethod
    def _meta_split(cls, metadata: dict) -> tuple[dict, str]:
        """user metadata -> (azure metadata dict, content type). EVERY
        key except content-type round-trips (tagging, object-lock,
        legal-hold and custom metadata must survive the gateway)."""
        meta, ctype = {}, ""
        for k, v in (metadata or {}).items():
            lk = k.lower()
            if lk == "content-type":
                ctype = v
            else:
                meta[cls._encode_meta_key(lk)] = str(v)
        return meta, ctype

    @classmethod
    def _meta_join(cls, headers: dict) -> dict:
        user = {}
        for k, v in headers.items():
            if not k.startswith("x-ms-meta-"):
                continue
            name = k[len("x-ms-meta-"):]
            decoded = cls._decode_meta_key(name)
            user[decoded if decoded is not None
                 else f"x-amz-meta-{name}"] = v
        return user

    def _read_all(self, reader, size: int) -> bytes:
        if isinstance(reader, (bytes, bytearray)):
            return bytes(reader)
        if not isinstance(reader, HashReader):
            reader = HashReader(reader, size)
        body = reader.read() if size < 0 else reader.read(size)
        reader.verify()
        reader.close()
        return body

    def put_object(self, bucket: str, key: str, reader, size: int = -1,
                   opts: Optional[PutOptions] = None) -> ObjectInfo:
        opts = opts or PutOptions()
        self.get_bucket_info(bucket)    # the S3 handler does not check
        if not isinstance(reader, (bytes, bytearray)) and \
                (size < 0 or size > self.STREAM_THRESHOLD):
            return self._put_object_streamed(bucket, key, reader, size,
                                             opts)
        body = self._read_all(reader, size)
        etag = hashlib.md5(body).hexdigest()
        md = dict(opts.metadata)
        md["etag"] = etag            # service ETags are not md5: pin it
        meta, ctype = self._meta_split(md)
        try:
            self.c.put_blob(bucket, key, body, meta, ctype)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        return ObjectInfo(bucket=bucket, name=key, size=len(body),
                          etag=etag)

    def _put_object_streamed(self, bucket: str, key: str, reader,
                             size: int, opts: PutOptions) -> ObjectInfo:
        """Large/unknown-size PUT: stage STAGE_CHUNK blocks, commit via
        Put Block List — constant memory, like the multipart path."""
        if not isinstance(reader, HashReader):
            reader = HashReader(reader, size)
        uid = _uuid.uuid4().hex
        ids: list[str] = []
        md5 = hashlib.md5()
        total = 0
        try:
            while True:
                chunk = reader.read(self.STAGE_CHUNK)
                if not chunk:
                    break
                md5.update(chunk)
                total += len(chunk)
                bid = _block_id(uid, 0, len(ids))
                self.c.put_block(bucket, key, bid, chunk)
                ids.append(bid)
            reader.verify()
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        finally:
            reader.close()
        etag = md5.hexdigest()
        md = dict(opts.metadata)
        md["etag"] = etag
        meta, ctype = self._meta_split(md)
        try:
            if not ids:              # empty object
                self.c.put_blob(bucket, key, b"", meta, ctype)
            else:
                self.c.put_block_list(bucket, key, ids, meta, ctype)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        return ObjectInfo(bucket=bucket, name=key, size=total,
                          etag=etag)

    def get_object_info(self, bucket: str, key: str,
                        opts: Optional[GetOptions] = None) -> ObjectInfo:
        try:
            h = self.c.get_blob_props(bucket, key)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        user = self._meta_join(h)
        etag = user.pop("etag", "") or h.get("etag", "").strip('"')
        return ObjectInfo(
            bucket=bucket, name=key,
            size=int(h.get("content-length", 0) or 0),
            etag=etag,
            mod_time=_http_date_ts(h.get("last-modified", "")),
            content_type=h.get("content-type", ""),
            user_defined=user)

    def get_object(self, bucket: str, key: str, offset: int = 0,
                   length: int = -1,
                   opts: Optional[GetOptions] = None
                   ) -> tuple[ObjectInfo, Iterator[bytes]]:
        info = self.get_object_info(bucket, key, opts)
        if length < 0:
            length = info.size - offset
        if length <= 0:
            return info, iter(())
        try:
            # full-object reads go without a Range header (a range of
            # "bytes=0--1" on a zero-byte blob is a 416 on real Azure)
            if offset == 0 and length >= info.size:
                _h, stream = self.c.get_blob(bucket, key)
            else:
                _h, stream = self.c.get_blob(bucket, key, offset,
                                             length)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        return info, stream

    def delete_object(self, bucket: str, key: str, version_id: str = "",
                      versioned: bool = False) -> ObjectInfo:
        try:
            self.c.delete_blob(bucket, key)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        return ObjectInfo(bucket=bucket, name=key)

    def delete_objects(self, bucket: str, objects: list[str]):
        out = []
        for key in objects:
            try:
                self.delete_object(bucket, key)
                out.append(None)
            except api_errors.ObjectApiError as e:
                out.append(e)
        return out

    def update_object_metadata(self, bucket: str, key: str,
                               metadata: dict, version_id: str = ""):
        info, stream = self.get_object(bucket, key)
        body = b"".join(stream)
        return self.put_object(bucket, key, body,
                               opts=PutOptions(metadata=metadata))

    def has_object_versions(self, bucket: str, key: str) -> bool:
        try:
            self.get_object_info(bucket, key)
            return True
        except api_errors.ObjectApiError:
            return False

    def heal_object(self, bucket: str, key: str, version_id: str = "",
                    deep_scan: bool = False, dry_run: bool = False):
        from ..object.healing import HealResultItem
        self.get_object_info(bucket, key)
        return HealResultItem(bucket=bucket, object=key)

    # -- listing -----------------------------------------------------------

    def list_objects(self, bucket: str, prefix: str = "",
                     marker: str = "", delimiter: str = "",
                     max_keys: int = 1000):
        """S3 markers are key names; Azure markers are opaque
        continuation tokens. A token cache maps the last key of each
        served page to Azure's token; on a cache miss (server restart,
        foreign marker) the gateway pages from the start and skips up
        to the marker — slower but correct against real Azure (feeding
        a key name into Azure's marker parameter is a 400)."""
        self.get_bucket_info(bucket)
        cache = getattr(self, "_list_tokens", None)
        if cache is None:
            cache = self._list_tokens = {}
        # start from the cached page token for this marker (may be ""
        # on a miss => page from the start); ALWAYS filter keys <=
        # marker, so a mid-page cut resumes correctly either way
        token = cache.get((bucket, prefix, delimiter, marker), "") \
            if marker else ""

        objs: list[ObjectInfo] = []
        prefixes: list[str] = []
        truncated = False
        while True:
            page_token = token
            try:
                blobs, pfx, next_tok = self.c.list_blobs(
                    bucket, prefix, delimiter, page_token,
                    max_results=max(max_keys, 1000))
            except AzureClientError as e:
                raise _map_err(e, bucket) from None
            for p in pfx:
                if marker and p <= marker:
                    continue
                if p not in prefixes:
                    prefixes.append(p)
            kept = 0
            for b in blobs:
                if marker and b["name"] <= marker:
                    continue
                kept += 1
                meta_etag = self._decode_etag_meta(b.get("metadata"))
                objs.append(ObjectInfo(
                    bucket=bucket, name=b["name"], size=b["size"],
                    etag=meta_etag or b["etag"],
                    mod_time=_http_date_ts(b["last_modified"])))
            if len(objs) + len(prefixes) >= max_keys:
                cut = max_keys - len(prefixes)
                dropped = len(objs) - cut
                objs = objs[:cut]
                truncated = bool(next_tok) or dropped > 0
                if objs and truncated:
                    # the next page re-fetches from THIS page's token
                    # and skips past the last served key
                    cache[(bucket, prefix, delimiter,
                           objs[-1].name)] = page_token
                if len(cache) > 4096:
                    cache.clear()      # bounded; misses just rescan
                break
            if not next_tok:
                break
            token = next_tok
        return objs, prefixes, truncated

    @classmethod
    def _decode_etag_meta(cls, meta: Optional[dict]) -> str:
        """Pinned md5 ETag out of a listing's blob metadata."""
        for name, v in (meta or {}).items():
            if cls._decode_meta_key(name) == "etag":
                return v
        return ""

    def list_object_versions(self, bucket: str, prefix: str = "",
                             marker: str = "", max_keys: int = 1000,
                             version_marker: str = "",
                             delimiter: str = ""):
        objs, pfx, trunc = self.list_objects(bucket, prefix, marker,
                                             delimiter,
                                             max_keys=max_keys)
        return single_version_page(objs, trunc, pfx)

    # -- multipart: azure-native staged blocks -----------------------------

    def new_multipart_upload(self, bucket, key, opts=None) -> str:
        self.get_bucket_info(bucket)
        uid = str(_uuid.uuid4())
        self._mpu[uid] = {"bucket": bucket, "key": key, "parts": {},
                          "metadata": dict(
                              (opts or PutOptions()).metadata)}
        return uid

    def get_multipart_info(self, bucket, key, uid) -> dict:
        return dict(self._up(bucket, key, uid).get("metadata", {}))

    def _up(self, bucket, key, uid):
        mpu = self._mpu.get(uid)
        if mpu is None or mpu["bucket"] != bucket or mpu["key"] != key:
            raise api_errors.InvalidUploadID(uid)
        return mpu

    def put_object_part(self, bucket, key, uid, part_number, reader,
                        size=-1):
        mpu = self._up(bucket, key, uid)
        body = self._read_all(reader, size)   # verify()s declared size
        etag = hashlib.md5(body).hexdigest()
        ids = []
        try:
            for sub in range(0, max(len(body), 1), self.MAX_BLOCK):
                bid = _block_id(uid, part_number, sub // self.MAX_BLOCK)
                self.c.put_block(bucket, key, bid,
                                 body[sub:sub + self.MAX_BLOCK])
                ids.append(bid)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        mpu["parts"][part_number] = (etag, ids, len(body))
        return ObjectPartInfo(number=part_number, etag=etag,
                              size=len(body), actual_size=len(body))

    def list_object_parts(self, bucket, key, uid, part_marker=0,
                          max_parts=1000):
        mpu = self._up(bucket, key, uid)
        return [ObjectPartInfo(number=n, etag=e, size=sz,
                               actual_size=sz)
                for n, (e, _ids, sz) in sorted(mpu["parts"].items())
                if n > part_marker][:max_parts]

    def list_multipart_uploads(self, bucket, key=""):
        return [{"object": m["key"], "upload_id": uid, "initiated": 0.0}
                for uid, m in self._mpu.items()
                if m["bucket"] == bucket and (not key or m["key"] == key)]

    def abort_multipart_upload(self, bucket, key, uid) -> None:
        self._up(bucket, key, uid)
        self._mpu.pop(uid, None)

    def complete_multipart_upload(self, bucket, key, uid, parts):
        mpu = self._up(bucket, key, uid)
        block_ids: list[str] = []
        total = 0
        for cp in parts:
            stored = mpu["parts"].get(cp.part_number)
            if stored is None or stored[0] != cp.etag.strip('"'):
                raise api_errors.InvalidPart(cp.part_number)
            block_ids.extend(stored[1])
            total += stored[2]
        part_etags = "".join(mpu["parts"][cp.part_number][0]
                             for cp in parts)
        etag = hashlib.md5(bytes.fromhex(part_etags)).hexdigest() \
            + f"-{len(parts)}"
        md = dict(mpu["metadata"])
        md["etag"] = etag
        meta, ctype = self._meta_split(md)
        try:
            self.c.put_block_list(bucket, key, block_ids, meta, ctype)
        except AzureClientError as e:
            raise _map_err(e, bucket, key) from None
        self._mpu.pop(uid, None)
        return ObjectInfo(bucket=bucket, name=key, size=total, etag=etag)

    # -- misc --------------------------------------------------------------

    def storage_info(self) -> dict:
        return {"total": 0, "free": 0, "used": 0, "online_disks": 1,
                "offline_disks": 0, "sets": 0, "drives_per_set": 0,
                "backend": "gateway-azure"}

    def close(self) -> None:
        pass


class AzureGateway:
    """Gateway factory (reference cmd/gateway-main.go `minio gateway
    azure` registration shape)."""

    def __init__(self, account: str, key_b64: str, host: str,
                 port: int = 10000, secure: bool = False):
        self.client = AzureBlobClient(account, key_b64, host, port,
                                      secure)

    def object_layer(self) -> AzureGatewayObjects:
        return AzureGatewayObjects(self.client)
