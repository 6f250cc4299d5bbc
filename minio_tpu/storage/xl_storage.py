"""XLStorage — one local POSIX drive.

The local implementation of StorageAPI (reference: cmd/xl-storage.go).
On-disk layout is the reference's exactly (so its binary can read our
drives):

    <root>/.minio.sys/format.json          drive identity + topology
    <root>/<bucket>/<object>/xl.meta       version journal (xl_meta.py)
    <root>/<bucket>/<object>/<dataDir>/part.N   bitrot-framed shards
    <root>/.minio.sys/tmp/<uuid>/...       staged writes (2-phase commit)
    <root>/.minio.sys/multipart/<sha>/<uploadID>/  multipart sessions

Writes are staged in tmp and committed with an atomic os.replace-based
rename (reference RenameData, cmd/xl-storage.go:2041). Bitrot
verification reads the streaming [digest||block]* framing
(cmd/xl-storage.go bitrotVerify:2339).
"""

from __future__ import annotations

import io
import os
import shutil
import threading
import uuid as _uuid
from typing import BinaryIO, Iterator, Optional

from .. import bitrot as bitrot_mod
from ..utils import atomicfile, crashpoint, knobs, telemetry
from . import errors
from .api import BitrotVerifier, StorageAPI
from .datatypes import DiskInfo, FileInfo, VolInfo
from .format import FORMAT_CONFIG_FILE, MINIO_META_BUCKET, FormatErasureV3
from .xl_meta import XLMetaV2

XL_STORAGE_FORMAT_FILE = "xl.meta"
XL_LEGACY_FORMAT_FILE = "xl.json"   # format v1 (migrated on access)
MINIO_META_TMP_BUCKET = MINIO_META_BUCKET + "/tmp"
MINIO_META_MULTIPART_BUCKET = MINIO_META_BUCKET + "/multipart"
MAX_PATH_LEN = 4096


def _check_path_length(p: str) -> None:
    if len(p) > MAX_PATH_LEN:
        raise errors.FileNameTooLong(p)
    for comp in p.split("/"):
        if len(comp) > 255:
            raise errors.FileNameTooLong(comp)


def _check_path_safe(p: str) -> None:
    """Reject path components that would escape the drive root — S3 keys
    may legally contain '..' (the reference rejects these at the storage
    layer too; see cmd/xl-storage.go path checks)."""
    if p.startswith("/") or p.startswith("\\"):
        raise errors.FileAccessDenied(p)
    for comp in p.replace("\\", "/").split("/"):
        if comp in ("..",):
            raise errors.FileAccessDenied(p)


class _DirectWriter:
    """Sequential O_DIRECT file writer (reference CreateFile's
    odirectWriter, cmd/xl-storage.go:1664 + cmd/fallocate_linux.go):
    bytes stage in a page-aligned mmap buffer and flush to the kernel
    in ALIGN-multiple chunks, bypassing the page cache — big PUTs must
    not evict a node's read cache. The unaligned tail is written after
    clearing O_DIRECT via fcntl (Linux semantics: alignment applies
    per-write, the flag can be dropped mid-file)."""

    ALIGN = 4096
    BUF = 1 << 20
    vectored = False

    def __init__(self, path: str, truncate: bool = True):
        import mmap
        # raises OSError on filesystems without O_DIRECT — callers
        # fall back to buffered IO. Non-truncating mode appends (the
        # open_appender contract); O_DIRECT appends stay aligned only
        # from an empty/aligned file, which open_appender checks.
        flags = os.O_WRONLY | os.O_CREAT | os.O_DIRECT \
            | (os.O_TRUNC if truncate else os.O_APPEND)
        self.fd = os.open(path, flags, 0o644)
        self._buf = mmap.mmap(-1, self.BUF)     # page-aligned
        self._fill = 0
        self._closed = False
        self._syscalls = 0

    def fileno(self) -> int:
        return self.fd

    def _flush_exact(self, view) -> None:
        """os.write may consume a partial (aligned) prefix — e.g. disk
        full mid-flush returns a short count, not an exception; a
        silent short write would corrupt the shard mid-file."""
        at = 0
        while at < len(view):
            n = os.write(self.fd, view[at:])
            self._syscalls += 1
            if n <= 0:
                raise OSError(f"short O_DIRECT write ({at}/{len(view)})")
            at += n

    def write(self, data) -> int:
        mv = memoryview(data).cast("B") if not isinstance(data, bytes) \
            else memoryview(data)
        n = len(mv)
        at = 0
        while at < n:
            take = min(self.BUF - self._fill, n - at)
            self._buf[self._fill:self._fill + take] = mv[at:at + take]
            self._fill += take
            at += take
            if self._fill == self.BUF:
                self._flush_exact(memoryview(self._buf)[:self.BUF])
                self._fill = 0
        return n

    def writev(self, buffers) -> int:
        """The appender's vectored verb, answered through the aligned
        staging buffer one buffer at a time: O_DIRECT's alignment rules
        stay this class's business. Returns the write(2) calls made."""
        before = self._syscalls
        for b in buffers:
            self.write(b)
        return self._syscalls - before

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            aligned = (self._fill // self.ALIGN) * self.ALIGN
            if aligned:
                self._flush_exact(memoryview(self._buf)[:aligned])
            tail = self._fill - aligned
            if tail:
                import fcntl
                flags = fcntl.fcntl(self.fd, fcntl.F_GETFL)
                fcntl.fcntl(self.fd, fcntl.F_SETFL,
                            flags & ~os.O_DIRECT)
                self._flush_exact(
                    memoryview(self._buf)[aligned:self._fill])
            # O_DIRECT bypasses the page cache for DATA only — file
            # size/allocation metadata still needs the barrier
            atomicfile.fsync_file(self.fd)
        finally:
            self._buf.close()
            os.close(self.fd)

    def __del__(self):
        # abandoned writers (a failed shard write drops the handle
        # without close) must not leak the raw fd + pinned mmap the
        # way GC-closed io objects don't
        try:
            if not self._closed:
                self._closed = True
                self._buf.close()
                os.close(self.fd)
        except (OSError, AttributeError):
            pass


class _Appender:
    """Append handle for shard files: an O_APPEND fd with no Python
    buffer between the caller's frames and the kernel, so a group's
    [digest‖block] frames go down in one writev(2) instead of two
    buffered writes a frame (each syscall drops and retakes the
    interpreter lock). `sync` is the shard-write barrier under
    MINIO_TPU_FSYNC: fsync at close — a shard referenced by a
    committed xl.meta must not evaporate in a power cut."""

    vectored = True
    IOV_MAX = 1024

    def __init__(self, path: str, sync: bool = False):
        self.fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                          0o666)
        self._sync = sync
        self._closed = False

    def fileno(self) -> int:
        return self.fd

    def write(self, data) -> int:
        self.writev((data,))
        return memoryview(data).nbytes

    def writev(self, buffers) -> int:
        """Every byte of `buffers`, in order; returns the writev(2)
        calls it took (one, unless the kernel took a prefix: ENOSPC
        and signals return a short count, not an exception). A short
        count advances through the list — no byte is sent twice — and
        a call that takes nothing raises."""
        pending = [b for b in buffers if memoryview(b).nbytes]
        calls = 0
        while pending:
            n = os.writev(self.fd, pending[:self.IOV_MAX])
            calls += 1
            if n <= 0:
                raise OSError(f"writev wrote {n} bytes")
            done = 0
            while done < len(pending):
                size = memoryview(pending[done]).nbytes
                if n < size:
                    break
                n -= size
                done += 1
            del pending[:done]
            if n:
                pending[0] = memoryview(pending[0]).cast("B")[n:]
        return calls

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            if self._sync:
                atomicfile.fsync_file(self.fd)
        finally:
            os.close(self.fd)

    def __del__(self):
        # a failed shard write drops the handle without close
        try:
            if not self._closed:
                self._closed = True
                os.close(self.fd)
        except (OSError, AttributeError):
            pass


def _direct_io_default() -> bool:
    return knobs.get_bool("MINIO_TPU_DIRECT_IO")


class XLStorage(StorageAPI):
    def __init__(self, root: str, direct_io: Optional[bool] = None):
        self.root = os.path.abspath(root)
        try:
            os.makedirs(self.root, exist_ok=True)
        except PermissionError as e:
            raise errors.DiskAccessDenied(str(e)) from e
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        if not os.access(self.root, os.W_OK):
            raise errors.DiskAccessDenied(self.root)
        self._disk_id = ""
        self._lock = threading.Lock()
        self._online = True
        self._healing = False
        # O_DIRECT shard writes (MINIO_TPU_DIRECT_IO=on): page-cache
        # bypass on the PUT path; falls back to buffered per-file when
        # the filesystem refuses (tmpfs)
        self.direct_io = _direct_io_default() if direct_io is None \
            else direct_io

    # -- identity ----------------------------------------------------------

    def __str__(self) -> str:
        return self.root

    def is_online(self) -> bool:
        return self._online

    def is_local(self) -> bool:
        return True

    def endpoint(self) -> str:
        return self.root

    def close(self) -> None:
        pass

    def get_disk_id(self) -> str:
        """Read the drive UUID from format.json (cached; reference
        GetDiskID re-checks on change)."""
        with self._lock:
            if self._disk_id:
                return self._disk_id
            fmt_path = os.path.join(self.root, MINIO_META_BUCKET,
                                    FORMAT_CONFIG_FILE)
            try:
                with open(fmt_path, "rb") as f:
                    fmt = FormatErasureV3.from_json(f.read())
            except FileNotFoundError:
                raise errors.UnformattedDisk(self.root) from None
            except OSError as e:
                raise errors.FaultyDisk(str(e)) from e
            self._disk_id = fmt.this
            return self._disk_id

    def set_disk_id(self, disk_id: str) -> None:
        # Local drives derive their ID from format.json; setter is for
        # remote clients (reference xlStorage.SetDiskID is a no-op too).
        pass

    def healing(self) -> bool:
        return self._healing

    def disk_info(self) -> DiskInfo:
        try:
            st = os.statvfs(self.root)
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        total = st.f_blocks * st.f_frsize
        free = st.f_bavail * st.f_frsize
        disk_id = ""
        try:
            disk_id = self.get_disk_id()
        except errors.StorageError:
            pass
        return DiskInfo(total=total, free=free, used=total - free,
                        fs_type="posix", endpoint=self.root,
                        mount_path=self.root, disk_id=disk_id,
                        healing=self._healing)

    # -- format helpers (used by the format/bootstrap layer) ---------------

    def read_format(self) -> FormatErasureV3:
        data = self.read_all(MINIO_META_BUCKET, FORMAT_CONFIG_FILE)
        return FormatErasureV3.from_json(data)

    def write_format(self, fmt: FormatErasureV3) -> None:
        self.make_vol_bulk(MINIO_META_BUCKET, MINIO_META_TMP_BUCKET,
                           MINIO_META_MULTIPART_BUCKET,
                           MINIO_META_BUCKET + "/buckets")
        self.write_all(MINIO_META_BUCKET, FORMAT_CONFIG_FILE,
                       fmt.to_json().encode())
        with self._lock:
            self._disk_id = fmt.this

    # -- paths -------------------------------------------------------------

    def _vol_dir(self, volume: str) -> str:
        if not volume or volume == "." or volume == "..":
            raise errors.VolumeNotFound(volume)
        _check_path_safe(volume)
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        _check_path_safe(path)
        p = os.path.join(self._vol_dir(volume), path)
        _check_path_length(p)
        return p

    # -- volumes -----------------------------------------------------------

    def make_vol(self, volume: str) -> None:
        vdir = self._vol_dir(volume)
        if os.path.isdir(vdir):
            raise errors.VolumeExists(volume)
        try:
            os.makedirs(vdir)
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def make_vol_bulk(self, *volumes: str) -> None:
        for v in volumes:
            os.makedirs(self._vol_dir(v), exist_ok=True)

    def list_vols(self) -> list[VolInfo]:
        out = []
        try:
            for name in sorted(os.listdir(self.root)):
                full = os.path.join(self.root, name)
                if os.path.isdir(full) and name != MINIO_META_BUCKET:
                    out.append(VolInfo(name=name,
                                       created=os.stat(full).st_ctime))
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        return out

    def stat_vol(self, volume: str) -> VolInfo:
        vdir = self._vol_dir(volume)
        try:
            st = os.stat(vdir)
        except FileNotFoundError:
            raise errors.VolumeNotFound(volume) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        return VolInfo(name=volume, created=st.st_ctime)

    def delete_vol(self, volume: str, force: bool = False) -> None:
        vdir = self._vol_dir(volume)
        try:
            if force:
                shutil.rmtree(vdir)
            else:
                os.rmdir(vdir)
        except FileNotFoundError:
            raise errors.VolumeNotFound(volume) from None
        except OSError as e:
            if os.path.isdir(vdir) and os.listdir(vdir):
                raise errors.VolumeNotEmpty(volume) from e
            raise errors.FaultyDisk(str(e)) from e

    # -- raw files ---------------------------------------------------------

    def read_all(self, volume: str, path: str) -> bytes:
        fp = self._file_path(volume, path)
        try:
            with open(fp, "rb") as f:
                return f.read()
        except FileNotFoundError:
            if not os.path.isdir(self._vol_dir(volume)):
                raise errors.VolumeNotFound(volume) from None
            raise errors.FileNotFound(path) from None
        except IsADirectoryError:
            raise errors.FileNotFound(path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        self._commit_file(self._file_path(volume, path), data)

    def _commit_file(self, fp: str, data: bytes, made: bool = False) -> None:
        """write_all's body. `made`: the caller has seen fp's directory
        (it made it, or read from it), so none is made unless the write
        misses it."""
        try:
            if not made:
                os.makedirs(os.path.dirname(fp), exist_ok=True)
            # torn-write injection context for in-process crash tests:
            # an armed action receives path=/data= and can commit a
            # truncated copy to the final name before aborting (what
            # power loss without the fsync discipline produces)
            crashpoint.hit("storage.write_all.commit", path=fp,
                           data=data)
            # write-temp → (fsync) → rename → (dirsync): MINIO_TPU_FSYNC
            # turns the barriers on (pkg/safe analog + ALICE safe-rename)
            try:
                atomicfile.write_atomic(fp, data)
            except FileNotFoundError:
                if not made:
                    raise
                os.makedirs(os.path.dirname(fp), exist_ok=True)
                atomicfile.write_atomic(fp, data)
        except NotADirectoryError:
            raise errors.FileParentIsFile(fp) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def append_file(self, volume: str, path: str, buf: bytes) -> None:
        if not os.path.isdir(self._vol_dir(volume)):
            raise errors.VolumeNotFound(volume)
        fp = self._file_path(volume, path)
        try:
            with telemetry.span("disk.append_file", bytes=len(buf)):
                os.makedirs(os.path.dirname(fp), exist_ok=True)
                with open(fp, "ab") as f:
                    f.write(buf)
                    # remote disks stream shards through THIS verb (the
                    # RPC client has no appender), so the shard-durable-
                    # before-meta-commit barrier must live here too
                    atomicfile.fsync_file(f)
        except NotADirectoryError:
            raise errors.FileParentIsFile(fp) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def has_appender(self) -> bool:
        """Capability probe for open_appender — wrappers delegate this,
        so a guard wrapper can expose open_appender unconditionally
        while the probe still reflects the backend's real support."""
        return True

    def open_appender(self, volume: str, path: str):
        """Persistent append handle for the shard-write hot path: the
        bitrot writer hands a group's [digest‖block] frames straight to
        the OS file (`writev`) instead of re-buffering them in Python
        and re-opening the file per flush.
        Local drives only — remote disks keep the buffered append_file
        batches (one RPC per flush, not per frame)."""
        if not os.path.isdir(self._vol_dir(volume)):
            raise errors.VolumeNotFound(volume)
        fp = self._file_path(volume, path)
        try:
            os.makedirs(os.path.dirname(fp), exist_ok=True)
            if self.direct_io:
                # append semantics must match the buffered path: only
                # go direct when the append offset is aligned (fresh
                # tmp shard files — the hot path — start at zero)
                try:
                    existing = os.path.getsize(fp)
                except OSError:
                    existing = 0
                if existing % _DirectWriter.ALIGN == 0:
                    try:
                        return _DirectWriter(fp, truncate=False)
                    except OSError:
                        pass      # fs without O_DIRECT: buffered
            # shard files must be durable BEFORE the xl.meta commit
            # references them: sync at close under the discipline
            return _Appender(fp, sync=atomicfile.fsync_enabled())
        except NotADirectoryError:
            raise errors.FileParentIsFile(fp) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def create_file(self, volume: str, path: str, size: int,
                    reader: BinaryIO) -> None:
        """Stream `size` bytes (exactly) from reader into a fresh file
        (reference CreateFile, cmd/xl-storage.go:1664: fallocate +
        sequential write; errLessData/errMoreData on mismatch)."""
        with telemetry.span("disk.create_file", size=size):
            self._create_file(volume, path, size, reader)

    def _create_file(self, volume: str, path: str, size: int,
                     reader: BinaryIO) -> None:
        fp = self._file_path(volume, path)
        if not os.path.isdir(self._vol_dir(volume)):
            raise errors.VolumeNotFound(volume)
        try:
            os.makedirs(os.path.dirname(fp), exist_ok=True)
            f = None
            if self.direct_io and size >= _DirectWriter.ALIGN:
                try:
                    f = _DirectWriter(fp)
                except OSError:
                    f = None              # tmpfs etc.: buffered
            if f is None:
                f = open(fp, "wb")
            try:
                if size > 0:
                    try:
                        os.posix_fallocate(f.fileno(), 0, size)
                    except OSError:
                        pass
                remaining = size
                while True:
                    chunk = reader.read(min(1 << 20, remaining)
                                        if size >= 0 else 1 << 20)
                    if not chunk:
                        break
                    if size >= 0 and len(chunk) > remaining:
                        raise errors.MoreData(path)
                    f.write(chunk)
                    remaining -= len(chunk)
                    if size >= 0 and remaining == 0:
                        if reader.read(1):
                            raise errors.MoreData(path)
                        break
                if size >= 0 and remaining > 0:
                    raise errors.LessData(path)
            finally:
                if not isinstance(f, _DirectWriter):
                    # _DirectWriter barriers inside its own close
                    # (after the unaligned-tail flush)
                    atomicfile.fsync_file(f)
                f.close()
        except NotADirectoryError:
            raise errors.FileParentIsFile(fp) from None
        except (errors.StorageError,):
            raise
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def read_file(self, volume: str, path: str, offset: int, length: int,
                  verifier: Optional[BitrotVerifier] = None) -> bytes:
        fp = self._file_path(volume, path)
        with telemetry.span("disk.read_file", length=length):
            return self._read_file(fp, volume, path, offset, length,
                                   verifier)

    def _read_file(self, fp: str, volume: str, path: str, offset: int,
                   length: int,
                   verifier: Optional[BitrotVerifier] = None) -> bytes:
        try:
            with open(fp, "rb") as f:
                if verifier is not None:
                    whole = f.read()
                    digest = bitrot_mod.hash_shard(
                        whole,
                        bitrot_mod.BitrotAlgorithm.from_string(
                            verifier.algorithm))
                    if digest != verifier.digest:
                        raise errors.BitrotHashMismatch(
                            verifier.digest.hex(), digest.hex())
                    return whole[offset:offset + length]
                f.seek(offset)
                return f.read(length)
        except FileNotFoundError:
            if not os.path.isdir(self._vol_dir(volume)):
                raise errors.VolumeNotFound(volume) from None
            raise errors.FileNotFound(path) from None
        except IsADirectoryError:
            raise errors.IsNotRegular(path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def read_file_stream(self, volume: str, path: str, offset: int,
                         length: int) -> BinaryIO:
        fp = self._file_path(volume, path)
        try:
            f = open(fp, "rb")
        except FileNotFoundError:
            if not os.path.isdir(self._vol_dir(volume)):
                raise errors.VolumeNotFound(volume) from None
            raise errors.FileNotFound(path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        f.seek(offset)
        return _LimitedReader(f, length)

    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None:
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        try:
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            os.replace(src, dst)
        except FileNotFoundError:
            raise errors.FileNotFound(src_path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        atomicfile.fsync_dir(os.path.dirname(dst))
        self._cleanup_empty_parents(src_volume, os.path.dirname(src))

    def delete_file(self, volume: str, path: str,
                    recursive: bool = False) -> None:
        fp = self._file_path(volume, path)
        try:
            if os.path.isdir(fp):
                if recursive:
                    shutil.rmtree(fp)
                else:
                    os.rmdir(fp)
            else:
                os.unlink(fp)
        except FileNotFoundError:
            raise errors.FileNotFound(path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        self._cleanup_empty_parents(volume, os.path.dirname(fp))

    def _cleanup_empty_parents(self, volume: str, dirpath: str) -> None:
        """Remove now-empty parent dirs up to (not incl.) the volume root
        (reference deleteFile parent sweep)."""
        vol = self._vol_dir(volume)
        while dirpath.startswith(vol) and dirpath != vol:
            try:
                os.rmdir(dirpath)
            except OSError:
                return
            dirpath = os.path.dirname(dirpath)

    def check_file(self, volume: str, path: str) -> None:
        fp = self._file_path(volume, path)
        if not os.path.isfile(os.path.join(fp, XL_STORAGE_FORMAT_FILE)) \
                and not os.path.isfile(
                    os.path.join(fp, XL_LEGACY_FORMAT_FILE)):
            raise errors.FileNotFound(path)

    def list_dir(self, volume: str, dir_path: str,
                 count: int = -1) -> list[str]:
        """Sorted entries; directories get a trailing slash (reference
        ListDir/readDirN semantics)."""
        vdir = self._vol_dir(volume)
        if not os.path.isdir(vdir):
            raise errors.VolumeNotFound(volume)
        full = os.path.join(vdir, dir_path) if dir_path else vdir
        try:
            names = sorted(os.listdir(full))
        except FileNotFoundError:
            raise errors.FileNotFound(dir_path) from None
        except NotADirectoryError:
            raise errors.FileNotFound(dir_path) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e
        out = []
        for n in names:
            if os.path.isdir(os.path.join(full, n)):
                out.append(n + "/")
            else:
                out.append(n)
            if 0 < count <= len(out):
                break
        return out

    # -- metadata ----------------------------------------------------------

    def _read_xl_meta(self, volume: str, path: str) -> XLMetaV2:
        try:
            buf = self.read_all(volume,
                                os.path.join(path, XL_STORAGE_FORMAT_FILE))
        except errors.FileNotFound:
            # legacy v1 drive: migrate xl.json -> xl.meta on first touch
            # (reference migrates at startup/access,
            # cmd/xl-storage-format-v1.go + readVersion fallback)
            from .xl_meta import from_xl_v1_json
            legacy = self.read_all(
                volume, os.path.join(path, XL_LEGACY_FORMAT_FILE))
            meta = from_xl_v1_json(legacy)
            self.write_all(volume,
                           os.path.join(path, XL_STORAGE_FORMAT_FILE),
                           meta.dumps())
            try:
                os.remove(self._file_path(
                    volume, os.path.join(path, XL_LEGACY_FORMAT_FILE)))
            except OSError:
                pass
            return meta
        return XLMetaV2.loads(buf)

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        """Append fi as a version into xl.meta (creating it if absent) —
        reference WriteMetadata (cmd/xl-storage.go:1219)."""
        try:
            meta = self._read_xl_meta(volume, path)
        except errors.FileNotFound:
            meta = XLMetaV2()
        meta.add_version(fi)
        self.write_all(volume, os.path.join(path, XL_STORAGE_FORMAT_FILE),
                       meta.dumps())

    def read_version(self, volume: str, path: str,
                     version_id: str = "") -> FileInfo:
        meta = self._read_xl_meta(volume, path)
        return meta.to_file_info(volume, path, version_id)

    def read_versions(self, volume: str, path: str) -> list[FileInfo]:
        meta = self._read_xl_meta(volume, path)
        return meta.list_file_infos(volume, path)

    def delete_version(self, volume: str, path: str, fi: FileInfo) -> None:
        """Drop one version; purge its data dir; remove xl.meta (and the
        object dir) when the journal empties (reference DeleteVersion,
        cmd/xl-storage.go:1147)."""
        meta = self._read_xl_meta(volume, path)
        data_dir, last = meta.delete_version(fi)
        if data_dir:
            try:
                self.delete_file(volume, os.path.join(path, data_dir),
                                 recursive=True)
            except errors.FileNotFound:
                pass
        if last:
            try:
                self.delete_file(volume,
                                 os.path.join(path, XL_STORAGE_FORMAT_FILE))
            except errors.FileNotFound:
                pass
        else:
            self.write_all(volume,
                           os.path.join(path, XL_STORAGE_FORMAT_FILE),
                           meta.dumps())

    def rename_data(self, src_volume: str, src_path: str, data_dir: str,
                    dst_volume: str, dst_path: str,
                    version_id: str = "",
                    fi: Optional[FileInfo] = None) -> None:
        """Commit a staged write: merge the committed version of src's
        xl.meta into dst's journal, move the data dir, drop src
        (reference RenameData, cmd/xl-storage.go:2041 — the
        2-phase-commit finish). `version_id` names the version being
        committed; without it the latest entry is assumed (correct
        only when the staged meta holds one version). `fi` is the
        version itself: with it src is taken for a staging directory
        that holds the data dir alone — no staged xl.meta is read."""
        with telemetry.span("disk.rename_data") as sp:
            dst = self._rename_data(src_volume, src_path, data_dir,
                                    dst_volume, dst_path, version_id, fi)
            if sp is not None:
                sp.attrs.update(src_read=int(fi is None), dst=dst)

    def _dst_journal(self, volume: str, path: str
                     ) -> tuple[XLMetaV2, str, bool]:
        """The journal a commit into volume/path merges into, what was
        found there — `journal` (an xl.meta), `legacy` (an xl.json,
        migrated), `corrupt` (an xl.meta that does not parse, dropped),
        `fresh` (none) — and whether the object directory was made
        here, empty. A fresh key costs one failed open and the mkdir it
        needs anyway; only a directory that is there without an
        xl.meta pays for the legacy probe."""
        obj = self._file_path(volume, path)
        try:
            with open(os.path.join(obj, XL_STORAGE_FORMAT_FILE), "rb") as f:
                buf = f.read()
        except FileNotFoundError:
            if self._make_object_dir(volume, obj):
                return XLMetaV2(), "fresh", True
            found = "legacy"           # a legacy drive, or a prefix
        except OSError:
            found = "journal"          # read_all names the error
        else:
            try:
                return XLMetaV2.loads(buf), "journal", False
            except errors.FileCorrupt:
                # a journal torn by a crash inside its write (the fsync
                # discipline off) is dropped, as upstream RenameData
                # drops one ("Data appears corrupt"): the versions it
                # held are on the other drives, and heal brings them
                return XLMetaV2(), "corrupt", False
        try:
            return self._read_xl_meta(volume, path), found, False
        except errors.FileNotFound:
            return XLMetaV2(), "fresh", False

    def _make_object_dir(self, volume: str, obj: str) -> bool:
        """mkdir an object directory; False when it is there already."""
        try:
            try:
                os.mkdir(obj)
            except FileNotFoundError:
                # parents missing: a nested key, or no such volume
                if not os.path.isdir(self._vol_dir(volume)):
                    raise errors.VolumeNotFound(volume) from None
                os.makedirs(obj, exist_ok=True)
            return True
        except FileExistsError:
            return False
        except NotADirectoryError:
            raise errors.FileParentIsFile(obj) from None
        except OSError as e:
            raise errors.FaultyDisk(str(e)) from e

    def _rename_data(self, src_volume: str, src_path: str, data_dir: str,
                     dst_volume: str, dst_path: str,
                     version_id: str = "",
                     fi: Optional[FileInfo] = None) -> str:
        staged = fi is not None
        if not staged:
            # the staged multipart session meta holds the session
            # placeholder AND the final version — "latest by mod time"
            # is wrong for version-faithful replays (preserved mod
            # times sort behind the placeholder), so the commit names
            # its version
            fi = self._read_xl_meta(src_volume, src_path).to_file_info(
                dst_volume, dst_path, version_id)
        dst_meta, dst, made = self._dst_journal(dst_volume, dst_path)
        dst_meta.add_version(fi)

        if data_dir:
            src_data = self._file_path(src_volume,
                                       os.path.join(src_path, data_dir))
            dst_data = self._file_path(dst_volume,
                                       os.path.join(dst_path, data_dir))
            try:
                if not made:
                    os.makedirs(os.path.dirname(dst_data), exist_ok=True)
                    if os.path.isdir(dst_data):
                        # a replayed commit (its staging already gone)
                        # must not take the committed data dir with it
                        if not os.path.isdir(src_data):
                            raise errors.FileNotFound(src_path)
                        shutil.rmtree(dst_data)
                os.replace(src_data, dst_data)
            except FileNotFoundError:
                raise errors.FileNotFound(src_path) from None
            except OSError as e:
                raise errors.FaultyDisk(str(e)) from e
            atomicfile.fsync_dir(os.path.dirname(dst_data))

        # the single-drive torn window: data dir in place, xl.meta not
        # yet rewritten — restart-side fsck must reclaim the orphan
        crashpoint.hit("storage.rename_data.before_meta")
        self._commit_file(
            self._file_path(dst_volume,
                            os.path.join(dst_path, XL_STORAGE_FORMAT_FILE)),
            dst_meta.dumps(), made=True)
        if not (staged and self._drop_staging(src_volume, src_path)):
            try:
                self.delete_file(src_volume, src_path, recursive=True)
            except errors.FileNotFound:
                pass
        return dst

    def _drop_staging(self, volume: str, path: str) -> bool:
        """Remove a staging directory that, its data dir renamed away,
        is empty: an rmdir. False when it holds more (or is gone): the
        caller's recursive delete decides."""
        fp = self._file_path(volume, path)
        try:
            os.rmdir(fp)
        except OSError:
            return False
        self._cleanup_empty_parents(volume, os.path.dirname(fp))
        return True

    # -- integrity ---------------------------------------------------------

    def check_parts(self, volume: str, path: str, fi: FileInfo) -> None:
        """Every part file must exist with its exact shard-file size
        (reference CheckParts, cmd/xl-storage.go)."""
        for part in fi.parts:
            pp = os.path.join(path, fi.data_dir, f"part.{part.number}")
            fp = self._file_path(volume, pp)
            csum = fi.erasure.get_checksum_info(part.number)
            algo = (bitrot_mod.BitrotAlgorithm.from_string(csum.algorithm)
                    if csum else bitrot_mod.DEFAULT_BITROT_ALGORITHM)
            want = bitrot_mod.bitrot_shard_file_size(
                fi.erasure.shard_file_size(part.size),
                fi.erasure.shard_size(), algo)
            try:
                st = os.stat(fp)
            except FileNotFoundError:
                raise errors.FileNotFound(pp) from None
            except OSError as e:
                raise errors.FaultyDisk(str(e)) from e
            if st.st_size < want:
                raise errors.FileCorrupt(
                    f"{pp}: size {st.st_size} < expected {want}")

    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        """Full bitrot scan of every part (reference VerifyFile,
        cmd/xl-storage.go:2410): streaming algos verify each
        [digest||block] frame; whole-file algos hash the entire shard."""
        for part in fi.parts:
            pp = os.path.join(path, fi.data_dir, f"part.{part.number}")
            csum = fi.erasure.get_checksum_info(part.number)
            algo = bitrot_mod.BitrotAlgorithm.from_string(
                csum.algorithm) if csum else \
                bitrot_mod.DEFAULT_BITROT_ALGORITHM
            fp = self._file_path(volume, pp)
            try:
                f = open(fp, "rb")
            except FileNotFoundError:
                raise errors.FileNotFound(pp) from None
            except OSError as e:
                raise errors.FaultyDisk(str(e)) from e
            with f:
                if algo.streaming:
                    self._verify_streaming(f, fi, part.size, algo, pp)
                else:
                    h = bitrot_mod.new_hasher(algo)
                    while True:
                        chunk = f.read(1 << 20)
                        if not chunk:
                            break
                        h.update(chunk)
                    if csum and csum.hash and h.digest() != csum.hash:
                        raise errors.BitrotHashMismatch(
                            csum.hash.hex(), h.digest().hex())

    def _verify_streaming(self, f, fi: FileInfo, part_size: int,
                          algo, pp: str) -> None:
        shard_size = fi.erasure.shard_size()
        remaining = fi.erasure.shard_file_size(part_size)
        while remaining > 0:
            want_digest = f.read(algo.digest_size)
            if len(want_digest) != algo.digest_size:
                raise errors.FileCorrupt(f"{pp}: truncated bitrot frame")
            n = min(shard_size, remaining)
            block = f.read(n)
            if len(block) != n:
                raise errors.FileCorrupt(f"{pp}: truncated shard block")
            got = bitrot_mod.hash_shard(block, algo)
            if got != want_digest:
                raise errors.BitrotHashMismatch(want_digest.hex(), got.hex())
            remaining -= n

    # -- walk --------------------------------------------------------------

    def walk(self, volume: str, dir_path: str = "", marker: str = "",
             recursive: bool = True) -> Iterator[FileInfo]:
        """Lexically sorted stream of latest-version FileInfos under a
        prefix (reference Walk, cmd/xl-storage.go:1015)."""
        vdir = self._vol_dir(volume)
        if not os.path.isdir(vdir):
            raise errors.VolumeNotFound(volume)

        def _walk(rel: str) -> Iterator[FileInfo]:
            full = os.path.join(vdir, rel) if rel else vdir
            try:
                entries = sorted(os.listdir(full))
            except OSError:
                return
            if XL_STORAGE_FORMAT_FILE in entries:
                if rel and (not marker or rel > marker):
                    try:
                        yield self.read_version(volume, rel)
                    except errors.StorageError:
                        pass
                return
            for e in entries:
                sub = os.path.join(rel, e) if rel else e
                subfull = os.path.join(full, e)
                if not os.path.isdir(subfull):
                    continue
                if recursive:
                    yield from _walk(sub)
                elif os.path.isfile(
                        os.path.join(subfull, XL_STORAGE_FORMAT_FILE)):
                    # flat object: yield it, not a pseudo-prefix
                    if not marker or sub > marker:
                        try:
                            yield self.read_version(volume, sub)
                        except errors.StorageError:
                            pass
                elif not marker or sub > marker:
                    yield FileInfo(volume=volume, name=sub + "/")

        yield from _walk(dir_path)

    def walk_versions(self, volume: str, dir_path: str = "",
                      marker: str = "", recursive: bool = True
                      ) -> Iterator[list[FileInfo]]:
        for fi in self.walk(volume, dir_path, marker, recursive):
            if fi.name.endswith("/"):
                continue
            try:
                yield self.read_versions(volume, fi.name)
            except errors.StorageError:
                pass


class _LimitedReader(io.RawIOBase):
    """Reads at most `length` bytes from an underlying file, closing it
    on exhaustion (reference ReadFileStream's LimitReader)."""

    def __init__(self, f, length: int):
        self._f = f
        self._remaining = length

    def read(self, n: int = -1) -> bytes:
        if self._remaining <= 0:
            return b""
        if n is None or n < 0:
            n = self._remaining
        data = self._f.read(min(n, self._remaining))
        self._remaining -= len(data)
        if not data:
            self._remaining = 0
        return data

    def readable(self) -> bool:
        return True

    def close(self) -> None:
        try:
            self._f.close()
        finally:
            super().close()
