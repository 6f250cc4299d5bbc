"""Atomic file commits with optional fsync/dirsync discipline.

Every raw-file commit in the tree follows one recipe — write a temp
sibling, atomically ``os.replace`` it over the final name — but the
recipe alone only protects against a crash of THIS process: without an
fsync barrier before the rename and a directory fsync after it, a
power cut (or a VM/host death) can surface the rename while the data
blocks are still unwritten — a torn or empty file under the committed
name. That is exactly the rename-before-fsync window the
crash-consistency literature (ALICE's "safe rename" pattern) calls
out.

The barriers are real I/O on the PUT hot path, so they ride one knob:
``MINIO_TPU_FSYNC=on`` (default off — tier-1 timing unchanged; the
kill/restart harness and durability-sensitive deployments turn it on).
``write_atomic`` is the shared helper the registry persist paths and
``xl_storage`` commit paths use; ``fsync_file``/``fsync_dir`` serve
call sites that manage their own file handles (shard appenders).

``load_json_doc`` is the read-side discipline: a checkpoint/registry
loader must treat a torn, truncated, or type-mangled JSON document as
ABSENT (fall back to the previous epoch / re-walk), never crash the
boot path on it.
"""

from __future__ import annotations

import json
import os
import uuid as _uuid
from typing import Optional

from . import knobs

__all__ = ["fsync_enabled", "fsync_file", "fsync_dir", "write_atomic",
           "load_json_doc"]


def fsync_enabled() -> bool:
    return knobs.get_bool("MINIO_TPU_FSYNC")


def fsync_file(f) -> None:
    """Flush + fsync an open file object (or raw fd) when the
    discipline is on. Best-effort on filesystems that refuse."""
    if not fsync_enabled():
        return
    try:
        if hasattr(f, "flush"):
            f.flush()
        os.fsync(f.fileno() if hasattr(f, "fileno") else f)
    except OSError:
        pass


def fsync_dir(path: str) -> None:
    """fsync the DIRECTORY so a just-committed rename survives power
    loss (the rename itself lives in the directory's data blocks)."""
    if not fsync_enabled():
        return
    try:
        dfd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(dfd)
    except OSError:
        pass
    finally:
        os.close(dfd)


def _write_file(path: str, data: bytes) -> None:
    """create → write → (fsync) → close on a bare descriptor: three
    syscalls where a buffered file object adds an fstat and an isatty
    probe of its own, each one a drop and retake of the interpreter
    lock under a fan-out of drive threads."""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o666)
    try:
        view = memoryview(data)
        while len(view):
            view = view[os.write(fd, view):]
        fsync_file(fd)
    finally:
        os.close(fd)


def write_atomic(path: str, data: bytes) -> None:
    """write-temp → (fsync) → rename → (dirsync): the one sanctioned
    raw-file commit. Cleans up the temp on any failure. Callers map
    OSError to their own error taxonomy."""
    tmp = path + "." + _uuid.uuid4().hex[:8] + ".tmp"
    try:
        _write_file(tmp, data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    fsync_dir(os.path.dirname(path) or ".")


def load_json_doc(raw: bytes) -> Optional[dict]:
    """Parse a persisted JSON document tolerantly: a torn/truncated
    file (crash inside the write) or a valid-JSON-but-wrong-type
    prefix (``b"12"`` from a truncated ``{"epoch": 12, ...}`` would
    parse as an int) returns None — the caller falls back to its
    previous copy — instead of raising into a boot path."""
    try:
        doc = json.loads(raw.decode())
    except (ValueError, UnicodeDecodeError):
        return None
    return doc if isinstance(doc, dict) else None
