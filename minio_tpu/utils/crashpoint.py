"""Declarative registry of named crash/fault points.

Crash-consistency testing (ALICE / CrashMonkey style) needs process
death at NAMED points inside multi-step commit windows — not random
kill -9 storms whose coverage nobody can enumerate. Every multi-file
commit in the tree (xl.meta write→rename, shard fan-out→meta commit,
multipart complete, metacache manifest/segment persist, registry epoch
writes, rebalance/resync checkpoints, MRF/journal drains) threads a
``crashpoint.hit("<name>")`` call through its window; the names are
declared HERE — name, doc, commit window — and ``tools/check``'s
``crashpoint`` rule enforces the discipline (a multi-file commit
function without a hit is a lint error, a hit naming an unregistered
point too), while the README crashpoint table is generated from this
registry exactly like the knob table.

Arming, two ways:

  * **process mode** (the kill/restart harness):
    ``MINIO_TPU_CRASHPOINT=<name>[:<nth>]`` — the Nth hit of ``name``
    calls ``os._exit(137)``: no atexit, no finally blocks, no flushes —
    the closest a process can get to SIGKILLing itself at a named
    instruction. ``tests/harness/proc.py`` seeds this env per node.

  * **in-process mode** (unit tests): ``arm(name, nth=, action=)``
    installs a callable fired at the Nth hit — raise
    :class:`CrashpointAbort` to abort the commit mid-window (the
    torn-write / partial-rename injector), or do arbitrary damage via
    the ``ctx`` kwargs the hit site passes (e.g. ``path=``/``data=``
    on raw file commits). ``disarm()`` in the test's finally.

``hit()`` is one global ``is None`` check when nothing is armed — the
hot paths pay nothing.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Callable, Dict, List, Optional

# NOTE: no top-level package imports — tools/check/crashtable.py loads
# this file standalone (importlib, no package context) to generate the
# README table, exactly like knobtable.py loads knobs.py. The knobs
# import happens lazily inside _parse_env.

__all__ = [
    "Crashpoint", "CRASHPOINTS", "define", "names", "hit",
    "arm", "arm_exit", "disarm", "armed_name", "hits", "refresh",
    "CrashpointAbort", "torn_write_action",
    "render_table", "TABLE_BEGIN", "TABLE_END",
]

CRASH_EXIT_CODE = 137        # what SIGKILL would have produced


class CrashpointAbort(Exception):
    """Raised by the default in-process action: the commit dies
    mid-window exactly where a crash would have, but the test process
    survives to inspect the wreckage."""

    def __init__(self, name: str):
        super().__init__(f"crashpoint {name} fired")
        self.name = name


class Crashpoint:
    """One declared point: name, one-line doc, the commit window it
    interrupts (module-level description for the README table)."""

    __slots__ = ("name", "doc", "window")

    def __init__(self, name: str, doc: str, window: str):
        self.name = name
        self.doc = doc
        self.window = window


CRASHPOINTS: Dict[str, Crashpoint] = {}


def define(name: str, doc: str, window: str) -> Crashpoint:
    assert name not in CRASHPOINTS, f"crashpoint {name} declared twice"
    cp = Crashpoint(name, doc, window)
    CRASHPOINTS[name] = cp
    return cp


def names() -> List[str]:
    return list(CRASHPOINTS)


# ---------------------------------------------------------------------------
# the registry — grouped by commit window, in README table order
# ---------------------------------------------------------------------------

_W = "PUT commit (engine._commit)"
define("put.shards.before_meta",
       "after the last shard-write fan-out (the writers closed in it) "
       "or the fallback close fan-out, before the rename fan-out — "
       "closed shards in tmp, no metadata anywhere", _W)
define("put.meta.before_rename",
       "after the last shard-write fan-out (the writers closed in it) "
       "or the fallback close fan-out, and after the per-drive "
       "versions are made in memory, before the rename fan-out — "
       "closed shards in tmp, no metadata anywhere", _W)
define("put.rename.partial",
       "inside the per-disk rename fan-out (one hit per disk; arm "
       ":<nth> to die after n-1 disks committed) — a torn commit "
       "below/at write quorum", _W)

_W = "Drive commit (xl_storage.rename_data)"
define("storage.rename_data.before_meta",
       "on ONE drive, after the data dir moved into place, before "
       "that drive's xl.meta write — an unreferenced data dir the "
       "fsck orphan sweep must reclaim", _W)
define("storage.write_all.commit",
       "inside every raw-file temp-write→rename commit (one hit per "
       "call, arm :<nth>); in-process actions receive path=/data= — "
       "the torn-write injector", _W)

_W = "Multipart (multipart.py)"
define("multipart.part.before_rename",
       "after a part's shards staged in tmp, before the rename into "
       "the session data dir — the session journal never saw the "
       "part", _W)
define("multipart.complete.before_rename",
       "after the final session meta write, before the commit "
       "rename_data fan-out — session intact, object absent", _W)
define("multipart.complete.rename.partial",
       "inside complete's per-disk rename fan-out (one hit per disk, "
       "arm :<nth>)", _W)

_W = "Metacache persist (object/metacache.py)"
define("metacache.persist.segment",
       "after each persisted index segment write (one hit per "
       "segment, arm :<nth>) — segments without a manifest", _W)
define("metacache.persist.before_manifest",
       "after every segment landed, before the manifest write — the "
       "orphan-segment window", _W)
define("metacache.journal.drain",
       "in the journal drainer, before a claimed delta batch applies "
       "— acked writes whose index deltas die with the process", _W)

_W = "Registry epoch writes"
define("topology.save.pool",
       "inside TopologyStore.save's per-pool loop (one hit per pool, "
       "arm :<nth>) — pools disagree on the topology epoch", _W)
define("tier.save.pool",
       "inside TierManager.save's per-pool loop (arm :<nth>) — pools "
       "disagree on the tier-config epoch", _W)
define("replicate.registry.save.pool",
       "inside TargetRegistry.save's per-pool loop (arm :<nth>) — "
       "pools disagree on the replication-target epoch", _W)
define("qos.save.pool",
       "inside QoSRegistry.save's per-pool loop (arm :<nth>) — pools "
       "disagree on the tenant-budget epoch", _W)
define("notify.registry.save.pool",
       "inside NotifyTargetRegistry.save's per-pool loop (arm :<nth>) "
       "— pools disagree on the notification-target epoch", _W)

_W = "Background checkpoints"
define("rebalance.checkpoint",
       "inside the drain's per-pool checkpoint write (arm :<nth>) — "
       "resume must tolerate a stale/torn checkpoint", _W)
define("resync.checkpoint",
       "inside the resync walker's per-pool checkpoint write (arm "
       ":<nth>) — resume must re-cover the un-checkpointed tail", _W)

_W = "Queues and drains"
define("replicate.push.before_apply",
       "in the sync worker, after spooling the source version, before "
       "the target apply — the push must survive as a retry, never a "
       "half-applied replica", _W)
define("mrf.drain.before_heal",
       "in the MRF drainer, after dequeuing an entry, before its heal "
       "runs — a crashed drain loses only retries, never objects", _W)
define("notify.queue.persist",
       "after one event record lands in a target's durable queue, "
       "before its delivery attempt — a restart must redrive exactly "
       "this entry (at-least-once, never lost)", _W)

_W = "Event journal (utils/eventlog.py)"
define("eventlog.persist.segment",
       "in the journal flusher, before a segment's temp-write→rename "
       "commit — a crash here must leave the prior segment set "
       "readable (restart serves the surviving prefix)", _W)

del _W


# ---------------------------------------------------------------------------
# arming + firing
# ---------------------------------------------------------------------------

class _Armed:
    __slots__ = ("name", "nth", "action", "count")

    def __init__(self, name: str, nth: int,
                 action: Optional[Callable[..., None]]):
        self.name = name
        self.nth = max(int(nth), 1)
        self.action = action
        self.count = 0


_mu = threading.Lock()
_UNSET = object()
# _UNSET until the env is parsed; then None (disarmed) or an _Armed
_armed = _UNSET


def _parse_env():
    from . import knobs
    spec = knobs.get_str("MINIO_TPU_CRASHPOINT").strip()
    if not spec:
        return None
    name, _, nth = spec.partition(":")
    if name not in CRASHPOINTS:
        # a typo'd point must not silently arm nothing AND must not
        # crash an otherwise-healthy request path: say so once, loudly
        print(f"minio_tpu: MINIO_TPU_CRASHPOINT names unregistered "
              f"point {name!r} — never fires", file=sys.stderr)
    try:
        n = int(nth) if nth else 1
    except ValueError:
        n = 1
    _note_armed(name, n)
    return _Armed(name, n, None)


def _note_armed(name: str, nth: int) -> None:
    """Journal that fault injection is live in this process — incident
    bundles must distinguish injected faults from organic ones."""
    try:
        from . import eventlog
        eventlog.emit("crashpoint.armed", point=name, nth=nth)
    except Exception:  # noqa: BLE001 — arming must not depend on the journal
        pass


def refresh() -> None:
    """Re-read MINIO_TPU_CRASHPOINT (tests that monkeypatch the env
    call this; server processes read it once, lazily)."""
    global _armed
    with _mu:
        _armed = _parse_env()


def arm(name: str, nth: int = 1,
        action: Optional[Callable[..., None]] = None) -> None:
    """In-process arming. ``action(name, **ctx)`` runs at the Nth hit;
    None means the default in-process action: raise CrashpointAbort
    (the commit dies mid-window, the process survives)."""
    global _armed
    if name not in CRASHPOINTS:
        raise KeyError(f"unregistered crashpoint {name!r} — declare it "
                       "in minio_tpu/utils/crashpoint.py")
    with _mu:
        _armed = _Armed(name, nth, action or _raise_abort)
    _note_armed(name, nth)


def arm_exit(name: str, nth: int = 1) -> None:
    """In-process arming of the PROCESS action (os._exit) — what the
    env spec does; for tests that spawn their own children."""
    arm(name, nth, action=_hard_exit)


def disarm() -> None:
    global _armed
    with _mu:
        _armed = None


def armed_name() -> Optional[str]:
    a = _armed
    if a is _UNSET or a is None:
        return None
    return a.name


def hits(name: str) -> int:
    """How many times the armed point has been hit (0 when another —
    or no — point is armed)."""
    a = _armed
    if a is _UNSET or a is None or a.name != name:
        return 0
    return a.count


def _raise_abort(name: str, **ctx) -> None:
    raise CrashpointAbort(name)


def _hard_exit(name: str, **ctx) -> None:
    # no atexit, no finally, no stream flushes: the closest an
    # in-process call gets to SIGKILL-at-an-instruction
    os._exit(CRASH_EXIT_CODE)


def torn_write_action(fraction: float = 0.5) -> Callable[..., None]:
    """An action for hit sites that pass ``path=``/``data=`` context
    (raw file commits): writes a truncated copy straight to the FINAL
    path, then aborts — the torn-file state a power cut mid-commit
    without fsync discipline leaves behind."""
    def act(name: str, **ctx) -> None:
        path, data = ctx.get("path"), ctx.get("data")
        if path is not None and data is not None:
            with open(path, "wb") as f:
                f.write(bytes(data)[: max(int(len(data) * fraction), 1)])
        raise CrashpointAbort(name)
    return act


def hit(name: str, **ctx) -> None:
    """Fire-if-armed. Call this AT the named instruction inside the
    commit window the registry describes. Near-free when disarmed."""
    global _armed
    a = _armed
    if a is _UNSET:
        with _mu:
            if _armed is _UNSET:
                _armed = _parse_env()
            a = _armed
    if a is None or a.name != name:
        return
    with _mu:
        a.count += 1
        fire = a.count == a.nth
    if fire:
        (a.action or _hard_exit)(name, **ctx)


# ---------------------------------------------------------------------------
# README table generator (tools/check/crashtable.py drift-checks this)
# ---------------------------------------------------------------------------

TABLE_BEGIN = ("<!-- crashpoint-table:begin "
               "(generated by tools/check/run.py --write-crashpoint-table) -->")
TABLE_END = "<!-- crashpoint-table:end -->"


def render_table() -> str:
    """The README crashpoint table, grouped by commit window —
    generated, never hand-edited (the `crashpoint` drift check pins
    it)."""
    lines: List[str] = []
    window = None
    for cp in CRASHPOINTS.values():
        if cp.window != window:
            window = cp.window
            if lines:
                lines.append("")
            lines.append(f"**{window}**")
            lines.append("")
            lines.append("| Crashpoint | Fires |")
            lines.append("|---|---|")
        lines.append(f"| `{cp.name}` | {cp.doc} |")
    return "\n".join(lines) + "\n"
