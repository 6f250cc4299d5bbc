"""Declarative registry of every ``MINIO_TPU_*`` tuning knob.

Before this module, ~45 env knobs were scattered as raw
``os.environ.get("MINIO_TPU_…")`` reads across a dozen modules, each
with its own parsing idiom (`_env_f`, `_env_int`, `_flag`, inline
``int(...)``) and a hand-maintained README table that drifted from the
code. Now every knob is declared HERE — name, type, default, doc — and
read through the typed getters below. ``tools/check`` enforces the
discipline two ways:

  * the ``knob-env`` lint rule fails any raw ``MINIO_TPU_*`` environ
    access outside this module (and any getter call naming an
    unregistered knob);
  * ``tools/check/knobtable.py`` regenerates the README knob table from
    this registry and the drift check fails when the committed table
    disagrees.

Getters read the ENVIRONMENT at call time (never cached here): tests
flip knobs with ``monkeypatch.setenv`` and modules that want an
import-time snapshot simply call the getter at module scope, exactly
like the old reads. Parse failures fall back to the declared default —
a typo'd value must degrade to documented behavior, not crash the
server at boot.

Boolean knobs accept ``on/1/true/yes`` and ``off/0/false/no``
(case-insensitive); anything else means the default. Defaults may be
callables (evaluated per read) for host-derived values such as the
staging-ring size; ``display`` carries the README-facing rendering of
such defaults ("2×cores", "64 MiB").
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Union

__all__ = [
    "Knob", "KNOBS", "define", "get", "all_knobs",
    "get_str", "get_int", "get_float", "get_bool", "get_raw", "is_set",
    "render_table", "TABLE_BEGIN", "TABLE_END",
]

_TRUE = ("on", "1", "true", "yes")
_FALSE = ("off", "0", "false", "no")

Default = Union[str, int, float, bool, Callable[[], Union[int, float, str]]]


class Knob:
    """One declared knob: name, type, default, one-line doc."""

    __slots__ = ("name", "type", "default", "doc", "section", "display")

    def __init__(self, name: str, type_: str, default: Default,
                 doc: str, section: str, display: str = ""):
        assert name.startswith("MINIO_TPU_"), name
        assert type_ in ("str", "int", "float", "bool"), type_
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc
        self.section = section
        self.display = display

    def resolve_default(self):
        d = self.default
        return d() if callable(d) else d

    def default_display(self) -> str:
        if self.display:
            return self.display
        d = self.resolve_default()
        if self.type == "bool":
            return "on" if d else "off"
        return str(d)


KNOBS: Dict[str, Knob] = {}


def define(name: str, type_: str, default: Default, doc: str,
           section: str, display: str = "") -> Knob:
    assert name not in KNOBS, f"knob {name} declared twice"
    k = Knob(name, type_, default, doc, section, display)
    KNOBS[name] = k
    return k


def get(name: str) -> Knob:
    try:
        return KNOBS[name]
    except KeyError:
        raise KeyError(f"unregistered knob {name!r} — declare it in "
                       "minio_tpu/utils/knobs.py") from None


def all_knobs() -> List[Knob]:
    return list(KNOBS.values())


# ---------------------------------------------------------------------------
# typed getters — the ONLY sanctioned MINIO_TPU_* environment reads
# ---------------------------------------------------------------------------

def get_raw(name: str) -> Optional[str]:
    """The raw environment value, or None when unset. Registered knobs
    only (a typo'd name must fail loudly, not silently default)."""
    get(name)
    return os.environ.get(name)


def is_set(name: str) -> bool:
    get(name)
    return name in os.environ


def get_str(name: str) -> str:
    k = get(name)
    v = os.environ.get(name)
    return str(k.resolve_default()) if v is None else v


def get_int(name: str) -> int:
    k = get(name)
    v = os.environ.get(name)
    if v is not None:
        try:
            return int(v)
        except ValueError:
            pass
    return int(k.resolve_default())


def get_float(name: str) -> float:
    k = get(name)
    v = os.environ.get(name)
    if v is not None:
        try:
            return float(v)
        except ValueError:
            pass
    return float(k.resolve_default())


def get_bool(name: str) -> bool:
    k = get(name)
    v = os.environ.get(name)
    if v is not None:
        s = v.strip().lower()
        if s in _TRUE:
            return True
        if s in _FALSE:
            return False
    return bool(k.resolve_default())


# ---------------------------------------------------------------------------
# the registry — grouped by plane, in README table order
# ---------------------------------------------------------------------------

_S = "Data path"
define("MINIO_TPU_PIPELINE", "bool", True,
       "`off` selects the serial PUT/GET hot loops", _S)
define("MINIO_TPU_PIPELINE_DEPTH", "int", 2,
       "bounded queue depth between pipeline stages", _S)
define("MINIO_TPU_PIPELINE_POOL", "int",
       lambda: 2 * (os.cpu_count() or 4),
       "staging buffers per geometry ring (boot re-derives from the "
       "admission budget; the env knob wins)", _S, display="2×cores")
define("MINIO_TPU_PIPELINE_POOL_TIMEOUT_S", "float", 60.0,
       "staging-buffer wait before the PUT fails loudly", _S)
define("MINIO_TPU_ENCODE_BATCH", "int", 8,
       "blocks fused per PUT encode+digest call", _S)
define("MINIO_TPU_GET_BATCH", "int", 8,
       "blocks fused per GET verify/decode call", _S)
define("MINIO_TPU_HEAL_BATCH", "int", 8,
       "blocks fused per heal recover call", _S)
define("MINIO_TPU_DEVICE_MIN_BYTES", "int", 8 << 20,
       "batch bytes below which the codec stays on the host path", _S,
       display="8 MiB")
define("MINIO_TPU_MESH", "str", "",
       "`1` dispatches the fused batches over a (dp, sp) mesh of every "
       "visible device; default is one device, whatever the count (the "
       "mesh route has not passed on real chips — PERF.md)", _S,
       display="off")
define("MINIO_TPU_DIRECT_IO", "bool", False,
       "`on` = O_DIRECT shard writes (page-cache bypass; buffered "
       "fallback where the filesystem refuses)", _S)

_S = "Batch former"
define("MINIO_TPU_SCHED_MAX_BATCH", "int", 32,
       "blocks per fused device dispatch", _S)
define("MINIO_TPU_SCHED_MAX_WAIT_MS", "float", 3.0,
       "cross-request coalescing grace window, milliseconds", _S)
define("MINIO_TPU_SCHED_INFLIGHT", "int", 2,
       "concurrent dispatches in flight (transfer/compute overlap)", _S)

define("MINIO_TPU_SCHED_ATTRIB", "bool", True,
       "`off` disables per-dispatch stage attribution (queue/transfer/"
       "compute/fetch histograms + child spans) — the overhead A/B "
       "escape hatch", _S)

_S = "SSE device path"
define("MINIO_TPU_SSE_CIPHER", "str", "aes-gcm",
       "package cipher for NEW SSE writes: `aes-gcm` (CPU DARE "
       "packages) or `chacha20` (ChaCha20-Poly1305, device-fusable); "
       "reads dispatch on each object's recorded cipher", _S)
define("MINIO_TPU_SSE_DEVICE", "str", "on",
       "`off` pins chacha20 SSE to the CPU stage (byte-identical "
       "stream); `on` fuses cipher+RS+digest into one device launch "
       "per PUT batch when a device is present", _S)
define("MINIO_TPU_SSE_DEVICE_MIN_BYTES", "int", 1 << 20,
       "smallest PUT (stated size) that rides the fused SSE device "
       "path; smaller or unknown-length streams stay on the CPU "
       "cipher", _S, display="1 MiB")
define("MINIO_TPU_SSE_DEVICE_MAX_BYTES", "int", 0,
       "upper bound of the fused-SSE size window (device-capacity "
       "guard); 0 = unbounded", _S)

_S = "Server"
define("MINIO_TPU_MAX_CLIENTS", "int", 0,
       "admission-gate size; 0 derives it from the RAM+CPU budget", _S,
       display="auto")
define("MINIO_TPU_REQUEST_DEADLINE", "float", 10.0,
       "seconds a request waits on admission before SlowDown", _S)
define("MINIO_TPU_SHED_WINDOW_S", "float", 5.0,
       "shed data writes this long after a staging-pool timeout", _S)
define("MINIO_TPU_ADMIT_SCHED_QUEUE", "int", 0,
       "queued device-batch blocks above which data writes shed "
       "(scheduler-occupancy admission signal; 0 disables)", _S,
       display="off")
define("MINIO_TPU_REQUEST_QUEUE", "int", 128,
       "threaded-listener accept backlog (socketserver "
       "request_queue_size)", _S)
define("MINIO_TPU_IAM_REFRESH_S", "float", 300.0,
       "full IAM cache refresh interval (bounded staleness)", _S)

_S = "Multi-tenant QoS"
define("MINIO_TPU_QOS", "bool", False,
       "enforce per-tenant admission shares and budgets at the "
       "admission gate (off = byte-identical legacy behavior)", _S)
define("MINIO_TPU_QOS_DEFAULT_SHARE", "float", 1.0,
       "admission-share weight for tenants without a registered "
       "budget", _S)
define("MINIO_TPU_QOS_DEFAULT_RPS", "float", 0.0,
       "default per-tenant request-rate budget (requests/s); "
       "0 = unlimited", _S, display="off")
define("MINIO_TPU_QOS_DEFAULT_RX_BPS", "float", 0.0,
       "default per-tenant request-body byte budget (bytes/s); "
       "0 = unlimited", _S, display="off")
define("MINIO_TPU_QOS_DEFAULT_TX_BPS", "float", 0.0,
       "default per-tenant response-body byte budget (bytes/s); "
       "0 = unlimited", _S, display="off")
define("MINIO_TPU_QOS_ACTIVE_S", "float", 2.0,
       "seconds since last request a tenant stays in the active set "
       "the share math divides the gate across", _S)
define("MINIO_TPU_QOS_SHED_WINDOW_S", "float", 5.0,
       "debounce window for tenant.shed journal events (first shed "
       "per tenant per window)", _S)

_S = "HTTP edge"
define("MINIO_TPU_EDGE", "bool", True,
       "`off` selects the threaded frontend (escape hatch and "
       "correctness oracle; TLS listeners always use it)", _S)
define("MINIO_TPU_EDGE_WORKERS", "int", 1,
       "event-loop threads; >1 binds one SO_REUSEPORT listener per "
       "loop", _S)
define("MINIO_TPU_EDGE_MAX_CONNS", "int", 8192,
       "open-connection budget per edge server; beyond it new "
       "connections shed 503 before any read", _S)
define("MINIO_TPU_EDGE_HEADER_S", "float", 10.0,
       "deadline for a complete request line + headers (slowloris "
       "partial requests shed at expiry)", _S)
define("MINIO_TPU_EDGE_IDLE_S", "float", 120.0,
       "idle keep-alive connection deadline (quiet close)", _S)
define("MINIO_TPU_EDGE_POOL", "int", 0,
       "blocking handler worker threads behind the event loop "
       "(0 = 8×cores + 16)", _S, display="auto")
define("MINIO_TPU_EDGE_LAG_S", "float", 1.0,
       "event-loop lag sampler interval (each tick observes how late "
       "the loop ran it into minio_tpu_edge_loop_lag_seconds; "
       "0 disables)", _S)

_S = "Fault plane"
define("MINIO_TPU_MRF_QUEUE_SIZE", "int", 10000,
       "max queued MRF heal entries (overflow drops)", _S)
define("MINIO_TPU_MRF_MAX_RETRIES", "int", 10,
       "heal retries before an entry counts failed", _S)
define("MINIO_TPU_MRF_BACKOFF_BASE", "float", 0.05,
       "first heal-retry delay, seconds (doubles per retry)", _S)
define("MINIO_TPU_MRF_BACKOFF_MAX", "float", 15.0,
       "heal-retry delay cap, seconds (schedule spans ~40 s — past "
       "the 10 s drive re-probe and the probe backoff)", _S)
define("MINIO_TPU_RPC_RETRIES", "int", 2,
       "extra attempts for idempotent RPC verbs", _S)
define("MINIO_TPU_RPC_RETRY_BACKOFF", "float", 0.05,
       "first RPC retry delay, seconds", _S)
define("MINIO_TPU_RPC_RETRY_BACKOFF_MAX", "float", 2.0,
       "RPC retry delay cap, seconds", _S)
define("MINIO_TPU_DISK_PROBE_S", "float", 10.0,
       "DiskMonitor scan interval: dead-slot re-probes AND slow-drive "
       "health evaluation run on this cadence", _S)
define("MINIO_TPU_PEER_PROBE_S", "float", 30.0,
       "offline peer health-probe backoff cap, seconds (any "
       "successful direct call re-admits the host immediately)", _S)
define("MINIO_TPU_CHAOS_SEED", "str", "",
       "replay a chaos test's exact fault schedule (tests print the "
       "failing seed)", _S, display="per-test")

_S = "Gray-failure plane"
define("MINIO_TPU_LAT_WINDOW", "int", 64,
       "latency samples retained per (drive/peer, verb) window", _S)
define("MINIO_TPU_HEDGE", "bool", True,
       "`off` disables latency-hedged shard reads (error-triggered "
       "hedging stays)", _S)
define("MINIO_TPU_HEDGE_K", "float", 3.0,
       "hedge deadline = healthy read p95 × this", _S)
define("MINIO_TPU_HEDGE_FLOOR_S", "float", 0.05,
       "hedge deadline floor, seconds", _S)
define("MINIO_TPU_HEDGE_CEIL_S", "float", 2.0,
       "hedge deadline ceiling, seconds (also the cold-start value "
       "before any latency samples exist)", _S)
define("MINIO_TPU_QUORUM_ACK", "bool", True,
       "`off` makes every shard-write fan-out wait for ALL drives "
       "again instead of acking at write quorum and abandoning "
       "laggards to the MRF-fed background lane", _S)
define("MINIO_TPU_WRITE_STALL_K", "float", 4.0,
       "write-straggler grace = healthy write p95 × this — or, when "
       "larger, × the median run time of the same fan-out's finished "
       "writes (a host where every write runs slow has no laggard)", _S)
define("MINIO_TPU_WRITE_STALL_FLOOR_S", "float", 0.5,
       "write-straggler grace floor, seconds", _S)
define("MINIO_TPU_WRITE_STALL_CEIL_S", "float", 10.0,
       "write-straggler grace ceiling, seconds (cold-start value)", _S)
define("MINIO_TPU_QUARANTINE", "bool", True,
       "`off` disables the slow-drive suspect/probation state machine",
       _S)
define("MINIO_TPU_QUAR_LATENCY_S", "float", 0.25,
       "absolute p95 latency above which a drive turns suspect", _S)
define("MINIO_TPU_QUAR_RATIO", "float", 8.0,
       "relative conviction bar: suspect needs p95 above healthy-peer "
       "p95 × this too (uniformly slow media quarantine nothing)", _S)
define("MINIO_TPU_QUAR_MIN_SAMPLES", "int", 8,
       "read/write samples required before a drive can be convicted",
       _S)
define("MINIO_TPU_QUAR_PROBATION_S", "float", 15.0,
       "suspect dwell before probation re-probes begin", _S)
define("MINIO_TPU_QUAR_PROBES", "int", 3,
       "consecutive healthy probation probes before the heal-verified "
       "re-admission", _S)

_S = "Partition tolerance"
define("MINIO_TPU_NAUGHTYNET", "bool", False,
       "`on` exposes the test-only naughtynet admin verb so harnesses "
       "can partition a live node's internode transport", _S)
define("MINIO_TPU_NAUGHTYNET_SEED", "int", 0,
       "default seed for the naughtynet fault schedule (chaos tests "
       "print the seed they armed)", _S, display="0")
define("MINIO_TPU_RPC_STREAM_READ_S", "float", 30.0,
       "per-read socket deadline on streamed RPC responses: a peer "
       "that goes silent mid-stream fails the reader instead of "
       "parking it forever (0 disables)", _S)
define("MINIO_TPU_REGISTRY_WRITE_QUORUM", "str", "1",
       "pools an epoch-registry write must land on before the commit "
       "is acked: a count, or `majority` — below it the write refuses "
       "instead of bumping the epoch on a minority side", _S)
define("MINIO_TPU_PEER_SHED_DEADLINE_X", "float", 4.0,
       "peer fan-out deadline tightening: effective deadline = min("
       "default, observed peer p99 × this), floored at 0.5 s "
       "(0 disables the healthtrack-derived tightening)", _S)

_S = "Telemetry"
define("MINIO_TPU_TRACE_SLOW_MS", "float", 500.0,
       "span trees at least this slow are always kept", _S)
define("MINIO_TPU_TRACE_SAMPLE", "float", 0.0,
       "keep-probability for ordinary (fast, error-free) traces", _S)
define("MINIO_TPU_TRACE_KEEP", "int", 128,
       "kept span-tree ring size", _S)
define("MINIO_TPU_TRACE_MAX_SPANS", "int", 512,
       "span budget per trace; extras no-op and are counted as "
       "`spans_dropped`", _S)
define("MINIO_TPU_CLUSTER_SCRAPE_S", "float", 2.0,
       "per-peer deadline for the federated metrics scrape "
       "(?cluster=1); a peer past it degrades the scrape and counts in "
       "minio_tpu_cluster_scrape_failed_total", _S)
define("MINIO_TPU_TRACE_FOLLOW_MAX_S", "float", 3600.0,
       "hard lifetime cap on a ?follow=1 trace stream (a forgotten "
       "client cannot hold peer subscriptions forever)", _S)

_S = "Topology"
define("MINIO_TPU_REBALANCE_MPU_GRACE_S", "float", 30.0,
       "live multipart sessions idle less than this get a grace "
       "before the decommission drain migrates them off the pool", _S)
define("MINIO_TPU_REBALANCE_CHECKPOINT_EVERY", "int", 16,
       "objects moved between drain checkpoints", _S)
define("MINIO_TPU_REBALANCE_PAGE", "int", 256,
       "rebalance listing page size", _S)
define("MINIO_TPU_REBALANCE_BACKOFF_S", "float", 0.05,
       "first drain backoff when the foreground is busy", _S)
define("MINIO_TPU_REBALANCE_BACKOFF_MAX_S", "float", 1.0,
       "drain backoff cap, seconds", _S)
define("MINIO_TPU_REBALANCE_BACKOFF_TRIES", "int", 8,
       "busy polls before the drain proceeds anyway", _S)

_S = "Tiering"
define("MINIO_TPU_TIER_QUEUE_SIZE", "int", 10000,
       "max queued tier-transition entries", _S)
define("MINIO_TPU_TIER_BACKOFF_S", "float", 0.05,
       "first transition backoff when the foreground is busy", _S)
define("MINIO_TPU_TIER_BACKOFF_MAX_S", "float", 1.0,
       "transition backoff cap, seconds", _S)
define("MINIO_TPU_TIER_BACKOFF_TRIES", "int", 8,
       "busy polls before a transition proceeds anyway", _S)

_S = "Replication"
define("MINIO_TPU_REPL_WORKERS", "int", 2,
       "sync workers draining the replication queue", _S)
define("MINIO_TPU_REPL_QUEUE", "int", 10000,
       "max queued (bucket, key) sync tasks (overflow drops; the "
       "resync verb is the backstop)", _S)
define("MINIO_TPU_REPL_BACKOFF_S", "float", 0.05,
       "first replication backoff when the foreground is busy", _S)
define("MINIO_TPU_REPL_BACKOFF_MAX_S", "float", 1.0,
       "replication backoff cap, seconds", _S)
define("MINIO_TPU_REPL_BACKOFF_TRIES", "int", 8,
       "busy polls before a sync proceeds anyway", _S)
define("MINIO_TPU_REPL_BW_BPS", "int", 0,
       "default per-target push bandwidth budget, bytes/sec "
       "(0 = unlimited; a target's own bw_bps wins)", _S,
       display="unlimited")
define("MINIO_TPU_REPL_RESYNC_CHECKPOINT_EVERY", "int", 16,
       "keys pushed between resync checkpoints", _S)
define("MINIO_TPU_REPL_RESYNC_PAGE", "int", 256,
       "resync listing page size", _S)

_S = "Tiering (restore)"
define("MINIO_TPU_RESTORE_ASYNC_BYTES", "int", 64 << 20,
       "RestoreObject switches to 202 + background tier pull at this "
       "size (0 = always synchronous)", _S, display="64 MiB")

_S = "Metacache"
define("MINIO_TPU_METACACHE", "bool", True,
       "`off` = exactly the old merge-walk listing behavior", _S)
define("MINIO_TPU_METACACHE_FEED", "bool", True,
       "scanners consume the index namespace feed", _S)
define("MINIO_TPU_METACACHE_STALENESS_S", "float", 2.0,
       "serve-time staleness bound (older deltas drain synchronously)",
       _S)
define("MINIO_TPU_METACACHE_FLUSH_S", "float", 0.2,
       "journal drain cadence, seconds", _S)
define("MINIO_TPU_METACACHE_PERSIST_S", "float", 30.0,
       "min seconds between persisted segment writes", _S)
define("MINIO_TPU_METACACHE_RECONCILE_S", "float", 300.0,
       "drift-repair walk cadence, seconds", _S)
define("MINIO_TPU_METACACHE_SEGMENT_KEYS", "int", 5000,
       "keys per persisted index segment", _S)
define("MINIO_TPU_METACACHE_JOURNAL", "int", 100000,
       "max pending deltas (overflow invalidates the bucket until "
       "reconcile — never a silent wrong listing)", _S)

_S = "Scan plane"
define("MINIO_TPU_SCAN_DEVICE", "str", "on",
       "`on` rides the device when one is present, `off` forces the "
       "CPU evaluator, `force` dispatches even on CPU backends "
       "(tests/bench)", _S)
define("MINIO_TPU_SCAN_PAGE_ROWS", "int", 2048,
       "rows per tokenized column page (fixed shape = stable jit "
       "cache)", _S)
define("MINIO_TPU_SCAN_MAX_STR", "int", 128,
       "widest cacheable string cell; wider cells decline to CPU", _S)
define("MINIO_TPU_SCAN_KERNEL_CACHE", "int", 64,
       "bounded LRU of compiled scan kernels (signatures bake in "
       "query literals)", _S)
define("MINIO_TPU_SCAN_MAX_BYTES", "int", 64 << 20,
       "device-path input cap; bigger objects stream via CPU", _S,
       display="64 MiB")

_S = "Hot-object cache"
define("MINIO_TPU_CACHE", "bool", False,
       "master switch for the erasure-aware read cache", _S)
define("MINIO_TPU_CACHE_DIR", "str", "",
       "cache entry directory", _S,
       display="<first-drive>/.minio.sys/cache")
define("MINIO_TPU_CACHE_BUDGET_BYTES", "int", 1 << 30,
       "watermark LRU budget", _S, display="1 GiB")
define("MINIO_TPU_CACHE_ADMIT", "int", 2,
       "GETs inside the window before an object is admitted", _S)
define("MINIO_TPU_CACHE_ADMIT_WINDOW_S", "float", 300.0,
       "access-frequency admission window, seconds", _S)

_S = "Events"
define("MINIO_TPU_QUEUE_FSYNC", "bool", False,
       "fsync durable event-queue writes (survives power loss)", _S)

_S = "Notifications"
define("MINIO_TPU_NOTIFY_WORKERS", "int", 2,
       "delivery workers draining the notification queue", _S)
define("MINIO_TPU_NOTIFY_QUEUE", "int", 10000,
       "max queued (bucket, key) namespace events (overflow drops + "
       "counts; delivery never blocks a mutation)", _S)
define("MINIO_TPU_NOTIFY_BACKOFF_S", "float", 0.05,
       "first delivery backoff when the foreground is busy", _S)
define("MINIO_TPU_NOTIFY_BACKOFF_MAX_S", "float", 1.0,
       "delivery backoff cap, seconds", _S)
define("MINIO_TPU_NOTIFY_BACKOFF_TRIES", "int", 8,
       "busy polls before a delivery proceeds anyway", _S)
define("MINIO_TPU_NOTIFY_STORE_LIMIT", "int", 10000,
       "per-target delivery backlog cap (overflow drops + counts — "
       "bounded memory/disk against a dead target)", _S)
define("MINIO_TPU_NOTIFY_OFFLINE_S", "float", 2.0,
       "offline window after a failed delivery: new events for that "
       "target queue without burning a send timeout each", _S)
define("MINIO_TPU_NOTIFY_REDRIVE_S", "float", 5.0,
       "periodic backlog redrive cadence, seconds", _S)
define("MINIO_TPU_NOTIFY_REPLICA_EVENTS", "bool", False,
       "`on` = replica-apply writes fire bucket notifications too "
       "(reference parity keeps them suppressed: replication does not "
       "re-fire source events)", _S)

_S = "Crash consistency"
define("MINIO_TPU_FSYNC", "bool", False,
       "`on` = fsync barriers on commit paths (fsync before rename, "
       "directory fsync after; shard files synced at close) — "
       "power-loss durability at real I/O cost", _S)
define("MINIO_TPU_CRASHPOINT", "str", "",
       "`<name>[:<nth>]` hard-exits the process (os._exit 137) at the "
       "Nth hit of the named crashpoint — the kill/restart harness's "
       "deterministic crash injector (see README crashpoint table)", _S,
       display="unset")
define("MINIO_TPU_FSCK_BOOT", "bool", False,
       "`on` runs the fsck consistency auditor (repair mode) at "
       "cluster boot, feeding repairable findings to heal/MRF", _S)
define("MINIO_TPU_FSCK_TMP_AGE_S", "float", 3600.0,
       "staged tmp writes older than this count as crash leftovers "
       "for fsck (younger ones may be in-flight PUTs)", _S)

_S = "Incident plane"
define("MINIO_TPU_EVENTLOG", "bool", True,
       "`off` disables the structured event journal (emits drop; the "
       "overhead A/B escape hatch)", _S)
define("MINIO_TPU_EVENTLOG_RING", "int", 2048,
       "in-memory journal ring size (the /events backlog bound)", _S)
define("MINIO_TPU_EVENTLOG_SEGMENT_EVENTS", "int", 64,
       "pending events that force an early segment flush", _S)
define("MINIO_TPU_EVENTLOG_FLUSH_S", "float", 2.0,
       "journal segment flush cadence, seconds", _S)
define("MINIO_TPU_EVENTLOG_KEEP_SEGMENTS", "int", 16,
       "persisted journal segments retained (older ones pruned)", _S)
define("MINIO_TPU_EVENTS_FOLLOW_MAX_S", "float", 3600.0,
       "hard lifetime cap on a ?follow=1 event stream (a forgotten "
       "client cannot hold peer subscriptions forever)", _S)
define("MINIO_TPU_SLO", "bool", True,
       "`off` disables the SLO burn-rate engine (gauges stop, no "
       "breach events)", _S)
define("MINIO_TPU_SLO_EVAL_S", "float", 5.0,
       "SLO evaluation cadence, seconds", _S)
define("MINIO_TPU_SLO_WINDOWS_S", "str", "60,300",
       "comma-separated burn-rate windows, seconds (multi-window "
       "alerting: short catches fast burn, long catches slow leaks)",
       _S)
define("MINIO_TPU_SLO_AVAIL_TARGET", "float", 99.9,
       "availability objective, percent of non-5xx responses per API "
       "class", _S)
define("MINIO_TPU_SLO_LAT_TARGET", "float", 99.0,
       "latency objective, percent of requests under the class "
       "threshold", _S)
define("MINIO_TPU_SLO_LAT_READ_MS", "float", 250.0,
       "read-class latency threshold, milliseconds", _S)
define("MINIO_TPU_SLO_LAT_WRITE_MS", "float", 1000.0,
       "write-class latency threshold, milliseconds", _S)
define("MINIO_TPU_SLO_BURN_THRESHOLD", "float", 4.0,
       "burn rate at which an objective breaches (clears at half "
       "this — hysteresis stops breach/clear flapping)", _S)
define("MINIO_TPU_SLO_MIN_SAMPLES", "int", 10,
       "requests a window must hold before its burn rate can breach "
       "(a single early 500 must not page)", _S)
define("MINIO_TPU_INCIDENTS", "bool", True,
       "`off` disables black-box incident capture", _S)
define("MINIO_TPU_INCIDENT_KEEP", "int", 16,
       "incident bundles retained on disk (older ones pruned)", _S)
define("MINIO_TPU_INCIDENT_DEBOUNCE_S", "float", 30.0,
       "min seconds between captures for the same trigger class "
       "(a flapping trigger must not fill the retention window)", _S)
define("MINIO_TPU_INCIDENT_EVENTS", "str",
       "slo.breach,drive.probation,net.partition,fsck.unrepaired,"
       "registry.fork",
       "comma-separated journal event classes that trigger a capture",
       _S)
define("MINIO_TPU_INCIDENT_WINDOW", "int", 256,
       "journal entries snapshotted into each bundle", _S)

_S = "Lock watchdog"
define("MINIO_TPU_LOCKCHECK", "bool", False,
       "instrument named locks: record the cross-thread acquisition "
       "graph, fail on order cycles (on under the chaos/concurrency "
       "suites)", _S)
define("MINIO_TPU_LOCKCHECK_RAISE", "bool", True,
       "raise LockOrderError at the acquire that closes a cycle "
       "(off = record only)", _S)
define("MINIO_TPU_LOCKCHECK_BLOCK_MS", "float", 200.0,
       "acquire wait above this while holding another lock is flagged "
       "held-while-blocking", _S)
define("MINIO_TPU_LOCKCHECK_HELD_MS", "float", 1000.0,
       "hold duration above this is flagged as a long hold", _S)

del _S


# ---------------------------------------------------------------------------
# README table generator (tools/check/knobtable.py drift-checks this)
# ---------------------------------------------------------------------------

TABLE_BEGIN = "<!-- knob-table:begin (generated by tools/check/run.py --write-knob-table) -->"
TABLE_END = "<!-- knob-table:end -->"


def render_table() -> str:
    """The README knob table, grouped by plane — generated, never
    hand-edited (the `knob-env` drift check pins it)."""
    lines: List[str] = []
    section = None
    for k in KNOBS.values():
        if k.section != section:
            section = k.section
            if lines:
                lines.append("")
            lines.append(f"**{section}**")
            lines.append("")
            lines.append("| Knob | Default | Effect |")
            lines.append("|---|---|---|")
        lines.append(f"| `{k.name}` | {k.default_display()} | {k.doc} |")
    return "\n".join(lines) + "\n"
