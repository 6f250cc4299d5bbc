"""Double-buffered stage pipeline for the object data paths.

The fork wins its throughput by *overlap*: QAT MD5 runs asynchronously
while erasure encode + shard writes proceed (cmd/erasure-encode.go:
113-124, "async kernel launch overlapped with the rest of the
pipeline"). This module generalizes that to the whole data path:

  * :class:`StagePipeline` — a small executor where each stage runs on
    its own thread, connected by BOUNDED queues. The bounds are the
    back-pressure: a fast producer blocks instead of ballooning memory,
    so staging RAM is capped by queue depth × buffer size.
  * a registry of :class:`~minio_tpu.parallel.bpool.BytePool` staging
    rings keyed by buffer width — PUT streams borrow their (B, k·S)
    encode buffers here, so total staging memory is bounded by the pool
    regardless of how many streams are in flight.
  * :data:`STATS` — always-on overlap accounting (wall vs sum-of-stage
    seconds, prefetch savings, pool pressure), exported as
    ``minio_tpu_pipeline_*`` Prometheus gauges so the win is observable
    in production, not just under the bench.

Env knobs (documented in README "Pipelined data path"):

  MINIO_TPU_PIPELINE=off          select the serial PUT/GET hot loops
  MINIO_TPU_PIPELINE_DEPTH=2      bounded queue depth between stages
  MINIO_TPU_PIPELINE_POOL=2×cores staging buffers per geometry ring
  MINIO_TPU_PIPELINE_POOL_TIMEOUT_S=60
                                  max wait for a staging buffer before
                                  the PUT fails (back-pressure made
                                  visible instead of a silent stall)
"""

from __future__ import annotations

import contextvars
import os
import queue
import threading
from typing import Callable, Optional, Sequence

from ..utils import knobs, telemetry
from .bpool import BytePool

ENABLED = knobs.get_bool("MINIO_TPU_PIPELINE")
DEPTH = max(1, knobs.get_int("MINIO_TPU_PIPELINE_DEPTH"))
# staging ring size: the pool is SHARED by every stream of a geometry,
# so it must scale with the ADMITTED concurrency (each admitted stream
# keeps ~2 batches in flight) or it throttles aggregate throughput
# instead of just bounding memory. The 2×cores value is only the
# fallback for pool rings created before the server computes its
# admission budget — configure_pool_buffers() re-derives the default
# from requests_budget() at boot (the env knob always wins).
_POOL_ENV_SET = knobs.is_set("MINIO_TPU_PIPELINE_POOL")
POOL_BUFFERS = max(4, knobs.get_int("MINIO_TPU_PIPELINE_POOL"))
POOL_TIMEOUT_S = knobs.get_float("MINIO_TPU_PIPELINE_POOL_TIMEOUT_S")


def configure_pool_buffers(requests_budget: int) -> int:
    """Size the staging rings from the RAM-gated admission budget: the
    budget already bounds in-flight object requests by RAM/2 with ~2
    staging buffers per request in its per-request footprint, so
    2×budget buffers per ring is the matching capacity (the old flat
    2×cores default starved budgets above one stream per core and
    oversized tiny-RAM hosts). Applies to rings created AFTER the call;
    MINIO_TPU_PIPELINE_POOL overrides. Returns the effective size."""
    global POOL_BUFFERS
    if not _POOL_ENV_SET:
        POOL_BUFFERS = max(4, 2 * int(requests_budget))
    return POOL_BUFFERS

# GET lookahead reads run here, NOT on metadata._POOL: a prefetch task
# fans its per-reader reads out onto _POOL, and a task that waits on
# subtasks of its own pool can deadlock when the pool saturates. Sized
# with the host's concurrency (the tasks are I/O-bound waiters); when a
# lookahead is still queued behind other streams at collection time the
# GET cancels it and reads inline, so prefetch stays a strict win.
PREFETCH_POOL = telemetry.host_pool(
    "prefetch_pool", max(16, 4 * (os.cpu_count() or 4)), "get-prefetch")


def prefetch(fn, *args):
    """One lookahead task on PREFETCH_POOL, its wait for a thread
    recorded (`telemetry.submit`)."""
    return telemetry.submit(PREFETCH_POOL, "prefetch_pool", fn, *args,
                            stage="lookahead")


def cancel_prefetch(fut) -> bool:
    """Take back a lookahead that has not started; True when it had
    not."""
    return telemetry.cancel(fut, "prefetch_pool")

_EOT = object()          # end-of-stream sentinel on the stage queues


# ---------------------------------------------------------------------------
# staging buffer rings
# ---------------------------------------------------------------------------

_pools: dict[int, BytePool] = {}
_pools_mu = threading.Lock()


def staging_pool(width: int) -> BytePool:
    """The shared staging ring for `width`-byte encode buffers — one
    ring per geometry (cap·k·S), shared by every stream with that
    geometry, so concurrent PUTs contend on a bounded pool instead of
    each allocating its own batch buffer."""
    with _pools_mu:
        pool = _pools.get(width)
        if pool is None:
            pool = BytePool(width, POOL_BUFFERS)
            _pools[width] = pool
        return pool


def pool_pressure() -> dict:
    """Aggregate wait/exhaustion counters across every staging ring."""
    with _pools_mu:
        pools = list(_pools.values())
    return {"waits": sum(p.waits for p in pools),
            "exhausted": sum(p.exhausted for p in pools),
            "rings": len(pools)}


# ---------------------------------------------------------------------------
# overlap accounting
# ---------------------------------------------------------------------------

class PipelineStats:
    """Always-on counters for the pipelined data path (a handful of
    float adds per stream — not per block — so they stay on in
    production). wall < stage_sum means the stages actually overlapped;
    stage_sum / wall is the effective parallelism."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self.put_streams = 0
        self.put_batches = 0
        self.put_wall_s = 0.0
        self.put_stage_s = 0.0
        self.get_groups = 0
        self.get_prefetched = 0
        self.get_prefetch_wait_s = 0.0     # time spent waiting on lookahead
        self.get_prefetch_read_s = 0.0     # what the read actually cost

    def record_put(self, wall_s: float, stage_s: float,
                   batches: int) -> None:
        with self._mu:
            self.put_streams += 1
            self.put_batches += batches
            self.put_wall_s += wall_s
            self.put_stage_s += stage_s

    def record_get_group(self, prefetched: bool, wait_s: float = 0.0,
                         read_s: float = 0.0) -> None:
        with self._mu:
            self.get_groups += 1
            if prefetched:
                self.get_prefetched += 1
                self.get_prefetch_wait_s += wait_s
                self.get_prefetch_read_s += read_s

    def snapshot(self) -> dict:
        with self._mu:
            out = {
                "enabled": int(ENABLED),
                "put_streams": self.put_streams,
                "put_batches": self.put_batches,
                "put_wall_s": round(self.put_wall_s, 4),
                "put_stage_s": round(self.put_stage_s, 4),
                "get_groups": self.get_groups,
                "get_prefetched": self.get_prefetched,
                "get_prefetch_wait_s": round(self.get_prefetch_wait_s, 4),
                "get_prefetch_saved_s": round(
                    max(self.get_prefetch_read_s
                        - self.get_prefetch_wait_s, 0.0), 4),
            }
        out.update({f"bpool_{k}": v for k, v in pool_pressure().items()
                    if k != "rings"})
        return out


STATS = PipelineStats()

_PIPELINE_GAUGE_HELP = {
    "enabled": "1 when the pipelined PUT/GET hot loops are selected",
    "put_streams_total": "PUT streams run through the stage pipeline",
    "put_batches_total": "Encode batches fed through the PUT pipeline",
    "put_wall_seconds_total": "Wall seconds inside pipelined PUT loops",
    "put_stage_seconds_total":
        "Summed per-stage seconds (ingest+encode+write) of pipelined "
        "PUT loops; ratio vs wall = achieved overlap",
    "get_groups_total": "GET block groups read",
    "get_prefetched_total":
        "GET block groups served via the one-group lookahead",
    "get_prefetch_saved_seconds_total":
        "Drive-read seconds hidden behind verify+decode by the GET "
        "lookahead",
    "bpool_waits_total":
        "Staging-buffer gets that had to block (back-pressure)",
    "bpool_exhausted_total":
        "Staging-buffer gets that timed out (pipeline stalled)",
}
# snapshot key -> exported suffix (names predate the registry and are
# kept stable for dashboards/tests)
_PIPELINE_GAUGE_KEYS = {
    "enabled": "enabled",
    "put_streams": "put_streams_total",
    "put_batches": "put_batches_total",
    "put_wall_s": "put_wall_seconds_total",
    "put_stage_s": "put_stage_seconds_total",
    "get_groups": "get_groups_total",
    "get_prefetched": "get_prefetched_total",
    "get_prefetch_saved_s": "get_prefetch_saved_seconds_total",
    "bpool_waits": "bpool_waits_total",
    "bpool_exhausted": "bpool_exhausted_total",
}


def _collect_pipeline_metrics() -> None:
    """Registry collector: refresh minio_tpu_pipeline_* from STATS at
    exposition time (no polling thread)."""
    snap = STATS.snapshot()
    for key, suffix in _PIPELINE_GAUGE_KEYS.items():
        if key in snap:
            telemetry.REGISTRY.gauge(
                f"minio_tpu_pipeline_{suffix}",
                _PIPELINE_GAUGE_HELP[suffix]).set(snap[key])


telemetry.REGISTRY.register_collector(_collect_pipeline_metrics)


# ---------------------------------------------------------------------------
# the stage executor
# ---------------------------------------------------------------------------

class StagePipeline:
    """Run items through `stages` (each fn(item) -> next item) with one
    thread per stage and bounded hand-off queues.

    * Order-preserving: one worker per stage + FIFO queues, so shard
      frames land on the writers in block order.
    * Back-pressure: `submit()` blocks when the first queue is full; a
      stage blocked on a full downstream queue stops pulling upstream.
    * Fail-fast: the FIRST stage exception is kept and re-raised (the
      original object, so quorum errors keep their type) from the next
      `submit()` or from `close()`. After a failure workers keep
      draining but stop processing — queued items are handed to
      `on_drop` so pooled buffers return to their ring instead of
      leaking with the wreck.
    """

    def __init__(self, stages: Sequence[Callable], depth: int = DEPTH,
                 name: str = "pipeline",
                 on_drop: Optional[Callable] = None):
        assert stages, "a pipeline needs at least one stage"
        self._stages = list(stages)
        self._on_drop = on_drop
        self._queues = [queue.Queue(maxsize=max(1, depth))
                        for _ in stages]
        self._error: Optional[BaseException] = None
        self._err_mu = threading.Lock()
        # stage workers inherit the creating request's span context so
        # stage-body spans land in the right tree (one Context copy per
        # thread — a Context must not run concurrently)
        tracing = telemetry.current_span() is not None

        def _target(i: int) -> Callable:
            if not tracing:
                return lambda: self._run(i)
            cctx = contextvars.copy_context()
            return lambda: cctx.run(self._run, i)

        self._threads = [
            threading.Thread(target=_target(i),
                             name=f"{name}-stage{i}", daemon=True)
            for i in range(len(stages))]
        for t in self._threads:
            t.start()

    # -- producer side -----------------------------------------------------

    def submit(self, item) -> None:
        """Feed one item to stage 0; raises the pipeline's pending error
        (dropping `item` via on_drop) instead of queueing into a wreck."""
        while True:
            err = self._error
            if err is not None:
                self._drop(item)
                raise err
            try:
                self._queues[0].put(item, timeout=0.1)
                return
            except queue.Full:
                continue        # re-check the error while blocked

    def close(self, abort: bool = False) -> None:
        """End of stream: wait for in-flight items, join the workers,
        and re-raise the first stage error (unless `abort`, the
        caller's-own-exception path, where the pipeline error would
        mask it)."""
        if abort:
            with self._err_mu:
                if self._error is None:
                    self._error = _Aborted()
        self._queues[0].put(_EOT)
        for t in self._threads:
            t.join()
        if not abort and self._error is not None \
                and not isinstance(self._error, _Aborted):
            raise self._error

    @property
    def failed(self) -> bool:
        return self._error is not None

    # -- workers -----------------------------------------------------------

    def _drop(self, item) -> None:
        if self._on_drop is not None and item is not _EOT:
            try:
                self._on_drop(item)
            except Exception:  # noqa: BLE001 — drop hooks are best-effort
                pass

    def _run(self, idx: int) -> None:
        fn = self._stages[idx]
        q = self._queues[idx]
        nxt = self._queues[idx + 1] if idx + 1 < len(self._queues) \
            else None
        while True:
            item = q.get()
            if item is _EOT:
                if nxt is not None:
                    nxt.put(_EOT)
                return
            if self._error is not None:
                self._drop(item)
                continue
            try:
                out = fn(item)
            except BaseException as e:  # noqa: BLE001 — surfaced to caller
                with self._err_mu:
                    if self._error is None:
                        self._error = e
                self._drop(item)
                continue
            if nxt is None:
                continue
            while True:
                if self._error is not None:
                    self._drop(out)
                    break
                try:
                    nxt.put(out, timeout=0.1)
                    break
                except queue.Full:
                    continue


class _Aborted(Exception):
    """Internal sentinel error: the caller aborted the stream (its own
    exception is in flight) — workers drain, nothing re-raises."""
