"""Unified telemetry plane: metrics registry + request-scoped spans.

The reference answers "what is the server doing" with two surfaces —
`mc admin trace` (cmd/http-tracer.go over pkg/pubsub) and the
Prometheus endpoint (cmd/metrics.go). This module is the substrate
both are rebuilt on, plus the piece the reference lacks and a
TPU-scale data path needs: per-request SPAN TREES that cross layers
(S3 handler → engine → pipeline/scheduler → shard I/O → internode
RPC), so "where did this slow PUT spend its time" has an answer in
production, not only under a profiler.

Two halves:

* :data:`REGISTRY` — a process-global metrics registry
  (Counter / Gauge / Histogram, labels, `# HELP`/`# TYPE` Prometheus
  text exposition). Every subsystem reports here — the admin metrics
  handler renders it instead of hand-formatting gauge strings, and
  bench.py snapshots it per config. Collector callbacks registered
  with :meth:`MetricsRegistry.register_collector` run at exposition
  time so live values (queue depths, pool pressure) need no polling
  thread.

* the span tracer — `contextvars`-propagated spans. A server
  middleware opens a root span per request; ``with span("encode"):``
  anywhere below attaches a child to whatever span is current on this
  thread (fan-out pools forward the context explicitly,
  `contextvars.copy_context()` per task). Tracing is ZERO-allocation
  when no root span is active: ``span()`` returns a shared no-op.

Sampling is tail-based: the keep/drop decision happens when the ROOT
span finishes, so errors and slow requests are always kept no matter
how rare — head sampling would have dropped most of them before
knowing they mattered. Knobs (also README "Observability"):

  MINIO_TPU_TRACE_SAMPLE=0.0     keep-probability for ordinary traces
  MINIO_TPU_TRACE_SLOW_MS=500    always keep traces at least this slow
  MINIO_TPU_TRACE_KEEP=128       kept-trace ring size

Clocks: a span's ``start`` is wall time (for humans and for joining
across nodes); ``t0_ns``/``t1_ns`` are ``time.perf_counter_ns()`` — one
monotonic clock for every thread of the process, so spans of different
threads order against each other, and a reader that took the same
clock beside a profiler annotation can lay the tree over the device
trace. ``tid`` is the thread the span was opened on.

The window recorder (``SPANS.record_begin()`` / ``record_end()``, admin
``/spans?record=<seconds>``) keeps EVERY finished root whole for a
bounded window — the ring's tail sampling shows only the slow tail, a
biased sample of where requests spend their time — and stamps each
span's thread CPU at both ends (wall minus CPU = time the thread
waited: socket, lock, GIL). Off, it costs one flag test per span.

Cross-process joins: the internode transport injects
``x-minio-trace-id`` / ``x-minio-span-id`` headers; the serving side
opens a `join()` span under that identity and records it as a
FRAGMENT. `SPANS.dump()` grafts fragments back into their parent
trees by span id — in one process (tests, single-node multi-drive)
the joined tree is complete; across real processes each node keeps
its own fragments for its own /spans endpoint.
"""

from __future__ import annotations

import bisect
import contextvars
import itertools
import math
import os
import random
import re
import threading
import time
import uuid
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from . import knobs

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "Span", "SpanSink", "SPANS", "span", "trace", "join",
    "current_span", "attach_span", "traced_iter",
    "timed", "accum", "host_pool", "submit", "cancel",
]

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# default latency buckets (seconds) — spans two orders of magnitude
# around typical object-op latencies on both tmpfs and spinning media
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _fmt(v: float) -> str:
    """Prometheus sample formatting: integers bare, floats plain."""
    if v == math.inf:
        return "+Inf"
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, int) or (isinstance(v, float) and v.is_integer()
                              and abs(v) < 1e15):
        return str(int(v))
    return repr(float(v))


def _escape_label(v: str) -> str:
    return v.replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _render_labels(key: tuple, extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(str(v))}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Family:
    """One metric family: name, help, type, samples keyed by labels."""

    kind = "untyped"

    def __init__(self, name: str, help_: str):
        if not _NAME_RE.match(name):
            raise ValueError(f"bad metric name {name!r}")
        self.name = name
        self.help = help_
        self._mu = threading.Lock()
        self._series: Dict[tuple, object] = {}

    def _check_labels(self, labels: dict) -> tuple:
        for k in labels:
            if not _LABEL_RE.match(k):
                raise ValueError(f"bad label name {k!r}")
        return _label_key(labels)

    def clear(self) -> None:
        """Forget every series (label churn hygiene: per-bucket gauges
        refreshed from a snapshot drop deleted buckets)."""
        with self._mu:
            self._series.clear()

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        with self._mu:
            items = sorted(self._series.items())
        for key, value in items:
            lines.extend(self._render_series(key, value))
        return lines

    def _render_series(self, key: tuple, value) -> List[str]:
        return [f"{self.name}{_render_labels(key)} {_fmt(value)}"]


class Counter(_Family):
    """Monotonic counter (optionally labelled)."""

    kind = "counter"

    def inc(self, amount: float = 1, **labels) -> None:
        key = self._check_labels(labels)
        with self._mu:
            self._series[key] = self._series.get(key, 0) + amount

    def value(self, **labels) -> float:
        with self._mu:
            return self._series.get(_label_key(labels), 0)

    def series(self) -> Dict[tuple, float]:
        """label-key -> value snapshot (the SLO engine aggregates
        status-class counts across label sets)."""
        with self._mu:
            return dict(self._series)


class Gauge(_Family):
    """Settable instantaneous value (optionally labelled)."""

    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        key = self._check_labels(labels)
        with self._mu:
            self._series[key] = value

    def inc(self, amount: float = 1, **labels) -> None:
        key = self._check_labels(labels)
        with self._mu:
            self._series[key] = self._series.get(key, 0) + amount

    def value(self, **labels) -> float:
        with self._mu:
            return self._series.get(_label_key(labels), 0)


class _HistSeries:
    __slots__ = ("counts", "total", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets     # per-bucket (non-cumulative)
        self.total = 0.0
        self.count = 0


class Histogram(_Family):
    """Fixed-bucket histogram; exposes `_bucket` (cumulative, with a
    +Inf bucket), `_sum` and `_count` series per label set."""

    kind = "histogram"

    def __init__(self, name: str, help_: str,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help_)
        self.buckets = tuple(sorted(buckets))

    def observe(self, value: float, **labels) -> None:
        key = self._check_labels(labels)
        idx = bisect.bisect_left(self.buckets, value)
        with self._mu:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistSeries(len(self.buckets) + 1)
            s.counts[idx] += 1
            s.total += value
            s.count += 1

    def count(self, **labels) -> int:
        with self._mu:
            s = self._series.get(_label_key(labels))
            return s.count if s is not None else 0

    def series_snapshot(self) -> Dict[tuple, tuple]:
        """label-key -> (per-bucket counts, sum, count), consistent
        per series — the SLO engine derives over-threshold fractions
        from bucket counts without reaching into family internals."""
        with self._mu:
            return {key: (list(s.counts), s.total, s.count)
                    for key, s in self._series.items()}

    def _render_series(self, key: tuple, s: "_HistSeries") -> List[str]:
        # snapshot under the family lock: a concurrent observe()
        # mutates counts/total/count together, and a torn read here
        # could emit _bucket{+Inf} < _count (breaks the histogram
        # invariant scrapers rely on)
        with self._mu:
            counts = list(s.counts)
            total, count = s.total, s.count
        out = []
        cum = 0
        for le, c in zip(self.buckets + (math.inf,), counts):
            cum += c
            le_pair = 'le="' + _fmt(le) + '"'
            out.append(f"{self.name}_bucket"
                       f"{_render_labels(key, le_pair)} {cum}")
        out.append(f"{self.name}_sum{_render_labels(key)} "
                   f"{_fmt(round(total, 9))}")
        out.append(f"{self.name}_count{_render_labels(key)} {count}")
        return out


class MetricsRegistry:
    """Process-global family registry. Getter methods are idempotent:
    the first call creates the family, later calls return it (and
    reject a kind mismatch — two subsystems silently sharing one name
    with different types is exactly the bug a registry exists to
    catch)."""

    def __init__(self) -> None:
        from . import lockcheck
        self._mu = lockcheck.mutex("telemetry.registry")
        self._families: Dict[str, _Family] = {}
        self._collectors: List[Callable[[], None]] = []

    def _get(self, cls, name: str, help_: str, **kw):
        with self._mu:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = cls(name, help_, **kw)
            elif not isinstance(fam, cls):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{fam.kind}, not {cls.kind}")
            return fam

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(Counter, name, help_)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(Gauge, name, help_)

    def histogram(self, name: str, help_: str = "",
                  buckets: Sequence[float] = DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._get(Histogram, name, help_, buckets=buckets)

    def register_collector(self, fn: Callable[[], None]) -> None:
        """`fn()` runs before every render — the hook live-value
        subsystems (queue depth, pool pressure) refresh gauges from."""
        with self._mu:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def _run_collectors(self) -> None:
        with self._mu:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:  # noqa: BLE001 — telemetry is passive
                pass

    def render(self, extra: Optional[Callable[[], None]] = None) -> str:
        """Prometheus text exposition of every family. `extra` is a
        one-shot collector run after the registered ones — a metrics
        endpoint passes its own server-scoped refresh here instead of
        registering globally, so several servers in one process each
        scrape THEIR values (last-registered-wins clobbering) and a
        dead server stops reporting."""
        self._run_collectors()
        if extra is not None:
            try:
                extra()
            except Exception:  # noqa: BLE001 — telemetry is passive
                pass
        with self._mu:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        lines: List[str] = []
        for fam in fams:
            lines.extend(fam.render())
        return "\n".join(lines) + "\n"

    def snapshot(self, prefix: str = "") -> dict:
        """name -> {labels-json: value} (histograms: {sum, count}) —
        the bench's registry snapshot."""
        self._run_collectors()
        with self._mu:
            fams = [f for f in self._families.values()
                    if f.name.startswith(prefix)]
        out: dict = {}
        for fam in fams:
            series = {}
            with fam._mu:       # consistent sum/count pairs
                for key, v in fam._series.items():
                    lk = ",".join(f"{k}={val}" for k, val in key) or ""
                    if isinstance(v, _HistSeries):
                        series[lk] = {"sum": round(v.total, 6),
                                      "count": v.count}
                    else:
                        series[lk] = v
            out[fam.name] = series
        return out


REGISTRY = MetricsRegistry()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

TRACE_HEADER = "x-minio-trace-id"
SPAN_HEADER = "x-minio-span-id"

SLOW_S = knobs.get_float("MINIO_TPU_TRACE_SLOW_MS") / 1e3
SAMPLE = knobs.get_float("MINIO_TPU_TRACE_SAMPLE")
KEEP = knobs.get_int("MINIO_TPU_TRACE_KEEP")
# spans per TRACE cap: a 10 GiB distributed PUT would otherwise
# materialize one span per block per drive (~100k objects) and the
# kept ring would pin all of them; past the budget span() returns the
# no-op and the root counts what was dropped
MAX_SPANS = knobs.get_int("MINIO_TPU_TRACE_MAX_SPANS")

_current: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("minio_tpu_span", default=None)


# span ids: a per-process tag + a counter (a uuid4 per span was the
# tracer's largest fixed cost); the tag keeps ids of different nodes
# apart when RPC fragments graft into a caller's tree
_ID_TAG = os.urandom(3).hex()
_ids = itertools.count(1)
# the window recorder's switch (SpanSink.record_begin/record_end): read
# bare on every span's open and close, so off costs a flag test
_recording = False


class Span:
    """One timed operation in a request's tree. Children append under
    the parent's lock — stage threads and drive fan-outs attach
    concurrently."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "t0_ns", "t1_ns", "tid", "cpu0_ns", "cpu_ns",
                 "duration_s", "attrs", "error", "children",
                 "remote", "_mu", "_token", "root", "has_error",
                 "slow_exempt", "n_spans", "n_dropped")

    def __init__(self, name: str, trace_id: str, parent_id: str = "",
                 attrs: Optional[dict] = None, remote: bool = False,
                 root: Optional["Span"] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = f"{_ID_TAG}{next(_ids):x}"
        self.parent_id = parent_id
        self.start = time.time()
        self.t0_ns = time.perf_counter_ns()
        self.t1_ns = 0
        self.tid = threading.get_ident()
        # thread CPU burnt inside the span, taken only while the window
        # recorder is on (-1 = not taken)
        self.cpu0_ns = time.thread_time_ns() if _recording else -1
        self.cpu_ns = -1
        self.duration_s = 0.0
        self.attrs = attrs or {}
        self.error = ""
        self.children: List[Span] = []
        self.remote = remote
        self._mu = threading.Lock()
        self._token = None
        # tree root (None when self IS the root): child errors set the
        # root's has_error so the tail-sampling keep decision is O(1)
        # instead of walking the whole tree per request
        self.root = root
        self.has_error = False
        # long-poll/streaming admin surfaces run for minutes by design:
        # exempt from the keep-if-slow rule (errors still keep)
        self.slow_exempt = False
        # per-trace span budget accounting (root only): spans created /
        # spans dropped past MAX_SPANS
        self.n_spans = 0
        self.n_dropped = 0

    def mark_error(self, msg: str) -> None:
        if not self.error:
            self.error = msg
        (self.root or self).has_error = True

    def _admit_child(self) -> bool:
        """Charge one span against this ROOT's budget; False = the
        trace is at MAX_SPANS and the caller should no-op."""
        with self._mu:
            if self.n_spans >= MAX_SPANS:
                self.n_dropped += 1
                return False
            self.n_spans += 1
            return True

    def add_child(self, child: "Span") -> None:
        with self._mu:
            self.children.append(child)

    def finish(self) -> None:
        self.t1_ns = time.perf_counter_ns()
        self.duration_s = (self.t1_ns - self.t0_ns) / 1e9
        if _recording and self.cpu0_ns >= 0 \
                and threading.get_ident() == self.tid:
            self.cpu_ns = time.thread_time_ns() - self.cpu0_ns

    def depth(self) -> int:
        with self._mu:
            kids = list(self.children)
        return 1 + max((c.depth() for c in kids), default=0)

    def walk(self) -> Iterable["Span"]:
        yield self
        with self._mu:
            kids = list(self.children)
        for c in kids:
            yield from c.walk()

    def to_dict(self, children: bool = True) -> dict:
        """The span as a dict — with its subtree under `children`, or
        alone (the window recorder's flat list links by `parent_id`)."""
        kids = []
        if children:
            with self._mu:
                kids = list(self.children)
        d = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "start": round(self.start, 6),
            "duration_ms": round(self.duration_s * 1e3, 3),
            "t0_ns": self.t0_ns,
            "t1_ns": self.t1_ns,
            "tid": self.tid,
        }
        if self.cpu_ns >= 0:
            d["cpu_ns"] = self.cpu_ns
        if self.parent_id:
            d["parent_id"] = self.parent_id
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.error:
            d["error"] = self.error
        if self.remote:
            d["remote"] = True
        if self.n_dropped:
            # spans not recorded past the per-trace MAX_SPANS budget —
            # "covered everything" must not be implied when it wasn't
            d["spans_dropped"] = self.n_dropped
        if kids:
            d["children"] = [c.to_dict() for c in kids]
        return d


class _SpanCtx:
    """Context manager that opens `span` on enter (making it current on
    this thread) and finishes it on exit. `root` spans are offered to
    the sink; `fragment` spans are recorded as RPC-join fragments."""

    __slots__ = ("span", "root", "fragment")

    def __init__(self, sp: Span, root: bool = False,
                 fragment: bool = False):
        self.span = sp
        self.root = root
        self.fragment = fragment

    def __enter__(self) -> Span:
        self.span._token = _current.set(self.span)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self.span
        if sp._token is not None:
            _current.reset(sp._token)
            sp._token = None
        if exc is not None:
            sp.mark_error(f"{type(exc).__name__}: {exc}")
        elif sp.error:
            (sp.root or sp).has_error = True
        sp.finish()
        if self.root:
            SPANS.offer(sp)
        elif self.fragment:
            SPANS.record_fragment(sp)
        return False


class _NoopSpanCtx:
    """Shared do-nothing context manager — the zero-cost path when no
    trace is active on this thread."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpanCtx()


def current_span() -> Optional[Span]:
    return _current.get()


def trace(name: str, trace_id: str = "", **attrs) -> _SpanCtx:
    """Open a ROOT span (a new trace). Used by the server middleware
    and the bench; everything below attaches via span()."""
    sp = Span(name, trace_id or uuid.uuid4().hex[:16],
              attrs=attrs or None)
    return _SpanCtx(sp, root=True)


def span(name: str, parent: Optional[Span] = None, **attrs):
    """Child span of `parent` (default: the current span on this
    thread). Returns a shared no-op when there is no active trace, so
    instrumented hot paths cost one context-var read when idle."""
    p = parent if parent is not None else _current.get()
    if p is None:
        return _NOOP
    root = p.root or p
    if not root._admit_child():
        return _NOOP
    sp = Span(name, p.trace_id, parent_id=p.span_id,
              attrs=attrs or None, root=root)
    p.add_child(sp)
    return _SpanCtx(sp)


def join(name: str, trace_id: str, parent_span_id: str = "",
         **attrs) -> _SpanCtx:
    """Server-side half of an internode RPC: open a span under the
    CALLER's trace identity. Finished joined spans are recorded as
    fragments; dump() grafts them back into the caller's tree."""
    sp = Span(name, trace_id, parent_id=parent_span_id,
              attrs=attrs or None, remote=True)
    return _SpanCtx(sp, fragment=True)


def traced_iter(name: str, it, **attrs):
    """Span over a CHUNK STREAM: yields from `it` with the span made
    current only WHILE the underlying iterator runs (set/reset around
    each next()), never across a yield. A plain `with span():` inside
    a generator would mutate the CONSUMER's context (PEP 567:
    generators don't get their own) and an abandoned generator (ranged
    reads, client hangups) would leak the span as that thread's
    current until GC — and then reset a foreign-context token. The
    span's duration covers first-to-last chunk; abandonment finishes
    it from the generator's close. `busy_ns` in its attrs is the time
    spent INSIDE the producer: the rest of the duration is the
    consumer's (the response writer's), not this layer's."""
    parent = _current.get()
    if parent is None:
        yield from it
        return
    root = parent.root or parent
    if not root._admit_child():
        yield from it
        return
    sp = Span(name, parent.trace_id, parent_id=parent.span_id,
              attrs=attrs or None, root=root)
    parent.add_child(sp)
    busy = 0
    try:
        while True:
            token = _current.set(sp)
            t = time.perf_counter_ns()
            try:
                try:
                    chunk = next(it)
                except StopIteration:
                    return
            finally:
                busy += time.perf_counter_ns() - t
                _current.reset(token)
            yield chunk
    except GeneratorExit:
        # the CONSUMER abandoned the stream (client hangup, ranged
        # probe) — routine, not an error: tail-keeping every
        # disconnect would crowd the ring with content-free trees
        sp.attrs["aborted"] = True
        raise
    except BaseException as e:
        sp.mark_error(f"{type(e).__name__}: {e}")
        raise
    finally:
        sp.attrs["busy_ns"] = busy
        sp.finish()
        # abandonment (GeneratorExit) must close the inner generator
        # NOW, not at GC: its finally blocks release locks and join
        # in-flight prefetch work (`yield from` did this implicitly)
        close = getattr(it, "close", None)
        if close is not None:
            close()


def attach_span(parent: Span, name: str, t0_ns: int,
                duration_s: float, **attrs) -> Optional[Span]:
    """Attach an externally-timed, already-finished span (work done on
    a shared thread no contextvar reaches, e.g. the batch scheduler's
    collector) under `parent`. `t0_ns` is the `time.perf_counter_ns()`
    stamp the caller took when the work began — the same clock every
    span carries, so the attached span orders against its siblings.
    Returns the new span (so the caller can attach stage children
    under it), or None past the trace's span budget."""
    root = parent.root or parent
    if not root._admit_child():
        return None
    sp = Span(name, parent.trace_id, parent_id=parent.span_id,
              attrs=attrs or None, root=root)
    # wall start rebuilt from the monotonic stamp, for the dump
    sp.start -= (sp.t0_ns - t0_ns) / 1e9
    sp.t0_ns = t0_ns
    sp.t1_ns = t0_ns + int(duration_s * 1e9)
    sp.cpu0_ns = -1
    sp.duration_s = duration_s
    parent.add_child(sp)
    return sp


class _Timed:
    """`with timed(name) as t:` — a span when a trace is active, and in
    any case the interval's seconds in `t.seconds` afterwards, from the
    span's own stamps: call sites that feed a counter or a latency
    tracker from the same interval take one timing, not two."""

    __slots__ = ("_ctx", "_span", "_t0_ns", "seconds")

    def __init__(self, ctx):
        self._ctx = ctx
        self.seconds = 0.0

    def __enter__(self) -> "_Timed":
        self._span = self._ctx.__enter__()
        self._t0_ns = self._span.t0_ns if self._span is not None \
            else time.perf_counter_ns()
        return self

    def annotate(self, **attrs) -> None:
        """Attributes known only once the interval's work has run."""
        if self._span is not None:
            self._span.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ctx.__exit__(exc_type, exc, tb)
        self.seconds = self._span.duration_s if self._span is not None \
            else (time.perf_counter_ns() - self._t0_ns) / 1e9
        return False


def timed(name: str, **attrs) -> _Timed:
    return _Timed(span(name, **attrs))


class _Accum:
    """One span for a boundary crossed many times (per chunk, per
    block): the caller adds each crossing's stamps, `flush()` attaches
    ONE span from the first start to the last end with the summed busy
    time and the call count in its attrs — a 64 MiB request stays far
    under MAX_SPANS. Not thread-safe: one thread adds, and flush runs
    after it is done."""

    __slots__ = ("name", "parent", "t0_ns", "t1_ns", "busy_ns", "calls")

    def __init__(self, name: str, parent: Span):
        self.name = name
        self.parent = parent
        self.t0_ns = self.t1_ns = self.busy_ns = self.calls = 0

    def add(self, t0_ns: int, t1_ns: int) -> None:
        if not self.calls:
            self.t0_ns = t0_ns
        self.t1_ns = t1_ns
        self.busy_ns += t1_ns - t0_ns
        self.calls += 1

    def flush(self, **attrs) -> None:
        if self.calls:
            attach_span(self.parent, self.name, self.t0_ns,
                        (self.t1_ns - self.t0_ns) / 1e9,
                        busy_ns=self.busy_ns, calls=self.calls, **attrs)
            self.busy_ns = self.calls = 0


class _NoAccum:
    """Shared do-nothing accumulator: no trace is active."""

    __slots__ = ()

    def add(self, t0_ns: int, t1_ns: int) -> None:
        pass

    def flush(self, **attrs) -> None:
        pass


_NOACCUM = _NoAccum()


def accum(name: str):
    """An accumulator under the current span — the shared no-op when no
    trace is active, so call sites add and flush unconditionally."""
    p = _current.get()
    return _Accum(name, p) if p is not None else _NOACCUM


# ---------------------------------------------------------------------------
# host thread pools: every task's wait for a thread
# ---------------------------------------------------------------------------

# a task's wait runs from tens of microseconds on an idle pool to
# hundreds of milliseconds behind a full one
POOL_WAIT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                     0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)
_POOL_WAIT = REGISTRY.histogram(
    "minio_tpu_host_pool_wait_seconds",
    "Wait of a host-pool task for a thread, submit -> start on a "
    "worker, by pool and fan-out stage (one observation a task)",
    buckets=POOL_WAIT_BUCKETS)


class _PoolQueue:
    """What the submit path keeps for one named pool: a token for each
    task submitted and not yet started (`pending`), the waits of
    started tasks not yet observed in the histogram (`waits`), and the
    pool's size. Both are deques, whose append, pop, popleft and len
    take no lock: a worker starting a task contends with no other (a
    lock or a future callback a task cost 20 writers of 10 MiB objects
    ~10 % of their rate on a 13-core TPU v5e host); the waits are folded
    into the histogram at scrape time, or by the worker that fills the
    buffer."""

    __slots__ = ("pool", "pending", "waits", "workers", "span_name")
    FOLD_AT = 1024

    def __init__(self, name: str, workers: int):
        self.pool = name
        self.pending: deque = deque()
        self.waits: deque = deque()
        self.workers = workers
        self.span_name = name + ".wait"

    def fold(self) -> None:
        while True:
            try:
                stage, wait_s = self.waits.popleft()
            except IndexError:
                return
            _POOL_WAIT.observe(wait_s, pool=self.pool, stage=stage)


_pool_queues: Dict[str, _PoolQueue] = {}


def host_pool(name: str, workers: int,
              thread_name_prefix: str) -> ThreadPoolExecutor:
    """A process-lifetime thread pool whose tasks go through `submit`
    under `name`; its size is what `minio_tpu_host_pool_workers`
    reports."""
    _pool_queues[name] = _PoolQueue(name, workers)
    return ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix=thread_name_prefix)


def submit(pool: ThreadPoolExecutor, pool_name: str, fn, *args,
           stage: str = ""):
    """`pool.submit(fn, *args)` with the task's wait for a thread
    recorded where it happens. The caller's span context rides along
    only when a trace is active (one Context copy a task: a Context
    must never run in two threads at once). When the task starts on
    its worker, the wait — submit stamp to start stamp — goes to
    `minio_tpu_host_pool_wait_seconds{pool,stage}` and, under the
    span that was current at submit, is attached as a finished span
    `<pool_name>.wait` with `stage=` and `ahead=` (the pool's tasks
    queued at submit). Nothing is attached without a trace, or once
    that span has finished: a quorum-abandoned laggard that starts
    after its request moved on is not a wait of that request. A task
    taken back before it started is taken back through `cancel`."""
    q = _pool_queues[pool_name]
    parent = _current.get()
    ahead = len(q.pending)
    q.pending.append(None)
    t_submit = time.perf_counter_ns()

    def run(*a):
        t_start = time.perf_counter_ns()
        q.pending.pop()
        wait_s = (t_start - t_submit) / 1e9
        q.waits.append((stage, wait_s))
        if len(q.waits) >= q.FOLD_AT:
            q.fold()
        if parent is not None and not parent.t1_ns:
            attach_span(parent, q.span_name, t_submit, wait_s,
                        stage=stage, ahead=ahead)
        return fn(*a)

    try:
        if parent is None:
            return pool.submit(run, *args)
        return pool.submit(contextvars.copy_context().run, run, *args)
    except BaseException:
        q.pending.pop()
        raise


def cancel(fut, pool_name: str) -> bool:
    """`fut.cancel()` for a task `submit` queued on `pool_name`: a task
    taken back before it started is no longer counted as queued."""
    if not fut.cancel():
        return False
    _pool_queues[pool_name].pending.pop()
    return True


def _collect_host_pools() -> None:
    """Registry collector: each named pool's waits observed, its queued
    tasks and its size."""
    queued = REGISTRY.gauge(
        "minio_tpu_host_pool_queued_tasks",
        "Tasks submitted to a host pool and not yet started on a "
        "worker")
    workers = REGISTRY.gauge(
        "minio_tpu_host_pool_workers", "Threads of a host pool")
    for pool, q in list(_pool_queues.items()):
        q.fold()
        queued.set(len(q.pending), pool=pool)
        workers.set(q.workers, pool=pool)


REGISTRY.register_collector(_collect_host_pools)


class SpanSink:
    """Tail-sampled store of finished traces + RPC-join fragments."""

    def __init__(self, capacity: int = KEEP,
                 slow_s: float = SLOW_S, sample: float = SAMPLE):
        from . import lockcheck
        self._mu = lockcheck.mutex("telemetry.spans")
        self.capacity = capacity
        self.slow_s = slow_s
        self.sample = sample
        self._kept: "deque[Span]" = deque(maxlen=capacity)
        # trace_id -> [fragment spans]; bounded FIFO eviction
        self._fragments: Dict[str, List[Span]] = {}
        self._fragment_order: "deque[str]" = deque()
        self._fragment_cap = 4 * capacity
        self.kept_total = 0
        self.dropped_total = 0
        # the window recorder: every finished root (and RPC fragment)
        # while on, whole, up to RECORD_CAP; the overflow is counted
        self._rec: List[Span] = []
        self._rec_dropped = 0
        self._rec_mark: tuple = ()
        self._rec_timer: Optional[threading.Timer] = None
        self.recorded: Optional[dict] = None

    def configure(self, slow_s: Optional[float] = None,
                  sample: Optional[float] = None) -> None:
        if slow_s is not None:
            self.slow_s = slow_s
        if sample is not None:
            self.sample = sample

    # -- ingest ------------------------------------------------------------

    def offer(self, root: Span) -> bool:
        """Tail-sampling: always keep errors and slow traces; keep the
        rest with probability `sample`. O(1): child errors were
        propagated to root.has_error as each span finished."""
        keep = bool(root.error) or root.has_error \
            or (root.duration_s >= self.slow_s
                and not root.slow_exempt) \
            or (self.sample > 0 and random.random() < self.sample)
        if _recording:
            self._record(root)
        with self._mu:
            if keep:
                self._kept.append(root)
                self.kept_total += 1
            else:
                self.dropped_total += 1
        return keep

    def record_fragment(self, sp: Span) -> None:
        if _recording:
            self._record(sp)
        with self._mu:
            frags = self._fragments.get(sp.trace_id)
            if frags is None:
                frags = self._fragments[sp.trace_id] = []
                self._fragment_order.append(sp.trace_id)
                while len(self._fragment_order) > self._fragment_cap:
                    evicted = self._fragment_order.popleft()
                    self._fragments.pop(evicted, None)
            if len(frags) < 64:           # bound one trace's fragments
                frags.append(sp)

    # -- the window recorder -----------------------------------------------

    RECORD_CAP = 4096

    def _record(self, root: Span) -> None:
        with self._mu:
            if len(self._rec) < self.RECORD_CAP:
                self._rec.append(root)
            else:
                self._rec_dropped += 1

    def record_begin(self) -> None:
        """Start keeping every finished root whole (a running recording
        starts over). Spans opened from now on stamp their thread CPU."""
        global _recording
        with self._mu:
            self._rec = []
            self._rec_dropped = 0
            self._rec_mark = (time.perf_counter_ns(), time.process_time())
        _recording = True

    def record_end(self) -> dict:
        """Stop, and return the window: `spans`, every span of every
        root that finished in it, flat (`parent_id` links them; a root
        has none); `roots` and `dropped` (roots past RECORD_CAP — never
        silent); `t_ns` and `cpu_s`, perf_counter_ns and the process's
        user+system CPU seconds at both ends. Spans still open at the
        end (a write the quorum ack abandoned) are left out."""
        global _recording
        _recording = False
        with self._mu:
            roots, self._rec = self._rec, []
            dropped, mark = self._rec_dropped, self._rec_mark
        t0_ns, cpu0 = mark or (time.perf_counter_ns(), time.process_time())
        return {
            "spans": [sp.to_dict(children=False)
                      for r in roots for sp in r.walk() if sp.t1_ns],
            "roots": len(roots), "dropped": dropped,
            "t_ns": [t0_ns, time.perf_counter_ns()],
            "cpu_s": [cpu0, time.process_time()]}

    def record_for(self, seconds: float) -> None:
        """The operator's form (admin `/spans?record=<seconds>`): record
        for `seconds`, then keep the window in `self.recorded` for the
        next fetch."""
        if self._rec_timer is not None:
            self._rec_timer.cancel()
        self.recorded = None
        self.record_begin()

        def done() -> None:
            self.recorded = self.record_end()
        self._rec_timer = threading.Timer(seconds, done)
        self._rec_timer.daemon = True
        self._rec_timer.start()

    # -- readback ----------------------------------------------------------

    def _graft(self, tree: dict, frags: List[Span]) -> None:
        """Attach fragments under the span that made the RPC (matched
        by parent span id); unmatched fragments land under the root."""
        index: Dict[str, dict] = {}

        def walk(node: dict) -> None:
            index[node["span_id"]] = node
            for c in node.get("children", ()):
                walk(c)

        walk(tree)
        for f in frags:
            target = index.get(f.parent_id, tree)
            target.setdefault("children", []).append(f.to_dict())

    def dump(self, n: int = 50, slowest: bool = False,
             name: str = "", trace_id: str = "") -> List[dict]:
        """Most recent (or slowest) kept traces as dict trees, with
        matching fragments grafted in. `name` keeps only roots with
        that span name (the per-API filter: root names ARE api names
        under the server middleware); `trace_id` selects one trace.
        Filters apply BEFORE the count cut, so `n` counts matches."""
        with self._mu:
            kept = list(self._kept)
            frags = {tid: list(fs) for tid, fs in self._fragments.items()}
        if name:
            kept = [s for s in kept if s.name == name]
        if trace_id:
            kept = [s for s in kept if s.trace_id == trace_id]
        if slowest:
            kept.sort(key=lambda s: -s.duration_s)
        else:
            kept.reverse()                # newest first
        out = []
        for root in kept[:max(n, 0)]:
            tree = root.to_dict()
            if root.trace_id in frags:
                self._graft(tree, frags[root.trace_id])
            out.append(tree)
        return out

    def clear(self) -> None:
        with self._mu:
            self._kept.clear()
            self._fragments.clear()
            self._fragment_order.clear()


SPANS = SpanSink()
