"""ScanEngine: the SelectObjectContent device/CPU routing seam.

``event_stream(req, data)`` is a drop-in replacement for
``s3select.select.event_stream``: it tries the device plan first —
compile the predicate (:mod:`.plan`), tokenize pages (:mod:`.pager`),
ride the batch former's ``scan`` verb (or run the kernels inline when
no scheduler is attached) — and on ANY decline falls back to the CPU
evaluator with byte-identical output (the erasure kernels' oracle
discipline: the fallback IS the oracle).

The device computes the row mask (and COUNT reductions); the passing
rows are then serialized by the SAME ``_emit``/framing helpers the CPU
path uses, over the SAME row dicts the CPU readers produce — so the
framed response (Records chunk boundaries, Stats, End) is identical by
construction, which the randomized property suite pins.

Metrics:
  minio_tpu_scan_requests_total{path=device|fallback}
  minio_tpu_scan_fallbacks_total{reason=...}
  minio_tpu_scan_pages_total / minio_tpu_scan_rows_total
  minio_tpu_scan_seconds{path=...}
"""

from __future__ import annotations

import time
from typing import Iterator, Optional

from ..s3select import select as sel
from ..s3select import sql as _sql
from ..utils import eventlog, knobs, telemetry
from . import kernels, pager
from .plan import Decline, compile_plan

#: device-path input cap: the kernels materialize the decompressed
#: object as row dicts + padded column pages (~10-40x the raw bytes),
#: so very large objects stream through the CPU evaluator instead
MAX_SCAN_BYTES = knobs.get_int("MINIO_TPU_SCAN_MAX_BYTES")


def _metrics():
    reg = telemetry.REGISTRY
    return (
        reg.counter("minio_tpu_scan_requests_total",
                    "SelectObjectContent requests by serving path"),
        reg.counter("minio_tpu_scan_fallbacks_total",
                    "Device-scan declines by reason (request fell back "
                    "to the CPU evaluator, output identical)"),
        reg.counter("minio_tpu_scan_pages_total",
                    "Tokenized pages submitted to the scan verb"),
        reg.counter("minio_tpu_scan_rows_total",
                    "Records scanned through the device path"),
        reg.histogram("minio_tpu_scan_seconds",
                      "SelectObjectContent wall time by serving path"),
    )


class ScanEngine:
    """Routes Select requests between the device plan and the CPU
    evaluator. One per server; `scheduler` is the shared multi-verb
    batch former (None = run kernels inline, still device-batched
    within the request)."""

    def __init__(self, scheduler=None):
        self.scheduler = scheduler
        self._m = _metrics()
        # stats (tests/bench)
        self.device_serves = 0
        self.fallbacks = 0
        self.fallback_reasons: dict[str, int] = {}

    # -- public seam -------------------------------------------------------

    def event_stream(self, req, data: bytes) -> Iterator[bytes]:
        """Full SelectObjectContent response body (generator)."""
        t0 = time.monotonic()
        try:
            frames = self._try_device(req, data)
        except Decline as d:
            frames = None
            self._count_fallback(d.reason)
        except Exception:  # noqa: BLE001 — any device-prep failure
            # falls back; the CPU path reproduces real input errors
            # (bad JSON, bad SQL) with their proper S3 error codes
            frames = None
            self._count_fallback("error")
        if frames is None:
            yield from sel.event_stream(req, data)
            self._m[0].inc(path="fallback")
            self._m[4].observe(time.monotonic() - t0, path="fallback")
            return
        yield from frames
        self.device_serves += 1
        self._m[0].inc(path="device")
        self._m[4].observe(time.monotonic() - t0, path="device")

    def stats(self) -> dict:
        return {"device_serves": self.device_serves,
                "fallbacks": self.fallbacks,
                "fallback_reasons": dict(self.fallback_reasons)}

    # -- device path -------------------------------------------------------

    def _count_fallback(self, reason: str) -> None:
        self.fallbacks += 1
        self.fallback_reasons[reason] = \
            self.fallback_reasons.get(reason, 0) + 1
        self._m[1].inc(reason=reason)
        eventlog.emit_once("device.decline", stage="scan",
                           reason=reason)

    def _try_device(self, req, data: bytes):
        """Returns the device-served frame iterator, or raises Decline.
        Everything that could change the response happens BEFORE the
        first frame is yielded, so a decline is always clean."""
        reason = kernels.decline_reason()
        if reason:
            # gate BEFORE the decompress/tokenize work: on a host with
            # no device every Select would otherwise pay the full page
            # build only to decline at submit time and re-parse on CPU
            raise Decline(reason)
        try:
            q = _sql.parse(req.expression)
        except _sql.SQLError:
            raise Decline("sql-error") from None   # CPU raises properly
        plan = compile_plan(q, req.input_format, req.json_type)
        with telemetry.span("scan.page", fmt=req.input_format):
            payload = sel._decompress(data, req.compression)
            if len(payload) > MAX_SCAN_BYTES:
                raise Decline("too-large")
            if req.input_format == "JSON":
                rows = list(sel._rows_json(payload, req))
            else:
                rows = list(sel._rows_csv(payload, req))
            pages = pager.build_pages(rows, plan)
        mask = self._run_pages(pages)
        self._m[2].inc(pages.n_pages)
        self._m[3].inc(pages.n_rows)
        rowmask = mask.reshape(-1)[:pages.n_rows]
        return self._frames(req, q, plan, rows, rowmask, pages, data)

    def _run_pages(self, pages) -> "pager.np.ndarray":
        """One boolean mask [B, R] via the batch former (coalescing
        with concurrent requests) or inline kernels."""
        if self.scheduler is not None:
            fut = self.scheduler.submit_scan(pages)
            try:
                out = fut.result()
            except Exception:  # noqa: BLE001 — dispatch failed
                raise Decline("dispatch-error") from None
            if out is None:
                raise Decline("declined")
            return out
        return kernels.run_batch(pages.plan, pages.arrays)

    # -- byte-identical emission -------------------------------------------

    def _records(self, req, q, plan, rows, rowmask, pages
                 ) -> Iterator[bytes]:
        """Serialized output records — the run_select loop with the
        WHERE decision replaced by the device mask."""
        from ..s3.s3errors import S3Error
        try:
            if plan.counts is not None:
                yield sel._emit(self._count_result(q, plan, rowmask,
                                                   pages), req)
                return
            emitted = 0
            for i, passed in enumerate(rowmask):
                if not passed:
                    continue
                row = rows[i]
                if q.star:
                    out = dict(row)
                else:
                    out = {}
                    for j, (e, alias) in enumerate(q.projections):
                        name = alias or (e.name
                                         if isinstance(e, _sql.Col)
                                         else f"_{j + 1}")
                        out[name] = _sql.evaluate(e, row, q.alias)
                yield sel._emit(out, req)
                emitted += 1
                if q.limit is not None and emitted >= q.limit:
                    return
        except _sql.SQLError as e:
            raise S3Error("InvalidArgument", f"SQL: {e}") from None

    def _count_result(self, q, plan, rowmask, pages) -> dict:
        """The Aggregator.result() dict for COUNT-only aggregates,
        computed from the device mask (exact integer reductions)."""
        import numpy as np
        nulls = pages.arrays["null"].reshape(
            -1, pages.arrays["null"].shape[-1])[:pages.n_rows]
        out = {}
        for i, ((_e, alias), spec) in enumerate(
                zip(q.projections, plan.counts)):
            name = alias or f"_{i + 1}"
            if spec is None:
                out[name] = None
            elif spec == "star":
                out[name] = int(np.count_nonzero(rowmask))
            else:
                out[name] = int(np.count_nonzero(
                    rowmask & ~nulls[:, spec]))
        return out

    def _frames(self, req, q, plan, rows, rowmask, pages, data: bytes
                ) -> Iterator[bytes]:
        """The CPU path's own framing loop over the device-masked
        records — shared code, so the framed stream cannot drift."""
        yield from sel.frame_records(
            self._records(req, q, plan, rows, rowmask, pages),
            len(data))
