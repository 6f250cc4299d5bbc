#!/usr/bin/env python3
"""At what rate does a launch's parity cross back to the host, and in
which FORM does it cross fastest?

Until PR 34 a PUT launch uploaded a (B, k, S) uint8 array and read
back a (B, m, S) uint8 one; at 8+8 the two have the same shape and
type, and the readback ran at a seventh of the upload's rate (PERF.md
§6, PR 34). This probe runs the PUT step at the two benchmark
geometries' shapes, the programs warm, and times the readback of the
parity alone, a form a column, on a quiet host AND beside 16 threads
that write 512 KiB frames to tmpfs (what the drives do during a
window). The step here is `put_step` as it was (`put_step_u8` below:
parity leaves as uint8), so the table can be taken again on any later
tree; "u32 (B,m,S/4)" is the form `models/pipeline.link_rows` adopted:

  survey   parity -> form (a small program of its own on the resident
           parity) -> `np.asarray`; GiB/s of the readback alone and of
           program + readback; the result's on-device layout
  pinned   the form program places its output in `pinned_host` memory
           itself (`out_shardings`); program + `np.asarray`
  step     the whole fused step with the form as its output, timed
           launch -> host array: `block_until_ready` then `np.asarray`
           (before PR 34) against `copy_to_host_async()` at launch
  cut      a padded launch: a cut program on the device (`head_blocks`,
           as before PR 34) + readback of the real blocks, against
           reading the whole rung and cutting the host view

    python tools/readback_probe.py            # a chip; ~6 min
    python tools/readback_probe.py --tiny     # XLA-CPU rehearsal: NOT rates

Writes chiprun_out/readback_probe.json and prints the tables. No
benchmark file: a tool, like bench.py.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from minio_tpu.models import pipeline  # noqa: E402
from minio_tpu.ops import rs_matrix, rs_tpu  # noqa: E402

# name -> (k, m, S, rungs, (padded rung, its real blocks))
GEOMETRIES = {
    "8+8": (8, 8, 524288, (8, 16, 20, 24), (20, 18)),
    "12+4": (12, 4, 349526, (8, 12, 16), (12, 11)),
}
TINY = {
    "8+8": (8, 8, 512, (2, 4), (4, 3)),
    "12+4": (12, 4, 346, (2, 4), (4, 3)),
}
GIB = float(1 << 30)


@functools.partial(jax.jit, static_argnums=(1, 2))
def put_step_u8(data, k: int, m: int):
    """`models/pipeline.put_step` as it was before PR 34: the same
    matmul and hash, parity leaving as (B, m, S) uint8."""
    b, _k, s = data.shape
    pm = np.asarray(rs_matrix.parity_matrix(k, m))
    parity = pipeline._rs_matmul(
        rs_tpu._bit_expand_cached(pm.tobytes(), pm.shape), data, m, k)
    rows = jnp.concatenate([data, parity], axis=-2).reshape(b * (k + m), s)
    digests = pipeline._hash_rows(rows, s, b"", "highwayhash")
    return parity, digests.reshape(b, k + m, 32)


@functools.partial(jax.jit, static_argnums=(1,))
def head_blocks(outputs: tuple, n: int) -> tuple:
    """The cut program of a padded launch, as it was before PR 34."""
    return tuple(o[:n] for o in outputs)


def _words(p):
    """(B, m, S) uint8 -> (B, m, ceil(S / 4)) uint32: the same bytes,
    S rounded up to the word on the device."""
    b, m, s = p.shape
    s4 = -(-s // 4) * 4
    if s4 != s:
        p = jnp.pad(p, ((0, 0), (0, 0), (0, s4 - s)))
    return lax.bitcast_convert_type(p.reshape(b, m, s4 // 4, 4), jnp.uint32)


# form -> (device side: parity (B, m, S) u8 -> what crosses back,
#          host side: that array -> a (B, m, S) u8 VIEW of it, no copy)
FORMS = {
    "u8 (B,m,S) [before]": (
        None, lambda h, b, m, s: h),
    "u32 (B,m,S/4)": (
        _words, lambda h, b, m, s: h.view(np.uint8)[..., :s]),
    "u32 (B,m*S/4)": (
        lambda p: _words(p).reshape(p.shape[0], -1),
        lambda h, b, m, s: h.view(np.uint8).reshape(b, m, -1)[..., :s]),
    "u8 (B*m,S)": (
        lambda p: p.reshape(-1, p.shape[2]),
        lambda h, b, m, s: h.reshape(b, m, s)),
    "u8 flat": (
        lambda p: p.reshape(-1), lambda h, b, m, s: h.reshape(b, m, s)),
    "u32 flat": (
        lambda p: _words(p).reshape(-1),
        lambda h, b, m, s: h.view(np.uint8).reshape(b, m, -1)[..., :s]),
    # four rows a tile, as 12+4's parity has by itself
    "u8 (B*m/4,4,S)": (
        lambda p: p.reshape(-1, 4, p.shape[2]),
        lambda h, b, m, s: h.reshape(b, m, s)),
    "u32 (B*m/4,4,S/4)": (
        lambda p: _words(p).reshape(-1, 4, -(-p.shape[2] // 4)),
        lambda h, b, m, s: h.view(np.uint8).reshape(b, m, -1)[..., :s]),
    # a row stride that is no power of two (8+8's S is 2**19)
    "u8 (B,m,S+512)": (
        lambda p: jnp.pad(p, ((0, 0), (0, 0), (0, 512))),
        lambda h, b, m, s: h[..., :s]),
    "u32 (B,m,S/4+128)": (
        lambda p: jnp.pad(_words(p), ((0, 0), (0, 0), (0, 128))),
        lambda h, b, m, s: h.view(np.uint8)[..., :s]),
    # a row that does NOT end on a lane tile (12+4's S does not), and
    # one that does (8+8's S does)
    "u8 (B,m,S+4)": (
        lambda p: jnp.pad(p, ((0, 0), (0, 0), (0, 4))),
        lambda h, b, m, s: h[..., :s]),
    "u32 (B,m,S/4+1)": (
        lambda p: jnp.pad(_words(p), ((0, 0), (0, 0), (0, 1))),
        lambda h, b, m, s: h.view(np.uint8)[..., :s]),
    "u8 (B,m,S^128)": (
        lambda p: jnp.pad(p, ((0, 0), (0, 0), (0, -p.shape[2] % 128))),
        lambda h, b, m, s: h[..., :s]),
}
STEP_FORMS = ["u8 (B,m,S) [before]", "u32 (B,m,S/4)", "u8 (B,m,S+4)",
              "u32 (B,m,S/4+1)"]


class HostLoad:
    """`threads` threads that each write `frame`-byte frames to a file
    of their own on tmpfs, rewinding every `file_bytes`."""

    def __init__(self, threads: int = 16, frame: int = 512 << 10,
                 file_bytes: int = 64 << 20):
        base = "/dev/shm" if os.path.isdir("/dev/shm") else None
        self.dir = tempfile.mkdtemp(prefix="readback_probe.", dir=base)
        self.stop = threading.Event()
        self.written = [0] * threads
        self.t0 = time.perf_counter()
        self.threads = [threading.Thread(
            target=self._run, args=(i, frame, file_bytes), daemon=True)
            for i in range(threads)]
        for t in self.threads:
            t.start()

    def _run(self, i: int, frame: int, file_bytes: int) -> None:
        buf = np.random.default_rng(i).integers(
            0, 256, frame, dtype=np.uint8).tobytes()
        fd = os.open(os.path.join(self.dir, f"d{i}"),
                     os.O_WRONLY | os.O_CREAT, 0o600)
        try:
            at = 0
            while not self.stop.is_set():
                at += os.write(fd, buf)
                self.written[i] += frame
                if at >= file_bytes:
                    os.lseek(fd, 0, os.SEEK_SET)
                    at = 0
        finally:
            os.close(fd)

    def close(self) -> float:
        """-> GiB/s the writers reached."""
        self.stop.set()
        for t in self.threads:
            t.join()
        rate = sum(self.written) / GIB / (time.perf_counter() - self.t0)
        shutil.rmtree(self.dir, ignore_errors=True)
        return rate


def _layout(arr) -> str:
    try:
        lay = arr.format.layout
        return (f"{arr.dtype.name}{list(arr.shape)} major_to_minor="
                f"{lay.major_to_minor} tiling={lay.tiling}")
    except Exception as e:  # noqa: BLE001 — a probe prints what it can
        return f"{arr.dtype.name}{list(arr.shape)} ({type(e).__name__})"


def _med(xs) -> float:
    return statistics.median(xs)


class Probe:
    def __init__(self, name: str, k: int, m: int, s: int, launches: int):
        self.name, self.k, self.m, self.s = name, k, m, s
        self.launches = launches
        self.form_progs = {
            form: (jax.jit(dev) if dev is not None else None)
            for form, (dev, _host) in FORMS.items()}
        self.step_progs = {form: self._step_prog(form)
                           for form in STEP_FORMS}
        self.data: dict[int, jax.Array] = {}
        self.want: dict[int, np.ndarray] = {}

    def _step_prog(self, form: str):
        dev = FORMS[form][0]
        if dev is None:
            return lambda data: put_step_u8(data, self.k, self.m)

        @jax.jit
        def step(data):
            parity, digests = put_step_u8(data, self.k, self.m)
            return dev(parity), digests
        return step

    def blocks(self, b: int) -> jax.Array:
        if b not in self.data:
            host = np.random.default_rng(b).integers(
                0, 256, (b, self.k, self.s), dtype=np.uint8)
            self.data[b] = jax.block_until_ready(jax.device_put(host))
        return self.data[b]

    def warm(self, b: int, form: str) -> None:
        """Compile and run the step in `form` at `b` blocks; today's
        form leaves the parity every other form is held to."""
        outs = jax.block_until_ready(self.step_progs[form](self.blocks(b)))
        if FORMS[form][0] is None:
            self.want[b] = np.array(outs[0])

    def upload(self, b: int) -> float:
        """GiB/s of `device_put` of a (b, k, S) uint8 array, waited."""
        host = np.asarray(self.blocks(b))
        secs = []
        for _ in range(self.launches):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(host))
            secs.append(time.perf_counter() - t0)
        return host.nbytes / GIB / _med(secs)

    def survey(self, b: int, form: str, pinned: bool = False) -> dict:
        """Readback of the parity of a launch of `b` blocks in `form`:
        medians over the launches."""
        data = self.blocks(b)
        prog = self.form_progs[form]
        if pinned:
            dev = FORMS[form][0] or (lambda p: p)
            prog = jax.jit(dev, out_shardings=jax.sharding.
                           SingleDeviceSharding(jax.devices()[0],
                                                memory_kind="pinned_host"))
        to_host = FORMS[form][1]
        t_prog, t_read, layout = [], [], ""
        for i in range(self.launches + 1):
            parity, _digests = put_step_u8(data, self.k, self.m)
            jax.block_until_ready(parity)
            t0 = time.perf_counter()
            out = parity if prog is None else prog(parity)
            jax.block_until_ready(out)
            t1 = time.perf_counter()
            host = np.asarray(out)
            t2 = time.perf_counter()
            if i == 0:      # the form program's compile; the check
                layout = _layout(out)
                view = to_host(host, b, self.m, self.s)
                assert view.shape == (b, self.m, self.s) \
                    and view.dtype == np.uint8, (view.shape, view.dtype)
                assert np.shares_memory(view, host), "the view copied"
                assert np.array_equal(view, self.want[b]), \
                    f"{form}: bytes differ"
                # what the host got: its strides follow the device's
                # dimension order, so say them
                strides = host.strides
                rows_whole = all(view[x, y].flags.c_contiguous
                                 for x in range(b) for y in range(self.m))
                continue
            t_prog.append(t1 - t0)
            t_read.append(t2 - t1)
        nbytes = b * self.m * self.s
        return {"geometry": self.name, "B": b, "form": form,
                "pinned": pinned, "layout": layout, "bytes": nbytes,
                "host_strides": list(strides), "rows_contiguous": rows_whole,
                "form_ms": 1e3 * _med(t_prog),
                "read_ms": 1e3 * _med(t_read),
                "read_GiB_s": nbytes / GIB / _med(t_read),
                "both_GiB_s": nbytes / GIB / _med(
                    [p + r for p, r in zip(t_prog, t_read)])}

    def step(self, b: int, form: str, early: bool) -> dict:
        """The whole fused step with `form` as its output, launch ->
        host arrays; `early`: `copy_to_host_async()` at launch."""
        data = self.blocks(b)
        prog = self.step_progs[form]
        to_host = FORMS[form][1]
        t_compute, t_fetch = [], []
        for i in range(self.launches + 1):
            t0 = time.perf_counter()
            outs = prog(data)
            if early:
                for o in outs:
                    o.copy_to_host_async()
            jax.block_until_ready(outs)
            t1 = time.perf_counter()
            host = tuple(np.asarray(o) for o in outs)
            t2 = time.perf_counter()
            if i == 0:
                assert np.array_equal(
                    to_host(host[0], b, self.m, self.s), self.want[b])
                continue
            t_compute.append(t1 - t0)
            t_fetch.append(t2 - t1)
        nbytes = b * self.m * self.s
        total = _med([c + f for c, f in zip(t_compute, t_fetch)])
        return {"geometry": self.name, "B": b, "form": form,
                "early_copy": early, "compute_ms": 1e3 * _med(t_compute),
                "fetch_ms": 1e3 * _med(t_fetch), "total_ms": 1e3 * total,
                "total_GiB_s": nbytes / GIB / total}

    def cut(self, rung: int, n: int, form: str) -> dict:
        """A launch of `n` blocks at `rung`: `head_blocks` on the
        device then the readback, against the readback of the whole
        rung cut on the host view."""
        data = self.blocks(rung)
        prog = self.step_progs[form]
        to_host = FORMS[form][1]
        flat = "(B,m," not in form  # no block axis left to cut
        t_dev, t_host = [], []
        for i in range(self.launches + 1):
            outs = jax.block_until_ready(prog(data))
            if not flat:
                t0 = time.perf_counter()
                head = head_blocks(tuple(outs), n)
                got = to_host(np.asarray(head[0]), n, self.m, self.s)
                dig = np.asarray(head[1])
                t_dev.append(time.perf_counter() - t0)
                assert np.array_equal(got, self.want[rung][:n])
            outs = jax.block_until_ready(prog(data))
            t0 = time.perf_counter()
            got = to_host(np.asarray(outs[0]), rung, self.m, self.s)[:n]
            dig = np.asarray(outs[1])[:n]
            t_host.append(time.perf_counter() - t0)
            assert np.array_equal(got, self.want[rung][:n]) and len(dig) == n
        return {"geometry": self.name, "rung": rung, "real": n,
                "form": form,
                "head_blocks_then_read_ms":
                    1e3 * _med(t_dev[1:]) if t_dev else None,
                "read_rung_cut_on_host_ms": 1e3 * _med(t_host[1:])}


def run(geometries: dict, launches: int) -> dict:
    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform,
                         "kind": dev.device_kind},
              "jax": jax.__version__, "launches": launches,
              "upload": [], "survey": [], "step": [], "cut": []}
    probes = {name: Probe(name, k, m, s, launches)
              for name, (k, m, s, _rungs, _pad) in geometries.items()}
    # every step program the tables need, compiled four at a time
    jobs = [(probes[name], b, form)
            for name, (_k, _m, _s, rungs, (rung, _n)) in geometries.items()
            for b in sorted({*rungs, rung})
            for form in STEP_FORMS
            if FORMS[form][0] is None or b in (*rungs[1:3], rung)]
    for p, b, _form in jobs:
        p.blocks(b)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(lambda j: j[0].warm(j[1], j[2]), jobs))
    report["warm_s"] = time.perf_counter() - t0
    report["programs"] = len(jobs)
    for host_state in ("quiet", "loaded"):
        load = HostLoad() if host_state == "loaded" else None
        try:
            for name, (_k, _m, _s, rungs, (rung, real)) in \
                    geometries.items():
                p = probes[name]
                for b in rungs:
                    report["upload"].append(
                        {"geometry": name, "B": b, "host": host_state,
                         "GiB_s": p.upload(b)})
                    for form in FORMS:
                        report["survey"].append(
                            {"host": host_state, **p.survey(b, form)})
                mid = rungs[len(rungs) // 2]
                for form in STEP_FORMS[:2]:
                    try:
                        report["survey"].append(
                            {"host": host_state,
                             **p.survey(mid, form, pinned=True)})
                    except Exception as e:  # noqa: BLE001 — said, not hidden
                        report["survey"].append(
                            {"host": host_state, "geometry": name,
                             "B": mid, "form": form, "pinned": True,
                             "error": f"{type(e).__name__}: {e}"[:300]})
                for b in rungs[1:3]:
                    for form in STEP_FORMS:
                        for early in (False, True):
                            report["step"].append(
                                {"host": host_state,
                                 **p.step(b, form, early)})
                for form in STEP_FORMS:
                    report["cut"].append(
                        {"host": host_state, **p.cut(rung, real, form)})
        finally:
            if load is not None:
                report["load_GiB_s"] = load.close()
    return report


def show(report: dict) -> None:
    print(f"device {report['device']}  jax {report['jax']}  "
          f"launches a row {report['launches']}  "
          f"load {report.get('load_GiB_s', 0):.2f} GiB/s")
    print("\nupload of (B, k, S) uint8, GiB/s")
    for r in report["upload"]:
        print(f"  {r['geometry']:5} B={r['B']:2} {r['host']:6} "
              f"{r['GiB_s']:6.2f}")
    print("\nsurvey: readback of parity, a form a row")
    seen = set()
    for r in report["survey"]:
        if "error" in r:
            print(f"  {r['geometry']:5} B={r['B']:2} {r['host']:6} "
                  f"{r['form']:20} pinned: {r['error']}")
            continue
        key = (r["geometry"], r["form"], r["pinned"])
        if key not in seen:
            seen.add(key)
            print(f"  layout {r['geometry']} {r['form']}"
                  f"{' pinned' if r['pinned'] else ''}: {r['layout']}")
        if not r["rows_contiguous"]:
            print(f"  ROWS NOT CONTIGUOUS on the host: {r['geometry']} "
                  f"B={r['B']} {r['form']} strides {r['host_strides']}")
        print(f"  {r['geometry']:5} B={r['B']:2} {r['host']:6} "
              f"{r['form']:20}{' pinned' if r['pinned'] else '':7} "
              f"form {r['form_ms']:7.2f} ms  read {r['read_ms']:7.2f} ms "
              f"= {r['read_GiB_s']:5.2f} GiB/s  "
              f"(form + read {r['both_GiB_s']:5.2f})")
    print("\nstep: launch -> host arrays, the form the step's output")
    for r in report["step"]:
        print(f"  {r['geometry']:5} B={r['B']:2} {r['host']:6} "
              f"{r['form']:20} early_copy={int(r['early_copy'])} "
              f"compute {r['compute_ms']:7.2f}  fetch "
              f"{r['fetch_ms']:7.2f}  total {r['total_ms']:7.2f} ms "
              f"= {r['total_GiB_s']:5.2f} GiB/s")
    print("\ncut: a padded launch")
    for r in report["cut"]:
        head = r["head_blocks_then_read_ms"]
        print(f"  {r['geometry']:5} {r['real']} of {r['rung']} "
              f"{r['host']:6} {r['form']:20} head_blocks + read "
              f"{'   —   ' if head is None else f'{head:7.2f}'} ms   "
              f"read rung, cut on host "
              f"{r['read_rung_cut_on_host_ms']:7.2f} ms")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes for an XLA-CPU rehearsal")
    ap.add_argument("--launches", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/readback_probe.json")
    ap.add_argument("--forms", default="",
                    help="only the forms that contain one of these, "
                         "comma-separated (the uint8 form always)")
    ap.add_argument("--geometries", default="",
                    help="only these geometries, comma-separated")
    ap.add_argument("--rungs", default="",
                    help="other block counts, e.g. '8+8:2,4,6;12+4:24,32' "
                         "(is it the result's SIZE that sets the rate?)")
    args = ap.parse_args()
    if args.forms:
        keep = [f for f in FORMS if FORMS[f][0] is None
                or any(w in f for w in args.forms.split(","))]
        for f in [f for f in FORMS if f not in keep]:
            del FORMS[f]
        STEP_FORMS[:] = [f for f in STEP_FORMS if f in FORMS]
    if not args.tiny and jax.devices()[0].platform != "tpu":
        print("readback_probe: no TPU; a rate comes only from a chip "
              "(--tiny rehearses the control flow)", file=sys.stderr)
        return 3
    geometries = TINY if args.tiny else GEOMETRIES
    if args.geometries:
        geometries = {g: geometries[g] for g in args.geometries.split(",")}
    for spec in filter(None, args.rungs.split(";")):
        name, _, counts = spec.partition(":")
        k, m, s, _rungs, pad = geometries[name]
        geometries[name] = (k, m, s, tuple(map(int, counts.split(","))), pad)
    report = run(geometries, args.launches)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    show(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
