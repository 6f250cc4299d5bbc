#!/usr/bin/env python3
"""What does a degraded GET's verify+decode step pay to COPY its shards,
against what it pays to hash them — and does a group-sized allocation
cost more at 32 MiB and over?

A degraded GET group is 8 blocks of k shard frames, each frame its own
`bytes` object as the shard reader returned it. Before it was changed,
the engine stacked them twice for a decode launch (a (k, S) array a
block, then the (G, k, S) launch input) and once for the host verify
(a (G·k, S) array for `bitrot.hash_shards_batch`). Both arrays are
about 2**25 bytes at the benchmark geometries, glibc's largest mmap
threshold: a fresh one is an `mmap`, its first-touch page faults and
an `munmap`. This probe times, at the cells' shapes, on 1 and on 8
threads at once (each thread its own frames):

  gather   nested_stack  the two `np.stack`s of the old decode input
           fresh_copy    one copy a frame into a fresh (G, k, S) array
           pooled_copy   the same into a buffer reused every time, a
                         numpy assignment a frame (the interpreter lock
                         released and taken back once a frame)
           native_gather the same in one `native.gather_rows` call
  verify   stack_hash    `np.stack` + `hash_shards_batch` (the old
                         host verify)
           pooled_hash   copy into a reused buffer + `hash_shards_batch`
           rows_hash     `bitrot.hash_rows`: each frame where it lies
           hash_only     `hash_shards_batch` on a ready contiguous array
  alloc    a fresh array filled, against a reused one, one MiB each
           side of 2**25 bytes — under glibc's defaults, then again
           with `mallopt` holding every allocation on the heap
  readback (a chip only) `np.asarray` of a device uint8 array of those
           two sizes, under the defaults and under that `mallopt`: if
           the allocator made the readback cliff (a uint8 result of
           32 MiB or more read back at a quarter of the rate, in
           `tools/readback_probe.py`), it goes away

    python tools/gather_probe.py            # chip host; ~2 min
    python tools/gather_probe.py --tiny     # small shapes: control flow only

Writes chiprun_out/gather_probe.json and prints the tables, ms an
operation (median over threads and repetitions). No benchmark file: a
tool, like readback_probe.py.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from minio_tpu import bitrot  # noqa: E402
from minio_tpu.utils import native  # noqa: E402

HH = bitrot.BitrotAlgorithm.HIGHWAYHASH256S
# name -> (blocks a group, k, S)
GEOMETRIES = {"12+4": (8, 12, 349526), "8+8": (8, 8, 524288)}
TINY = {"12+4": (8, 12, 3495), "8+8": (8, 8, 5243)}
MIB = 1 << 20
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3


def _frames(g, k, s, seed):
    rng = np.random.default_rng(seed)
    return [np.frombuffer(rng.integers(0, 256, s, dtype=np.uint8).tobytes(),
                          dtype=np.uint8) for _ in range(g * k)]


def _copy_into(out, frames, g, k):
    for b in range(g):
        for c in range(k):
            out[b, c] = frames[b * k + c]
    return out


def _ops(g, k, s):
    """op name -> fn(state) -> None; state is one thread's frames and
    its reused buffers."""
    def nested(st):
        f = st["frames"]
        np.stack([np.stack(f[b * k:(b + 1) * k]) for b in range(g)])

    def fresh(st):
        _copy_into(np.empty((g, k, s), np.uint8), st["frames"], g, k)

    def pooled(st):
        _copy_into(st["buf"], st["frames"], g, k)

    def native_gather(st):
        native.gather_rows(st["frames"], st["buf"].reshape(-1))

    def stack_hash(st):
        bitrot.hash_shards_batch(np.stack(st["frames"]), HH)

    def pooled_hash(st):
        _copy_into(st["buf"], st["frames"], g, k)
        bitrot.hash_shards_batch(st["buf"].reshape(g * k, s), HH)

    def rows_hash(st):
        bitrot.hash_rows(st["frames"], HH)

    def hash_only(st):
        bitrot.hash_shards_batch(st["ready"], HH)
    return {"nested_stack": nested, "fresh_copy": fresh,
            "pooled_copy": pooled, "native_gather": native_gather,
            "stack_hash": stack_hash,
            "pooled_hash": pooled_hash, "rows_hash": rows_hash,
            "hash_only": hash_only}


def _timed(fn, states, reps):
    """Each state on its own thread, `reps` calls after one warm call;
    the median call, ms."""
    times: list[float] = []
    mu = threading.Lock()
    start = threading.Barrier(len(states))

    def work(st):
        fn(st)
        start.wait()
        mine = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(st)
            mine.append((time.perf_counter() - t0) * 1e3)
        with mu:
            times.extend(mine)
    threads = [threading.Thread(target=work, args=(st,)) for st in states]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return round(statistics.median(times), 3)


def gather(geometries, reps, thread_counts):
    out = {}
    for name, (g, k, s) in geometries.items():
        states = []
        for i in range(max(thread_counts)):
            frames = _frames(g, k, s, seed=i)
            states.append({"frames": frames,
                           "buf": np.empty((g, k, s), np.uint8),
                           "ready": np.stack(frames)})
        row = {"shape": [g, k, s], "bytes": g * k * s}
        for op, fn in _ops(g, k, s).items():
            row[op] = {str(n): _timed(fn, states[:n], reps)
                       for n in thread_counts}
        out[name] = row
    return out


def alloc(sizes, reps, thread_counts):
    out = {}
    for size in sizes:
        src = [np.full(size, i, np.uint8) for i in range(max(thread_counts))]
        bufs = [np.empty(size, np.uint8) for _ in src]

        def fresh(st):
            np.copyto(np.empty(size, np.uint8), st[0])

        def reused(st):
            np.copyto(st[1], st[0])
        states = list(zip(src, bufs))
        out[str(size)] = {
            op: {str(n): _timed(fn, states[:n], reps) for n in thread_counts}
            for op, fn in (("fresh", fresh), ("reused", reused))}
    return out


def readback(sizes, reps):
    """GiB/s of `np.asarray` of a fresh device uint8 array a call."""
    import jax
    import jax.numpy as jnp
    make = jax.jit(lambda x, i: x + i.astype(jnp.uint8))
    out = {}
    for size in sizes:
        x = jnp.zeros(size, jnp.uint8)
        rates = []
        for i in range(reps + 1):
            y = jax.block_until_ready(make(x, jnp.int32(i)))
            t0 = time.perf_counter()
            np.asarray(y)
            dt = time.perf_counter() - t0
            if i:
                rates.append(size / dt / (1 << 30))
        out[str(size)] = round(statistics.median(rates), 3)
    return out


def heap_only() -> bool:
    """Every allocation from the heap from here on: no mmap a block,
    nothing trimmed back to the kernel."""
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return False
    return bool(libc.mallopt(M_MMAP_THRESHOLD, 1 << 30)
                and libc.mallopt(M_TRIM_THRESHOLD, 1 << 30))


def show(report):
    for name, row in report["gather"].items():
        print(f"\n{name} {row['shape']} = {row['bytes']} bytes, ms a group")
        for op, by in row.items():
            if isinstance(by, dict):
                print(f"  {op:14s} " + "  ".join(
                    f"{n} thr {v:9.3f}" for n, v in by.items()))
    for phase in ("alloc", "alloc_heap_only"):
        print(f"\n{phase}, ms a fill")
        for size, by in report[phase].items():
            for op, v in by.items():
                print(f"  {int(size) / MIB:7.2f} MiB {op:7s} " + "  ".join(
                    f"{n} thr {t:9.3f}" for n, t in v.items()))
    for phase in ("readback", "readback_heap_only"):
        if report.get(phase):
            print(f"\n{phase}, GiB/s: " + "  ".join(
                f"{int(s) / MIB:.2f} MiB {r}"
                for s, r in report[phase].items()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes: the control flow, not the costs")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--threads", default="1,8")
    ap.add_argument("--out", default="chiprun_out/gather_probe.json")
    args = ap.parse_args()
    thread_counts = [int(t) for t in args.threads.split(",")]
    geometries = TINY if args.tiny else GEOMETRIES
    edge = (1 << 25) // (64 if args.tiny else 1)
    step = MIB // (64 if args.tiny else 1)
    sizes = [edge - step, edge + step]
    report = {"cpus": os.cpu_count(),
              "gather": gather(geometries, args.reps, thread_counts),
              "alloc": alloc(sizes, args.reps, thread_counts)}
    on_chip = False
    if not args.tiny:
        import jax
        on_chip = jax.devices()[0].platform == "tpu"
    report["readback"] = readback(sizes, args.reps) if on_chip else None
    report["heap_only"] = heap_only()
    report["alloc_heap_only"] = alloc(sizes, args.reps, thread_counts)
    report["readback_heap_only"] = readback(sizes, args.reps) \
        if on_chip else None
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    show(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
