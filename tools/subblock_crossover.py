#!/usr/bin/env python3
"""Where does the device start to beat the host for objects under one
block?

An object whose whole body is under one 4 MiB block is one short block
of shard length S_t = ceil(bytes / k). The PUT path can encode it on
the host (native GF(2^8) matmul + HighwayHash, `engine._host_encode`)
or launch it on the device at the smallest S rung >= S_t
(`parallel/ladder.s_rungs`: the ragged step `put_step_ragged` at a
shard length below the full block's). This probe times both, for every
S_t of the warp `--obj.randsize` mix at 12+4 (octave midpoints of 40
KiB - 10 MiB), a launch of B = 1, 2 and 4 such blocks:

  host    the blocks' contiguous (k, S_t) copy, parity, and the digests
          of every data and parity row — what the engine does when a
          launch's future resolves to None
  device  one launch through `Codec._launch` (the ragged row, forced to
          the device): upload, step, readback, host views — the batch
          former's own call, its program warm

Each is timed on a quiet host and beside 6 Python threads that hold the
interpreter lock in turn (a server's drive and request threads do).
Wall ms, medians (a thread's CPU clock ticks in 5-10 ms steps on the
chip's host: too coarse for these calls). The route
`object/codec.py` takes for a sub-block launch is set from the table
this prints (PERF.md gives the numbers).

    python tools/subblock_crossover.py            # a chip; ~3 min
    python tools/subblock_crossover.py --tiny   # XLA-CPU rehearsal: NOT rates

Writes the report as JSON to `--out` (.bench_out/subblock_crossover.json).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from minio_tpu import bitrot  # noqa: E402
from minio_tpu.object.codec import FUSED, Codec  # noqa: E402
from minio_tpu.parallel import ladder  # noqa: E402
from minio_tpu.utils import device  # noqa: E402

# the octave midpoints of warp's --obj.size 10MiB --obj.randsize range
# (40 KiB - 10 MiB) that are under one 4 MiB block
OBJECT_BYTES = (57926, 115852, 231705, 463410, 926819, 1853638, 3707276)
BLOCK = 4 << 20
K, M = 12, 4
BATCHES = (1, 2, 4)
ALGO = bitrot.BitrotAlgorithm.HIGHWAYHASH256S


def _med(xs) -> float:
    return statistics.median(xs)


class GilLoad:
    """`threads` Python threads that each run a pure-Python loop: the
    interpreter lock changes hands every switch interval, as it does
    among a server's threads."""

    def __init__(self, threads: int = 6):
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._spin, daemon=True)
                        for _ in range(threads)]
        for t in self.threads:
            t.start()

    def _spin(self) -> None:
        x = 0
        while not self.stop.is_set():
            for i in range(2000):
                x ^= i

    def close(self) -> None:
        self.stop.set()
        for t in self.threads:
            t.join()


def host_encode(codec: Codec, data: np.ndarray, s_t: int) -> None:
    """`engine._host_encode` of (B, k, S_t): what the engine runs for
    a launch whose future resolved to None."""
    b = data.shape[0]
    rows = np.ascontiguousarray(data[:, :, :s_t])
    parity = codec.encode_parity_batch(rows, force="native")
    bitrot.hash_shards_batch(rows.reshape(b * codec.k, -1), ALGO)
    bitrot.hash_shards_batch(parity.reshape(b * codec.m, -1), ALGO)


def device_encode(codec: Codec, data: np.ndarray, lengths: np.ndarray,
                  stages: dict | None = None):
    def cb(stage, seconds, **_kw):
        if stages is not None:
            stages.setdefault(stage, []).append(seconds)
    return codec._launch(FUSED["encode_and_hash_batch.ragged"], data,
                         (lengths,), (), ALGO, force="device",
                         stage_cb=cb)


def timed(fn, reps: int) -> float:
    """Wall ms, the median over `reps` calls."""
    wall = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        wall.append(time.perf_counter() - t0)
    return 1e3 * _med(wall)


def run(object_bytes, block: int, reps: int) -> dict:
    full_s = -(-block // K)
    rungs = ladder.s_rungs(full_s)
    dev = jax.devices()[0]
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "jax": jax.__version__, "k": K, "m": M, "full_S": full_s,
              "s_rungs": list(rungs), "reps": reps, "rows": [],
              "compile": []}
    rng = np.random.default_rng(0)
    cases = []
    for nbytes in object_bytes:
        s_t = -(-nbytes // K)
        s_r = ladder.s_rung(full_s, s_t)
        codec = Codec(K, M, s_r * K)
        for b in BATCHES:
            data = np.zeros((b, K, s_r), np.uint8)
            data[:, :, :s_t] = rng.integers(0, 256, (b, K, s_t),
                                            dtype=np.uint8)
            lengths = np.full(b, s_t, np.int32)
            cases.append((nbytes, s_t, s_r, b, codec, data, lengths))

    # every program once (its compile), four at a time; the results
    # held to the host's bytes
    def warm(case) -> dict:
        nbytes, s_t, s_r, b, codec, data, lengths = case
        t0 = time.perf_counter()
        parity, digests = device_encode(codec, data, lengths)
        secs = time.perf_counter() - t0
        host = Codec(K, M, s_t * K)
        rows = np.ascontiguousarray(data[:, :, :s_t])
        want = host.encode_parity_batch(rows, force="native")
        assert np.array_equal(parity[:, :, :s_t], want), (s_t, b)
        assert np.array_equal(
            digests[:, :K],
            bitrot.hash_shards_batch(rows.reshape(b * K, -1), ALGO)
            .reshape(b, K, -1)), (s_t, b)
        return {"S": s_r, "B": b, "first_call_s": secs}
    seen, first = set(), []
    for c in cases:
        if (c[2], ladder.rung("encode", c[3])) not in seen:
            seen.add((c[2], ladder.rung("encode", c[3])))
            first.append(c)
    with ThreadPoolExecutor(max_workers=4) as pool:
        report["compile"] = list(pool.map(warm, first))
    for c in cases:
        warm(c)

    for state in ("quiet", "gil"):
        load = GilLoad() if state == "gil" else None
        try:
            for nbytes, s_t, s_r, b, codec, data, lengths in cases:
                h_wall = timed(lambda: host_encode(codec, data, s_t), reps)
                stages: dict = {}
                d_wall = timed(
                    lambda: device_encode(codec, data, lengths, stages),
                    reps)
                report["rows"].append({
                    "state": state, "object_bytes": nbytes, "S_t": s_t,
                    "S": s_r, "B": b, "host_ms": h_wall,
                    "device_ms": d_wall,
                    **{f"{st}_ms": 1e3 * _med(v)
                       for st, v in stages.items()}})
        finally:
            if load is not None:
                load.close()
    return report


def show(report: dict) -> None:
    print(f"device {report['device']}  jax {report['jax']}  "
          f"{report['k']}+{report['m']}  S rungs {report['s_rungs']}  "
          f"medians of {report['reps']}")
    for c in report["compile"]:
        print(f"  first call S={c['S']:6} B={c['B']}: "
              f"{c['first_call_s']:.2f} s")
    print(f"  {'state':5} {'bytes':>8} {'S_t':>6} {'S':>6} B  "
          f"{'host ms':>8}  {'device ms':>9}  h2d / compute / fetch")
    for r in report["rows"]:
        print(f"  {r['state']:5} {r['object_bytes']:8} {r['S_t']:6} "
              f"{r['S']:6} {r['B']}  {r['host_ms']:8.3f}  "
              f"{r['device_ms']:9.3f}  {r.get('h2d_ms', 0):.3f} / "
              f"{r.get('compute_ms', 0):.3f} / {r.get('fetch_ms', 0):.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes for an XLA-CPU rehearsal")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--out", default=".bench_out/subblock_crossover.json")
    args = ap.parse_args()
    if args.tiny:
        report = run([n // 64 for n in OBJECT_BYTES], BLOCK // 64, 3)
    else:
        if jax.devices()[0].platform != "tpu":
            print("subblock_crossover: no TPU; a time comes only from a "
                  "chip (--tiny rehearses the control flow)",
                  file=sys.stderr)
            return 3
        device.probe()      # the compile cache where the node keeps it
        report = run(OBJECT_BYTES, BLOCK, args.reps)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    show(report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
