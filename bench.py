#!/usr/bin/env python
"""Benchmark of record: erasure encode+bitrot throughput per chip.

Measures the BASELINE.json metric — aggregate erasure encode + bitrot
GiB/s per chip on an EC 12+4 set at 1 MiB blocks (the PutObject hot-loop
device work: RS parity + per-shard HighwayHash256 streaming-bitrot
digests, one fused program) — and compares against the host-CPU SIMD
reedsolomon+highwayhash baseline (the reference's data path, natively
reimplemented in native/gf_rs.cpp + native/highwayhash.cpp since the Go
toolchain isn't present).

Prints ONE json line:
  {"metric": ..., "value": N, "unit": "GiB/s", "vs_baseline": N, ...}

Timing methodology: every kernel (put, fused verify+decode, fused
verify+heal, config #5 multipart 16+4/SHA256) runs on device-resident
input; a sample is the wall time of one call closed by
`jax.block_until_ready`, after a warm-up call that pays the compile.
Kernels are sampled round-robin (put, decode, heal, mp, put, ...) so
their ratios come from adjacent samples; per kernel the JSON carries
{median_ms, iqr_ms, n}. The output names the device it ran on
(platform, device_kind, count) and the run FAILS when that platform is
not a TPU — a CPU run is never reported under the device metric's name.
Shard and digest byte-identity against the host oracle is asserted
before any timing. Kernel time proper (device-side duration, without
the launch and sync a host clock sees) comes from a profiler trace —
the benchmark PR's job (ROADMAP A1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional, Sequence

import numpy as np

K, M = 12, 4
N_SHARDS = K + M
BLOCK = 1 << 20                      # 1 MiB blocks (BASELINE config)
S = -(-BLOCK // K)                   # shard bytes per block
BATCH = 32                           # concurrent PutObject streams
REPS = 30                            # timed calls per kernel


def _median(xs: list) -> float:
    return float(np.median(np.asarray(xs)))


def _iqr(xs: list) -> float:
    a = np.asarray(xs)
    return float(np.percentile(a, 75) - np.percentile(a, 25))


def bench_device() -> tuple[float, dict]:
    import jax
    from minio_tpu import bitrot as bitrot_mod
    from minio_tpu.models.pipeline import (get_step, heal_step,
                                           host_rows, put_step)
    from minio_tpu.ops import gf256, rs_matrix, rs_ref, rs_tpu
    from minio_tpu.utils import device

    dp = device.probe()
    if not dp.is_tpu:
        raise RuntimeError(
            "bench_device times device kernels and found no TPU "
            f"(platform {dp.platform!r}: {dp.reason}); a CPU run is "
            "never reported under a device metric's name")

    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (BATCH, K, S)).astype(np.uint8)
    dd = jax.device_put(data)

    # ---- identity gates (shards AND digests vs the host oracle) ------
    hh = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256
    parity, digests = put_step(dd[:1], K, M)
    parity = host_rows(np.asarray(parity), S)[0]
    digests = np.asarray(digests)[0]
    want = rs_ref.encode(data[0], M)
    assert (parity == want[K:]).all(), "device encode diverges from oracle"
    for row in (0, K, N_SHARDS - 1):
        want_dg = bitrot_mod.hash_shard(want[row], hh)
        assert digests[row].tobytes() == want_dg, \
            f"device digest diverges from oracle (shard {row})"

    # fused decode (3 shards missing) / heal (4 lost rows) operands
    ops = {"put": lambda d: put_step(d, K, M)}
    for mode, lost in (("decode", (1, 5, 13)), ("heal", (0, 4, 8, 12))):
        mask = sum(1 << i for i in range(N_SHARDS) if i not in lost)
        if mode == "decode":
            mat, _u, _miss = rs_matrix.missing_data_matrix(K, M, mask)
        else:
            mat, _u, _miss = rs_matrix.recover_matrix(K, M, mask)
        mat = np.ascontiguousarray(np.asarray(mat, np.uint8))
        m2 = rs_tpu._bit_expand_cached(mat.tobytes(), mat.shape)
        r = mat.shape[0]
        step = get_step if mode == "decode" else heal_step
        ops[mode] = (lambda step, m2, r: lambda x: step(x, m2, r, K, S)
                     )(step, m2, r)
        got = [host_rows(np.asarray(o), S) for o in ops[mode](dd[:1])]
        want_rows = gf256.gf_matmul(mat, data[0])
        assert (got[0][0] == want_rows).all(), f"device {mode} diverges"
        want_dg = bitrot_mod.hash_shard(data[0][0].tobytes(), hh)
        assert got[1][0, 0].tobytes() == want_dg, \
            f"device {mode} survivor digest diverges"
        if mode == "heal":
            want_odg = bitrot_mod.hash_shard(want_rows[0].tobytes(), hh)
            assert got[2][0, 0].tobytes() == want_odg, \
                "device heal output digest diverges"

    # config #5: multipart 16+4, SHA256 bitrot, own geometry/batch
    k5, m5 = 16, 4
    s5 = -(-BLOCK // k5)
    data5 = np.random.default_rng(7).integers(
        0, 256, (BATCH, k5, s5)).astype(np.uint8)
    dd5 = jax.device_put(data5)
    p5, dg5 = put_step(dd5[:1], k5, m5, 0, b"", "sha256")
    p5, dg5 = host_rows(np.asarray(p5), s5)[0], np.asarray(dg5)[0]
    want5 = rs_ref.encode(data5[0], m5)
    assert (p5 == want5[k5:]).all(), "config5 encode diverges"
    import hashlib
    for row in (0, k5, k5 + m5 - 1):
        assert dg5[row].tobytes() == hashlib.sha256(
            want5[row].tobytes()).digest(), "config5 digest diverges"
    ops["mp_16p4_sha256"] = lambda d: put_step(d, k5, m5, 0, b"",
                                               "sha256")

    # ---- warm every kernel (compile), then sample round-robin --------
    inputs = {name: dd5 if name.startswith("mp_") else dd for name in ops}
    for name, op in ops.items():
        jax.block_until_ready(op(inputs[name]))
    samples: dict[str, list[float]] = {name: [] for name in ops}
    for _rep in range(REPS):
        for name, op in ops.items():
            t0 = time.perf_counter()
            jax.block_until_ready(op(inputs[name]))
            samples[name].append(time.perf_counter() - t0)

    stats = {name: {"median_ms": round(_median(xs) * 1e3, 3),
                    "iqr_ms": round(_iqr(xs) * 1e3, 3), "n": len(xs)}
             for name, xs in samples.items()}
    # per-kernel ratios vs put, from adjacent same-round samples
    for name in ops:
        if name != "put":
            rs = [p / x for p, x in zip(samples["put"], samples[name])]
            stats[name]["vs_put_median"] = round(_median(rs), 3)
            stats[name]["vs_put_iqr"] = round(_iqr(rs), 3)

    med = _median(samples["put"])
    gib = BATCH * K * S / med / 2**30
    bytes5 = BATCH * k5 * s5
    info = {
        "platform": dp.platform, "device_kind": dp.device_kind,
        "device_count": dp.count,
        "kernel": "pallas+hh256",
        "reps": REPS,
        "kernels_ms": stats,
        "decode_3miss_gibs": round(
            BATCH * K * S / _median(samples["decode"]) / 2**30, 2),
        "heal_4miss_gibs": round(
            BATCH * K * S / _median(samples["heal"]) / 2**30, 2),
        "config5_multipart_16p4_sha256_gibs": round(
            bytes5 / _median(samples["mp_16p4_sha256"]) / 2**30, 2),
        "note": "decode/heal are FUSED verify+reconstruct (HighwayHash256 "
                "verification of all survivors in-program; heal also "
                "digests rebuilt shards); each sample is one call on "
                "device-resident input closed by block_until_ready, "
                f"kernels sampled round-robin, medians + IQR over {REPS} "
                "samples — host-clock step time (launch + sync "
                "included), not profiler kernel time",
    }
    return gib, info


def bench_cpu_baseline() -> tuple[float, dict]:
    """Reference-style CPU data path: SIMD GF(2^8) encode + HighwayHash256
    over every shard (the reference's per-PUT work), single core."""
    from minio_tpu import bitrot
    from minio_tpu.ops import rs_matrix
    from minio_tpu.utils import native

    if not native.available():
        return 0.0, {"error": "native lib unavailable"}

    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (K, S)).astype(np.uint8)
    pm = np.asarray(rs_matrix.parity_matrix(K, M))

    # per-block: encode (GFNI if present, matching "best SIMD on this CPU")
    # + HighwayHash-256 every one of the n shards (streaming bitrot)
    n_blocks = 24
    t0 = time.perf_counter()
    for _ in range(n_blocks):
        parity = native.gf_matmul(pm, data)
        full = np.concatenate([data, parity], axis=0)
        native.hh256_batch(bitrot.MAGIC_HIGHWAYHASH_KEY, full)
    dt = (time.perf_counter() - t0) / n_blocks
    gib = K * S / dt / 2**30
    # encode-only rate for reference
    t0 = time.perf_counter()
    for _ in range(n_blocks):
        native.gf_matmul(pm, data)
    dt_enc = (time.perf_counter() - t0) / n_blocks
    lib = native.get_lib()
    avx2 = False
    try:
        import ctypes
        lib.hh_has_avx2.restype = ctypes.c_int
        avx2 = bool(lib.hh_has_avx2())
    except Exception:
        pass
    return gib, {"gfni": native.has_gfni(), "hh_avx2": avx2,
                 "cpu_encode_only_gibs": round(K * S / dt_enc / 2**30, 3)}


def bench_saturation(streams: Sequence[int] = (1, 2, 4, 8, 16, 32),
                     size: int = 16 << 20, drives: int = 16,
                     parity: int = 4, block: int = 1 << 20,
                     lost_shards: int = 2, ab: bool = True,
                     force_device: Optional[bool] = None,
                     sched_max_wait: Optional[float] = None) -> dict:
    """Concurrency saturation sweep (ROADMAP item #1's measurement
    mode): for each stream count, run `streams` concurrent PutObject
    streams then concurrent healthy GETs then concurrent DEGRADED GETs
    (`lost_shards` shard files removed per object, so every read group
    rides the fused verify+decode verb), reporting aggregate GiB/s per
    phase plus the batch former's per-verb dispatch occupancy (groups
    and blocks per fused device launch) at that point.

    With `ab`, each point re-runs the GET phases with the scheduler
    BYPASSED (engines built with scheduler=None → one device dispatch
    per request bucket) — the per-request-launch baseline the former is
    supposed to beat once concurrency saturates a single dispatch.

    force_device: route every batch to the device backend regardless of
    size/platform (the engine-test fixture's trick) — default on when
    the jax backend is NOT a TPU, so the former is exercised (XLA-CPU)
    on dev hosts; on a real TPU the natural routing thresholds apply.
    Caveat: forced XLA-CPU numbers are compile-dominated (coalesced
    batches hit fresh jit shapes mid-phase) — occupancy stats are
    meaningful everywhere, the GiB/s and A/B ratios only on a real
    device where the per-dispatch constant the former amortizes
    actually exists.
    """
    import concurrent.futures as cf
    import glob
    import shutil
    import tempfile

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.parallel.scheduler import BatchScheduler
    from minio_tpu.utils import telemetry

    def stage_snap() -> dict:
        return dict(telemetry.REGISTRY.snapshot(
            "minio_tpu_device_dispatch_seconds").get(
            "minio_tpu_device_dispatch_seconds", {}))

    def stage_split(before: dict) -> dict:
        """Per-verb mean ms per dispatch stage since `before` — the
        queue/transfer/compute/fetch attribution of ISSUE 13 pillar c,
        read back from the registry histogram deltas."""
        split: dict = {}
        for lk, v in stage_snap().items():
            b = before.get(lk, {"sum": 0, "count": 0})
            dc = v["count"] - b["count"]
            if dc <= 0:
                continue
            labels = dict(p.split("=", 1) for p in lk.split(","))
            split.setdefault(labels.get("verb", "?"), {})[
                labels.get("stage", "?")] = {
                "mean_ms": round((v["sum"] - b["sum"]) / dc * 1e3, 3),
                "n": dc}
        return split

    if force_device is None:
        force_device = not codec_mod._device_is_tpu()
    was_is_tpu = codec_mod._device_is_tpu
    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    if force_device:
        codec_mod._device_is_tpu = lambda: True
        codec_mod.DEVICE_MIN_BYTES = 0
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    payload = os.urandom(size)
    out: dict = {"config": {"streams": list(streams), "size": size,
                            "k": drives - parity, "m": parity,
                            "block": block, "lost_shards": lost_shards,
                            "forced_device_route": bool(force_device)},
                 "points": []}

    def run_point(n_streams: int, use_sched: bool) -> dict:
        root = tempfile.mkdtemp(
            prefix=f"bench_sat_{n_streams}_", dir=base)
        # sched_max_wait widens the coalescing grace window past the
        # production default — the smoke's tiny 2-stream points need
        # determinism (3 ms loses to CI scheduling jitter), the real
        # sweep wants production behavior
        sched = (BatchScheduler(max_wait=sched_max_wait)
                 if sched_max_wait is not None else BatchScheduler()) \
            if use_sched else None
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False, scheduler=sched)
        res: dict = {}
        try:
            sets.make_bucket("bench")
            sets.put_object("bench", "warm", payload)   # warm the path

            def stat_delta(before: Optional[dict]) -> dict:
                if sched is None:
                    return {}
                now_ = sched.stats()["verbs"]
                if before is None:
                    return now_
                d = {}
                for verb, vs in now_.items():
                    b = vs["batches"] - before[verb]["batches"]
                    c = vs["coalesced"] - before[verb]["coalesced"]
                    blk = vs["blocks"] - before[verb]["blocks"]
                    if b:
                        d[verb] = {
                            "dispatches": b, "groups": b + c,
                            "occupancy_groups": round((b + c) / b, 3),
                            "occupancy_blocks": round(blk / b, 3)}
                return d

            def put_one(i: int) -> None:
                sets.put_object("bench", f"o{i}", payload)

            def get_one(i: int) -> None:
                _, it = sets.get_object("bench", f"o{i}")
                n = sum(len(c) for c in it)
                assert n == size, (i, n)

            snap = stat_delta(None)
            sstage = stage_snap() if sched is not None else {}
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(max_workers=n_streams) as ex:
                list(ex.map(put_one, range(n_streams)))
            put_wall = time.perf_counter() - t0
            res["put_gib_s"] = round(
                n_streams * size / put_wall / 2**30, 4)
            res["sched_put"] = stat_delta(snap)
            if sched is not None:
                res["stages_put"] = stage_split(sstage)

            get_one(0)                     # warm the GET path
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(max_workers=n_streams) as ex:
                list(ex.map(get_one, range(n_streams)))
            res["get_gib_s"] = round(
                n_streams * size / (time.perf_counter() - t0) / 2**30,
                4)

            # degrade every object: drop `lost_shards` shard files so
            # each read group needs the fused verify+decode verb. Loss
            # is aligned by SHARD INDEX (each object loses data shards
            # 0..lost-1), not by drive: the per-object distribution
            # shuffle maps one dead drive to a different shard index
            # per object, i.e. a different survivor mask per request —
            # buckets that can never fuse. Index-aligned loss gives
            # concurrent requests ONE shared erasure pattern, the
            # coalescible stream the former exists to fuse.
            eng = sets.sets[0]
            for i in range(n_streams):
                dist = eng._read_one("bench",
                                     f"o{i}").erasure.distribution
                for j in range(lost_shards):
                    for f in glob.glob(os.path.join(
                            root, f"d{dist.index(j + 1)}", "bench",
                            f"o{i}", "*", "part.1")):
                        os.remove(f)
            get_one(0)     # warm (compiles the fused decode program)
            snap = stat_delta(None)
            sstage = stage_snap() if sched is not None else {}
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(max_workers=n_streams) as ex:
                list(ex.map(get_one, range(n_streams)))
            res["deg_get_gib_s"] = round(
                n_streams * size / (time.perf_counter() - t0) / 2**30,
                4)
            res["sched_deg_get"] = stat_delta(snap)
            if sched is not None:
                res["stages_deg_get"] = stage_split(sstage)
        finally:
            sets.close()
            if sched is not None:
                sched.close()
            shutil.rmtree(root, ignore_errors=True)
        return res

    try:
        for s in streams:
            point: dict = {"streams": s}
            point.update(run_point(s, True))
            if ab:
                bypass = run_point(s, False)
                point["bypass"] = {
                    kk: bypass[kk] for kk in
                    ("put_gib_s", "get_gib_s", "deg_get_gib_s")}
                base_deg = bypass["deg_get_gib_s"]
                if base_deg:
                    point["deg_get_vs_bypass_x"] = round(
                        point["deg_get_gib_s"] / base_deg, 3)
            out["points"].append(point)
    finally:
        codec_mod._device_is_tpu = was_is_tpu
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
    return out


def bench_rebalance_ab(streams: int = 8, size: int = 4 << 20,
                       drives: int = 8, parity: int = 2,
                       preload: int = 32) -> dict:
    """Foreground-PUT latency with vs without an active pool drain
    (the rebalance-throttle acceptance probe): two pools on tmpfs,
    pool 0 preloaded, then identical concurrent PUT rounds are timed
    per-op before and during a live decommission of pool 0. Reports
    p50/p99 per phase and `put_p99_degradation_x` — the throttle keeps
    it under ~2x because the walker backs off whenever the foreground
    shows scheduler/staging pressure."""
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.server_sets import ErasureServerSets
    from minio_tpu.object.sets import ErasureSets

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_reb_", dir=base)
    payload = os.urandom(size)
    drain_payload = os.urandom(size // 2)
    out: dict = {"config": {"streams": streams, "size": size,
                            "drives_per_pool": drives, "m": parity,
                            "preload": preload}}
    try:
        zz = ErasureServerSets([ErasureSets.from_drives(
            [f"{root}/p{p}d{i}" for i in range(drives)], 1, drives,
            parity, block_size=1 << 20, enable_mrf=False)
            for p in (0, 1)])
        zz.make_bucket("bench")
        for i in range(preload):                # drain inventory
            zz.server_sets[0].put_object("bench", f"drain-{i}",
                                         drain_payload)

        def put_round(prefix: str) -> list[float]:
            lat: list[float] = []
            mu = threading.Lock()

            def one(i: int) -> None:
                t0 = time.perf_counter()
                # route directly to the ACTIVE pool's engine: the
                # foreground workload under test, not the zone probe
                zz.server_sets[1].put_object("bench", f"{prefix}{i}",
                                             payload)
                dt = time.perf_counter() - t0
                with mu:
                    lat.append(dt)

            with cf.ThreadPoolExecutor(max_workers=streams) as ex:
                list(ex.map(one, range(streams)))
            return lat

        def pcts(lat: list[float]) -> dict:
            xs = sorted(lat)
            return {"p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                    "p99_ms": round(xs[max(0, int(len(xs) * 0.99) - 1)]
                                    * 1e3, 2)}

        put_round("warm")                        # warm the path
        baseline = put_round("base") + put_round("base2")
        out["baseline"] = pcts(baseline)

        zz.start_decommission(0)        # the real admin code path
        reb = zz._rebalancer
        during = put_round("dr") + put_round("dr2")
        out["during_drain"] = pcts(during)
        out["drain_status_at_measure"] = {
            k: reb.status().get(k)
            for k in ("status", "objects_moved", "objects_failed")}
        deadline = time.monotonic() + 120
        while reb.running() and time.monotonic() < deadline:
            time.sleep(0.1)
        reb.stop()
        out["drain_final"] = {k: reb.status().get(k)
                              for k in ("status", "objects_moved",
                                        "objects_failed")}
        out["put_p99_degradation_x"] = round(
            out["during_drain"]["p99_ms"]
            / max(out["baseline"]["p99_ms"], 1e-9), 3)
        zz.close()
    finally:
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_tier_ab(streams: int = 8, size: int = 4 << 20,
                  drives: int = 8, parity: int = 2,
                  preload: int = 32) -> dict:
    """Foreground-PUT latency with vs without an active tier-transition
    drain (the tiering-throttle acceptance probe, the --ab-rebalance
    shape): one pool on tmpfs preloaded with transition inventory, then
    identical concurrent PUT rounds are timed per-op before and while
    the TransitionWorker moves that inventory to an fs tier. Reports
    p50/p99 per phase and `put_p99_degradation_x` — the shared
    foreground-pressure throttle keeps it bounded because the worker
    backs off whenever the foreground shows scheduler/staging
    pressure."""
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.tier.config import TierConfig, TierManager
    from minio_tpu.tier.transition import TransitionWorker

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_tier_", dir=base)
    payload = os.urandom(size)
    cold_payload = os.urandom(size // 2)
    out: dict = {"config": {"streams": streams, "size": size,
                            "drives": drives, "m": parity,
                            "preload": preload}}
    try:
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=1 << 20, enable_mrf=False)
        sets.make_bucket("bench")
        for i in range(preload):                # transition inventory
            sets.put_object("bench", f"cold-{i}", cold_payload)
        tiers = TierManager(sets)
        tiers.add(TierConfig("bench-cold", "fs",
                             {"path": f"{root}/tier"}))

        def put_round(prefix: str) -> list[float]:
            lat: list[float] = []
            mu = threading.Lock()

            def one(i: int) -> None:
                t0 = time.perf_counter()
                sets.put_object("bench", f"{prefix}{i}", payload)
                dt = time.perf_counter() - t0
                with mu:
                    lat.append(dt)

            with cf.ThreadPoolExecutor(max_workers=streams) as ex:
                list(ex.map(one, range(streams)))
            return lat

        def pcts(lat: list[float]) -> dict:
            xs = sorted(lat)
            return {"p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                    "p99_ms": round(xs[max(0, int(len(xs) * 0.99) - 1)]
                                    * 1e3, 2)}

        put_round("warm")                        # warm the path
        baseline = put_round("base") + put_round("base2")
        out["baseline"] = pcts(baseline)

        worker = TransitionWorker(sets, tiers).start()
        for i in range(preload):
            worker.enqueue("bench", f"cold-{i}", "", "bench-cold")
        during = put_round("dr") + put_round("dr2")
        out["during_drain"] = pcts(during)
        out["drain_status_at_measure"] = worker.stats()
        worker.drain(120)
        out["drain_final"] = worker.stats()
        out["put_p99_degradation_x"] = round(
            out["during_drain"]["p99_ms"]
            / max(out["baseline"]["p99_ms"], 1e-9), 3)
        worker.close()
        sets.close()
    finally:
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_replicate_ab(streams: int = 8, size: int = 4 << 20,
                       drives: int = 8, parity: int = 2,
                       preload: int = 48,
                       block: int = 1 << 20) -> dict:
    """Foreground-PUT latency with vs without an active replication
    resync drain (the --ab-rebalance/--ab-tier shape applied to the
    replication plane): two in-process sites on tmpfs, site A preloaded
    with resync inventory, identical concurrent PUT rounds timed per-op
    before and while the resync walker seeds site B. Reports p50/p99
    per phase, `put_p99_degradation_x` (the shared foreground-pressure
    throttle keeps it bounded), and the replication lag histogram of
    the steady-state pushes the foreground PUTs triggered."""
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.engine import PutOptions
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.object.server_sets import ErasureServerSets
    from minio_tpu.replicate import (LayerReplClient, ReplicationPlane,
                                     SiteTarget, TargetRegistry, new_arn)
    from minio_tpu.utils import telemetry

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_repl_", dir=base)
    payload = os.urandom(size)
    cold_payload = os.urandom(max(size // 2, 1 << 16))
    out: dict = {"config": {"streams": streams, "size": size,
                            "drives": drives, "m": parity,
                            "preload": preload}}
    try:
        def mk_site(name: str):
            sets = ErasureSets.from_drives(
                [f"{root}/{name}/d{i}" for i in range(drives)], 1,
                drives, parity, block_size=block, enable_mrf=False)
            layer = ErasureServerSets([sets], load_topology=False)
            layer.make_bucket("bench")
            return layer

        src = mk_site("a")
        dst = mk_site("b")
        reg = TargetRegistry(src, site_id="bench-a")
        plane = ReplicationPlane(src, reg)
        src.attach_replication(plane)
        for i in range(preload):                # resync inventory
            src.put_object("bench", f"cold-{i}", cold_payload,
                           opts=PutOptions(versioned=True))

        def put_round(prefix: str) -> list[float]:
            lat: list[float] = []
            mu = threading.Lock()

            def one(i: int) -> None:
                t0 = time.perf_counter()
                src.put_object("bench", f"{prefix}{i}", payload,
                               opts=PutOptions(versioned=True))
                dt = time.perf_counter() - t0
                with mu:
                    lat.append(dt)

            with cf.ThreadPoolExecutor(max_workers=streams) as ex:
                list(ex.map(one, range(streams)))
            return lat

        def pcts(lat: list[float]) -> dict:
            xs = sorted(lat)
            return {"p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                    "p99_ms": round(xs[max(0, int(len(xs) * 0.99) - 1)]
                                    * 1e3, 2)}

        put_round("warm")                        # warm the path
        baseline = put_round("base") + put_round("base2")
        out["baseline"] = pcts(baseline)

        # register the target + start the resync drain, then measure
        # foreground PUTs racing it (their own steady-state pushes ride
        # the plane concurrently)
        arn = new_arn("bench")
        reg.add(SiteTarget(arn=arn, bucket="bench", dest_bucket="bench",
                           site="bench-b", type="layer"),
                client=LayerReplClient(dst, "bench", "bench-b"))
        resync = plane.start_resync(arn, checkpoint_every=1000)
        during = put_round("dr") + put_round("dr2")
        out["during_resync"] = pcts(during)
        out["resync_status_at_measure"] = resync.status()
        for _ in range(600):
            if not resync.running():
                break
            time.sleep(0.1)
        plane.drain(120)
        out["resync_final"] = resync.status()
        out["plane_final"] = plane.stats()
        out["put_p99_degradation_x"] = round(
            out["during_resync"]["p99_ms"]
            / max(out["baseline"]["p99_ms"], 1e-9), 3)
        # replication lag histogram (steady-state pushes of the
        # foreground PUTs): bucketed counts straight off the registry
        hist = telemetry.REGISTRY.histogram("minio_tpu_repl_lag_seconds")
        series = None
        with hist._mu:
            for _k, s in hist._series.items():
                series = {"buckets_s": list(hist.buckets),
                          "counts": list(s.counts),
                          "count": s.count,
                          "mean_s": round(s.total / s.count, 4)
                          if s.count else 0.0}
        out["lag_histogram"] = series or {}
        plane.close()
        src.close()
        dst.close()
    finally:
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_notify_ab(streams: int = 8, size: int = 4 << 20,
                    drives: int = 8, parity: int = 2,
                    webhook_delay_s: float = 0.05,
                    block: int = 1 << 20) -> dict:
    """Foreground-PUT latency with vs without bucket event
    notifications against a SLOW webhook (the --ab-replicate shape
    applied to the notification plane): one in-process layer on tmpfs,
    identical concurrent PUT rounds timed per-op before and after a
    NotificationConfiguration wires every PUT to a webhook whose every
    POST stalls `webhook_delay_s`. The plane's bounded queue + worker
    pool + foreground-pressure throttle must keep the PUT hot path
    out of the webhook's latency: reports p50/p99 per phase,
    `put_p99_degradation_x` (the acceptance bound: a dead/slow webhook
    degrades PUT p99 by <= 5%), the plane's final counters after a
    full drain (zero events lost), and the delivery-lag histogram."""
    import concurrent.futures as cf
    import http.server
    import shutil
    import socket
    import tempfile
    import threading

    from minio_tpu.notify import (NotificationPlane, NotifyTarget,
                                  NotifyTargetRegistry, new_arn)
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.engine import PutOptions
    from minio_tpu.object.server_sets import ErasureServerSets
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.utils import telemetry

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_notify_", dir=base)
    payload = os.urandom(size)
    out: dict = {"config": {"streams": streams, "size": size,
                            "drives": drives, "m": parity,
                            "webhook_delay_s": webhook_delay_s}}
    received = [0]

    class _SlowHook(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            time.sleep(webhook_delay_s)
            received[0] += 1
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), _SlowHook)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False)
        layer = ErasureServerSets([sets], load_topology=False)
        layer.make_bucket("bench")
        reg = NotifyTargetRegistry(layer)
        arn = new_arn("bench", "webhook")
        reg.add(NotifyTarget(arn=arn, type="webhook",
                             params={"endpoint":
                                     f"http://127.0.0.1:{port}/",
                                     "timeout": 5.0}))
        plane = NotificationPlane(layer, reg,
                                  queue_dir=f"{root}/notifyq",
                                  node="bench")
        layer.attach_notifications(plane)

        def put_round(prefix: str) -> list[float]:
            lat: list[float] = []
            mu = threading.Lock()

            def one(i: int) -> None:
                t0 = time.perf_counter()
                layer.put_object("bench", f"{prefix}{i}", payload,
                                 opts=PutOptions(versioned=True))
                dt = time.perf_counter() - t0
                with mu:
                    lat.append(dt)

            with cf.ThreadPoolExecutor(max_workers=streams) as ex:
                list(ex.map(one, range(streams)))
            return lat

        def pcts(lat: list[float]) -> dict:
            xs = sorted(lat)
            return {"p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                    "p99_ms": round(xs[max(0, int(len(xs) * 0.99) - 1)]
                                    * 1e3, 2)}

        put_round("warm")                        # warm the path
        baseline = put_round("base") + put_round("base2")
        out["baseline"] = pcts(baseline)

        # wire every creation to the slow webhook, then measure the
        # foreground PUTs racing their own event deliveries
        plane.set_config(
            "bench",
            "<NotificationConfiguration><QueueConfiguration>"
            f"<Queue>{arn}</Queue>"
            "<Event>s3:ObjectCreated:*</Event>"
            "</QueueConfiguration></NotificationConfiguration>")
        during = put_round("dr") + put_round("dr2")
        out["during_notify"] = pcts(during)
        out["plane_at_measure"] = plane.stats()
        assert plane.drain(180), plane.stats()   # zero loss: all land
        out["plane_final"] = plane.stats()
        out["webhook_received"] = received[0]
        out["put_p99_degradation_x"] = round(
            out["during_notify"]["p99_ms"]
            / max(out["baseline"]["p99_ms"], 1e-9), 3)
        # delivery-lag histogram: bucketed counts off the registry
        hist = telemetry.REGISTRY.histogram(
            "minio_tpu_notify_lag_seconds")
        series = None
        with hist._mu:
            for _k, s in hist._series.items():
                series = {"buckets_s": list(hist.buckets),
                          "counts": list(s.counts),
                          "count": s.count,
                          "mean_s": round(s.total / s.count, 4)
                          if s.count else 0.0}
        out["lag_histogram"] = series or {}
        plane.close()
        layer.close()
    finally:
        srv.shutdown()
        srv.server_close()
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_list_ab(keys: int = 10000, drives: int = 8, parity: int = 2,
                  page: int = 1000, versions_every: int = 20,
                  payload_bytes: int = 16) -> dict:
    """Listing A/B: merge-walk vs persisted bucket metacache.

    One pool on tmpfs seeded with `keys` small objects (a nested
    prefix every 4th key, an extra version every `versions_every`-th),
    then per mode:

      * page the whole namespace (max_keys=`page`) and report per-page
        p50/p99 — the walk mode re-runs the heap merge + per-name
        quorum metadata read every page, the index mode slices memory;
      * run one "crawler cycle" (DataUsageCrawler.scan_once plus the
        noncurrent version-group walks the lifecycle sweep and the
        tier transition action run) and report wall time + the
        namespace-walk counter delta — with the index attached the
        cycle performs ZERO merge walks: the one amortized walk
        happened at build time (reported separately as build_s).

    The index-served pages are asserted name-identical to the
    merge-walk pages before timing (the oracle discipline the erasure
    kernels use)."""
    import shutil
    import tempfile

    from minio_tpu.features.lifecycle import iter_version_groups
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.background import DataUsageCrawler
    from minio_tpu.object.engine import PutOptions
    from minio_tpu.object.metacache import MetacacheManager, walks_counter
    from minio_tpu.object.server_sets import ErasureServerSets
    from minio_tpu.object.sets import ErasureSets

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_list_", dir=base)
    payload = os.urandom(payload_bytes)
    out: dict = {"config": {"keys": keys, "drives": drives, "m": parity,
                            "page": page,
                            "versions_every": versions_every}}

    def walk_totals() -> dict:
        c = walks_counter()
        with c._mu:
            items = dict(c._series)
        tot = {"merge": 0.0, "index": 0.0}
        for key, v in items.items():
            src = dict(key).get("source", "merge")
            tot[src] = tot.get(src, 0.0) + v
        return tot

    def pcts(lat: list) -> dict:
        xs = sorted(lat)
        return {"p50_ms": round(xs[len(xs) // 2] * 1e3, 3),
                "p99_ms": round(xs[max(0, int(len(xs) * 0.99) - 1)]
                                * 1e3, 3)}

    try:
        zz = ErasureServerSets([ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=1 << 18, enable_mrf=False)],
            load_topology=False)
        zz.make_bucket("bench")
        t0 = time.perf_counter()
        for i in range(keys):
            name = f"dir{i % 4}/obj-{i:07d}" if i % 4 else f"obj-{i:07d}"
            zz.put_object("bench", name, payload)
            if versions_every and i % versions_every == 0:
                zz.put_object("bench", name, payload,
                              opts=PutOptions(versioned=True))
        out["seed_s"] = round(time.perf_counter() - t0, 2)

        def page_walk() -> tuple[list, list]:
            lats, names, marker = [], [], ""
            while True:
                t0 = time.perf_counter()
                objs, _pfx, trunc = zz.list_objects("bench", "", marker,
                                                    "", page)
                lats.append(time.perf_counter() - t0)
                names.extend(o.name for o in objs)
                if not trunc or not objs:
                    return lats, names
                marker = objs[-1].name

        crawler = DataUsageCrawler(zz, interval=1e9, persist=False)

        def cycle() -> dict:
            before = walk_totals()
            t0 = time.perf_counter()
            crawler.scan_once()
            for _ in iter_version_groups(zz, "bench",
                                         consumer="lifecycle"):
                pass
            for _ in iter_version_groups(zz, "bench",
                                         consumer="transition"):
                pass
            wall = time.perf_counter() - t0
            after = walk_totals()
            return {"wall_s": round(wall, 3),
                    "merge_walks": round(after["merge"]
                                         - before["merge"], 1),
                    "index_reads": round(after["index"]
                                         - before["index"], 1)}

        # -- phase A: merge-walk (no index attached) -----------------------
        walk_lats, walk_names = page_walk()
        out["walk"] = dict(pcts(walk_lats), pages=len(walk_lats),
                           cycle=cycle())

        # -- phase B: metacache index --------------------------------------
        mgr = MetacacheManager(zz, flush_s=0.05).start()
        zz.attach_metacache(mgr)
        t0 = time.perf_counter()
        assert mgr.build("bench")
        out["build_s"] = round(time.perf_counter() - t0, 2)
        idx_lats, idx_names = page_walk()
        if idx_names != walk_names:     # oracle: identical pages
            raise AssertionError(
                f"index pages diverged from merge-walk: "
                f"{len(idx_names)} vs {len(walk_names)} names")
        out["index"] = dict(pcts(idx_lats), pages=len(idx_lats),
                            cycle=cycle(),
                            metacache=mgr.stats())
        out["index"]["metacache"].pop("buckets", None)
        out["page_p50_speedup_x"] = round(
            out["walk"]["p50_ms"] / max(out["index"]["p50_ms"], 1e-9), 2)
        out["cycle_speedup_x"] = round(
            out["walk"]["cycle"]["wall_s"]
            / max(out["index"]["cycle"]["wall_s"], 1e-9), 2)
    finally:
        try:
            # stop the metacache daemon BEFORE its backing tree is
            # deleted, even when a phase raised
            zz.close()
        except Exception:  # noqa: BLE001 — includes zz never assigned
            pass
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_select_ab(streams: Sequence[int] = (1, 2, 4, 8),
                    rows: int = 20000, queries_per_stream: int = 4,
                    sched_max_wait: float = 0.25) -> dict:
    """S3 Select A/B: device scan plane vs the CPU row-by-row
    evaluator at 1..N concurrent SelectObjectContent requests.

    One CSV corpus (`rows` records, mixed numeric/string cells), one
    predicate-heavy query. Per concurrency point, each of n threads
    runs `queries_per_stream` Selects:

      * cpu   — s3select.select.event_stream (the oracle),
      * device — ScanEngine riding a shared BatchScheduler with the
        kernels FORCED onto the local XLA backend; the scheduler's
        scan-verb batches/coalesced counter deltas per point prove
        concurrent requests coalesce into shared launches.

    Device output is asserted byte-identical to the CPU stream before
    any timing (the erasure kernels' oracle discipline)."""
    import io
    import csv as _csv
    import random as _random
    import threading

    from minio_tpu.parallel.scheduler import BatchScheduler
    from minio_tpu.s3select.select import SelectRequest, event_stream
    from minio_tpu.scan import ScanEngine

    rng = _random.Random(20240803)
    buf = io.StringIO()
    w = _csv.writer(buf)
    w.writerow(("a", "b", "c", "d"))
    words = ("x", "zz", "abc", "Par", "x y", "")
    for i in range(rows):
        w.writerow((rng.randint(-50, 50), round(rng.uniform(0, 9), 3),
                    rng.choice(words), i % 7))
    data = buf.getvalue().encode()

    req = SelectRequest()
    req.expression = ("SELECT a, b, c FROM S3Object WHERE "
                      "(a >= 0 AND b < 4.5) OR c LIKE 'x%' "
                      "OR d BETWEEN 2 AND 3")
    req.csv_header = "USE"

    was_mode = os.environ.get("MINIO_TPU_SCAN_DEVICE")
    os.environ["MINIO_TPU_SCAN_DEVICE"] = "force"
    out: dict = {"config": {"rows": rows, "streams": list(streams),
                            "queries_per_stream": queries_per_stream,
                            "expression": req.expression},
                 "points": []}
    sched = BatchScheduler(max_wait=sched_max_wait)
    try:
        oracle = b"".join(event_stream(req, data))
        out["config"]["response_bytes"] = len(oracle)
        eng = ScanEngine(sched)
        # byte-identity + jit warm BEFORE timing
        if b"".join(eng.event_stream(req, data)) != oracle:
            raise AssertionError("device Select diverged from the "
                                 "CPU evaluator")
        if eng.device_serves != 1:
            raise AssertionError(
                f"device path declined: {eng.fallback_reasons}")

        def run_point(n: int, device: bool) -> dict:
            engine = ScanEngine(sched) if device else None
            lats: list[float] = []
            errs: list[BaseException] = []
            mu = threading.Lock()
            barrier = threading.Barrier(n)

            def one() -> None:
                try:
                    barrier.wait()
                    for _ in range(queries_per_stream):
                        t0 = time.perf_counter()
                        if device:
                            body = b"".join(
                                engine.event_stream(req, data))
                        else:
                            body = b"".join(event_stream(req, data))
                        dt = time.perf_counter() - t0
                        if body != oracle:
                            raise AssertionError(
                                "device Select diverged from the CPU "
                                "evaluator under concurrency")
                        with mu:
                            lats.append(dt)
                except BaseException as e:  # noqa: BLE001 — re-raised
                    with mu:                # on the main thread below
                        errs.append(e)

            ts = [threading.Thread(target=one) for _ in range(n)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            if errs:
                raise errs[0]
            nq = n * queries_per_stream
            xs = sorted(lats)
            point = {
                "queries": nq,
                "wall_s": round(wall, 3),
                "queries_per_s": round(nq / wall, 2),
                "scanned_mb_s": round(nq * len(data) / wall / 1e6, 1),
                "p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                "p99_ms": round(xs[max(0, int(len(xs) * .99) - 1)]
                                * 1e3, 2),
            }
            if device:
                point["device_serves"] = engine.device_serves
                point["fallbacks"] = engine.fallbacks
            return point

        for n in streams:
            before = dict(sched.verb_stats["scan"])
            dev = run_point(n, device=True)
            vs = sched.verb_stats["scan"]
            dev["sched_batches"] = vs["batches"] - before["batches"]
            dev["sched_coalesced"] = (vs["coalesced"]
                                      - before["coalesced"])
            cpu = run_point(n, device=False)
            out["points"].append({
                "streams": n, "device": dev, "cpu": cpu,
                "speedup_x": round(cpu["wall_s"]
                                   / max(dev["wall_s"], 1e-9), 2)})
    finally:
        sched.close()
        if was_mode is None:
            os.environ.pop("MINIO_TPU_SCAN_DEVICE", None)
        else:
            os.environ["MINIO_TPU_SCAN_DEVICE"] = was_mode
    out["max_speedup_x"] = max(p["speedup_x"] for p in out["points"])
    return out


def bench_cache_ab(objects: int = 16, size: int = 4 << 20,
                   gets: int = 200, streams: int = 4,
                   drives: int = 6, parity: int = 2,
                   block: int = 1 << 18) -> dict:
    """Hot-GET A/B: erasure read path with the hot-object read cache
    off vs on.

    One pool on tmpfs seeded with `objects` objects; `gets` reads from
    `streams` threads over a hot subset (80/20-ish zipf pick). The
    cache-on pass wires CacheObjects the way cluster boot does
    (attach_read_cache + wrapper serving GETs) with a 1-hit admission
    bar so the second touch of every hot key serves from the cache
    WITHOUT the shard-read/verify/decode path — proven by the
    minio_tpu_erasure_get_streams_total counter delta, not just
    latency. Bytes are asserted identical to the backend read."""
    import random as _random
    import shutil
    import tempfile
    import threading

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.cache import CacheObjects
    from minio_tpu.object.server_sets import ErasureServerSets
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.utils import telemetry

    def decode_streams() -> float:
        return telemetry.REGISTRY.counter(
            "minio_tpu_erasure_get_streams_total",
            "Object read streams served through the erasure "
            "shard-read/verify/decode path").value()

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_cache_", dir=base)
    out: dict = {"config": {"objects": objects, "size": size,
                            "gets": gets, "streams": streams,
                            "drives": drives, "m": parity}}
    rng = _random.Random(4096)
    # 80% of reads land on the hottest 20% of keys
    hot = max(1, objects // 5)
    picks = [rng.randrange(hot) if rng.random() < 0.8
             else rng.randrange(objects) for _ in range(gets)]
    try:
        zz = ErasureServerSets([ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False)], load_topology=False)
        zz.make_bucket("bench")
        payloads = []
        for i in range(objects):
            payloads.append(os.urandom(size))
            zz.put_object("bench", f"o-{i:04d}", payloads[i])

        def run_pass(layer) -> dict:
            lats: list[float] = []
            mu = threading.Lock()
            chunks = [picks[i::streams] for i in range(streams)]
            barrier = threading.Barrier(streams)

            def one(mine: list) -> None:
                barrier.wait()
                for idx in mine:
                    t0 = time.perf_counter()
                    _info, s = layer.get_object("bench", f"o-{idx:04d}")
                    body = b"".join(s)
                    dt = time.perf_counter() - t0
                    assert body == payloads[idx]
                    with mu:
                        lats.append(dt)

            before = decode_streams()
            ts = [threading.Thread(target=one, args=(c,))
                  for c in chunks if c]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - t0
            xs = sorted(lats)
            return {
                "wall_s": round(wall, 3),
                "get_gib_s": round(len(lats) * size / wall / (1 << 30),
                                   3),
                "p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                "p99_ms": round(xs[max(0, int(len(xs) * .99) - 1)]
                                * 1e3, 2),
                "decode_streams": round(decode_streams() - before, 1),
            }

        out["off"] = run_pass(zz)

        cache = CacheObjects(zz, os.path.join(root, "cache"),
                             budget_bytes=2 * objects * size,
                             admit_hits=1)
        zz.attach_read_cache(cache)
        out["on"] = run_pass(cache)
        out["on"]["cache"] = {k: cache.stats()[k] for k in
                              ("hits", "misses", "fills", "evictions")}
        out["speedup_x"] = round(out["off"]["wall_s"]
                                 / max(out["on"]["wall_s"], 1e-9), 2)
        out["decode_streams_saved"] = round(
            out["off"]["decode_streams"] - out["on"]["decode_streams"],
            1)
    finally:
        try:
            zz.close()
        except Exception:  # noqa: BLE001 — includes zz never assigned
            pass
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_sse_ab(streams=(1, 2, 4), size: int = 4 << 20,
                 objects: int = 3, drives: int = 6, parity: int = 2,
                 block: int = 1 << 17) -> dict:
    """Encrypted data-path A/B: device-fused cipher+RS+digest PUT (one
    launch per batch, ops/chacha20_jax inside the batch former) and the
    fused verify+decipher GET, vs the CPU ChaCha20 fallback.

    Each pass runs every concurrency point: N writers PUT `objects`
    objects each under DIFFERENT object keys — cross-request coalescing
    of encrypted batches is exactly what the geometry-keyed scheduler
    bucket buys — then read everything back through the
    verify-then-decrypt seam and byte-check against the plaintext.
    The device pass pins the fused route (TPU flag + DEVICE_MIN_BYTES=0;
    on a CPU-only host the same XLA programs run on the host backend, so
    the A/B measures program fusion + batching, not silicon) and reports
    launch/coalescing counter deltas plus the queue/transfer/compute/
    fetch dispatch attribution the scheduler histograms collect."""
    import shutil
    import tempfile
    import threading

    from minio_tpu.features import crypto as sse
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object import engine as engine_mod
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.parallel.scheduler import BatchScheduler
    from minio_tpu.utils import telemetry

    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    out: dict = {"config": {"streams": list(streams), "size": size,
                            "objects": objects, "drives": drives,
                            "m": parity, "block": block},
                 "cpu": [], "device": []}
    was_tpu = codec_mod._device_is_tpu
    was_min = codec_mod.DEVICE_MIN_BYTES
    was_attrib = os.environ.get("MINIO_TPU_SCHED_ATTRIB")
    was_win = os.environ.get("MINIO_TPU_SSE_DEVICE_MIN_BYTES")
    os.environ["MINIO_TPU_SCHED_ATTRIB"] = "1"
    os.environ["MINIO_TPU_SSE_DEVICE_MIN_BYTES"] = "0"
    pt = os.urandom(size)
    try:
        for mode in ("cpu", "device"):
            codec_mod._device_is_tpu = \
                (lambda m=mode: m == "device")
            codec_mod.DEVICE_MIN_BYTES = 0 if mode == "device" \
                else (1 << 60)
            for ns in streams:
                root = tempfile.mkdtemp(prefix="bench_sse_", dir=base)
                sched = BatchScheduler()
                sets_ = None
                try:
                    sets_ = ErasureSets.from_drives(
                        [f"{root}/d{i}" for i in range(drives)], 1,
                        drives, parity, block_size=block,
                        enable_mrf=False, scheduler=sched)
                    sets_.make_bucket("bench")
                    oeks = [os.urandom(32) for _ in range(ns)]
                    bases = [os.urandom(12) for _ in range(ns)]
                    # jit warmup outside the timed window
                    sets_.put_object(
                        "bench", "warm", pt,
                        opts=engine_mod.PutOptions(
                            sse_spec=sse.DeviceSSE(oeks[0], bases[0])))
                    b0, c0 = sched.batches, sched.coalesced
                    barrier = threading.Barrier(ns)
                    errs: list = []

                    def put_worker(t: int) -> None:
                        try:
                            barrier.wait()
                            for i in range(objects):
                                sets_.put_object(
                                    "bench", f"o-{t}-{i}", pt,
                                    opts=engine_mod.PutOptions(
                                        sse_spec=sse.DeviceSSE(
                                            oeks[t], bases[t])))
                        except Exception as exc:  # noqa: BLE001
                            errs.append(exc)

                    ts = [threading.Thread(target=put_worker, args=(t,))
                          for t in range(ns)]
                    t0 = time.perf_counter()
                    for th in ts:
                        th.start()
                    for th in ts:
                        th.join()
                    put_wall = time.perf_counter() - t0
                    if errs:
                        raise errs[0]

                    def get_worker(t: int) -> None:
                        try:
                            barrier.wait()
                            for i in range(objects):
                                name = f"o-{t}-{i}"

                                def fetch(off, ln, _n=name):
                                    _, it = sets_.get_object(
                                        "bench", _n, off, ln)
                                    return it

                                got = b"".join(sse.chacha_decrypt_ranged(
                                    fetch, sse.encrypted_size(size),
                                    oeks[t], bases[t], 0, size))[:size]
                                assert got == pt, "A/B byte mismatch"
                        except Exception as exc:  # noqa: BLE001
                            errs.append(exc)

                    ts = [threading.Thread(target=get_worker, args=(t,))
                          for t in range(ns)]
                    t0 = time.perf_counter()
                    for th in ts:
                        th.start()
                    for th in ts:
                        th.join()
                    get_wall = time.perf_counter() - t0
                    if errs:
                        raise errs[0]
                    nbytes = ns * objects * size
                    out[mode].append({
                        "streams": ns,
                        "put_gib_s": round(nbytes / put_wall / (1 << 30),
                                           4),
                        "get_gib_s": round(nbytes / get_wall / (1 << 30),
                                           4),
                        "launches": sched.batches - b0,
                        "coalesced": sched.coalesced - c0,
                    })
                finally:
                    if sets_ is not None:
                        sets_.close()
                    sched.close()
                    shutil.rmtree(root, ignore_errors=True)
        # compressed+encrypted at the max concurrency point: the
        # handler's exact transform chain — the snappy compressor
        # stays a host stage and its OUTPUT is the plaintext the
        # engine ciphers in-batch (fused or fallback per mode)
        from minio_tpu.features.snappy import (SnappyFramedCompress,
                                               decompress_stream)
        pt_c = (b"minio tpu sse device data path " * 97)[:4096]
        pt_c = pt_c * max(1, size // len(pt_c))
        ns = max(streams)
        for mode in ("cpu", "device"):
            codec_mod._device_is_tpu = \
                (lambda m=mode: m == "device")
            codec_mod.DEVICE_MIN_BYTES = 0 if mode == "device" \
                else (1 << 60)
            root = tempfile.mkdtemp(prefix="bench_sse_", dir=base)
            sched = BatchScheduler()
            sets_ = None
            try:
                sets_ = ErasureSets.from_drives(
                    [f"{root}/d{i}" for i in range(drives)], 1,
                    drives, parity, block_size=block,
                    enable_mrf=False, scheduler=sched)
                sets_.make_bucket("bench")
                oeks = [os.urandom(32) for _ in range(ns)]
                bases = [os.urandom(12) for _ in range(ns)]
                comp = SnappyFramedCompress()
                clen = len(comp.update(pt_c) + comp.finalize())
                barrier = threading.Barrier(ns)
                errs: list = []

                def cput(t: int) -> None:
                    try:
                        barrier.wait()
                        for i in range(objects):
                            c = SnappyFramedCompress()
                            body = c.update(pt_c) + c.finalize()
                            sets_.put_object(
                                "bench", f"c-{t}-{i}", body,
                                opts=engine_mod.PutOptions(
                                    sse_spec=sse.DeviceSSE(
                                        oeks[t], bases[t])))
                    except Exception as exc:  # noqa: BLE001
                        errs.append(exc)

                ts = [threading.Thread(target=cput, args=(t,))
                      for t in range(ns)]
                t0 = time.perf_counter()
                for th in ts:
                    th.start()
                for th in ts:
                    th.join()
                put_wall = time.perf_counter() - t0
                if errs:
                    raise errs[0]

                def cget(t: int) -> None:
                    try:
                        barrier.wait()
                        for i in range(objects):
                            name = f"c-{t}-{i}"

                            def fetch(off, ln, _n=name):
                                _, it = sets_.get_object(
                                    "bench", _n, off, ln)
                                return it

                            ct = sse.chacha_decrypt_ranged(
                                fetch, sse.encrypted_size(clen),
                                oeks[t], bases[t], 0, clen)
                            got = b"".join(decompress_stream(ct))
                            assert got == pt_c, "A/B byte mismatch"
                    except Exception as exc:  # noqa: BLE001
                        errs.append(exc)

                ts = [threading.Thread(target=cget, args=(t,))
                      for t in range(ns)]
                t0 = time.perf_counter()
                for th in ts:
                    th.start()
                for th in ts:
                    th.join()
                get_wall = time.perf_counter() - t0
                if errs:
                    raise errs[0]
                nbytes = ns * objects * len(pt_c)   # plaintext rate
                out[f"{mode}_compressed"] = {
                    "streams": ns, "ratio": round(len(pt_c) / clen, 2),
                    "put_gib_s": round(nbytes / put_wall / (1 << 30),
                                       4),
                    "get_gib_s": round(nbytes / get_wall / (1 << 30),
                                       4),
                }
            finally:
                if sets_ is not None:
                    sets_.close()
                sched.close()
                shutil.rmtree(root, ignore_errors=True)
        snap = telemetry.REGISTRY.snapshot(
            "minio_tpu_device_dispatch_seconds")
        out["dispatch_stage_seconds"] = snap.get(
            "minio_tpu_device_dispatch_seconds", {})
        last_cpu, last_dev = out["cpu"][-1], out["device"][-1]
        out["put_speedup_x"] = round(
            last_dev["put_gib_s"] / max(last_cpu["put_gib_s"], 1e-9), 2)
        out["get_speedup_x"] = round(
            last_dev["get_gib_s"] / max(last_cpu["get_gib_s"], 1e-9), 2)
    finally:
        codec_mod._device_is_tpu = was_tpu
        codec_mod.DEVICE_MIN_BYTES = was_min
        for k, v in (("MINIO_TPU_SCHED_ATTRIB", was_attrib),
                     ("MINIO_TPU_SSE_DEVICE_MIN_BYTES", was_win)):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def bench_gray_ab(objects: int = 16, size: int = 1 << 20,
                  gets: int = 60, streams: int = 4, drives: int = 6,
                  parity: int = 2, block: int = 1 << 17,
                  stall_s: float = 0.5) -> dict:
    """Gray-failure A/B: PUT/GET tail latency with ONE drive stalling
    `stall_s` per I/O, the gray-failure plane off vs on.

    OFF = MINIO_TPU_HEDGE/QUORUM_ACK/QUARANTINE all off: every PUT
    waits out the stalled drive's shard writes and any GET whose read
    plan includes it waits out the stalled shard read. ON = defaults
    (tightened floors so the adaptive deadlines bite at bench scale):
    hedged reads race the staller, PUTs ack at write quorum, and a
    DiskMonitor health scan walks the drive through suspect →
    probation → heal-verified re-admission once the stall clears.

    The bench asserts its own acceptance bar: zero acked-write loss
    after the MRF drain (every object byte-identical with the staller
    disarmed) and the full quarantine round trip."""
    import shutil
    import tempfile
    import threading

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.background import DiskMonitor
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.storage import XLStorage
    from minio_tpu.storage.naughty import NaughtyDisk
    from minio_tpu.utils import healthtrack

    READ_STALLS = ("read_file_stream", "read_file", "read_all")
    WRITE_STALLS = ("append_file", "create_file", "write_all",
                    "write_metadata", "rename_data")
    KNOBS_OFF = {"MINIO_TPU_HEDGE": "off", "MINIO_TPU_QUORUM_ACK": "off",
                 "MINIO_TPU_QUARANTINE": "off"}
    KNOBS_ON = {"MINIO_TPU_HEDGE": "on", "MINIO_TPU_QUORUM_ACK": "on",
                "MINIO_TPU_QUARANTINE": "on",
                # tightened floors/ceilings: the adaptive deadline must
                # bite below the injected stall even from a cold start
                "MINIO_TPU_HEDGE_FLOOR_S": "0.05",
                "MINIO_TPU_HEDGE_CEIL_S": str(stall_s / 4),
                "MINIO_TPU_WRITE_STALL_FLOOR_S": "0.1",
                "MINIO_TPU_WRITE_STALL_CEIL_S": str(stall_s / 2),
                "MINIO_TPU_QUAR_LATENCY_S": str(stall_s / 2.5),
                "MINIO_TPU_QUAR_MIN_SAMPLES": "4",
                "MINIO_TPU_QUAR_PROBATION_S": "0",
                "MINIO_TPU_QUAR_PROBES": "2"}

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    out: dict = {"config": {"objects": objects, "size": size,
                            "gets": gets, "streams": streams,
                            "drives": drives, "m": parity,
                            "stall_s": stall_s}}
    saved = {k: os.environ.get(k)
             for k in set(KNOBS_OFF) | set(KNOBS_ON)}
    roots: list = []

    def pctls(xs: list) -> dict:
        s = sorted(xs)
        return {"p50_ms": round(s[len(s) // 2] * 1e3, 2),
                "p99_ms": round(s[max(0, int(len(s) * .99) - 1)] * 1e3,
                                2)}

    def run_pass(env: dict) -> tuple[dict, "ErasureSets", NaughtyDisk,
                                     list]:
        for k, v in env.items():
            os.environ[k] = v
        healthtrack.TRACKER.reset()
        root = tempfile.mkdtemp(prefix="bench_gray_", dir=base)
        roots.append(root)
        raw = [XLStorage(f"{root}/d{j}") for j in range(drives)]
        nd = NaughtyDisk(raw[0], enabled=False)
        drv = [nd] + raw[1:]
        sets = ErasureSets.from_storage(
            drv, set_count=1, set_drive_count=drives, parity=parity,
            block_size=block,
            mrf_options=dict(max_retries=10, backoff_base=0.02,
                             backoff_max=0.25))
        sets.make_bucket("bench")
        payloads = [os.urandom(size) for _ in range(objects)]
        nd.stall_verbs = {v: stall_s
                          for v in READ_STALLS + WRITE_STALLS}
        nd.arm()

        put_lat: list[float] = []
        for i, body in enumerate(payloads):
            t0 = time.perf_counter()
            sets.put_object("bench", f"o-{i:04d}", body)
            put_lat.append(time.perf_counter() - t0)

        # the laggard-abandoned shards converge through MRF while the
        # drive is STILL slow (quarantined drives keep taking writes);
        # settle that background heal churn so the GET phase measures
        # steady state instead of heal-lock contention
        sets.drain_mrf(120.0)

        get_lat: list[float] = []
        worker_errs: list = []
        mu = threading.Lock()
        picks = [i % objects for i in range(gets)]
        chunks = [picks[i::streams] for i in range(streams)]
        barrier = threading.Barrier(sum(1 for c in chunks if c))

        def one(mine: list) -> None:
            barrier.wait()
            for idx in mine:
                t0 = time.perf_counter()
                _info, s = sets.get_object("bench", f"o-{idx:04d}")
                body = b"".join(s)
                dt = time.perf_counter() - t0
                if body != payloads[idx]:
                    raise AssertionError(f"o-{idx:04d} bytes differ")
                with mu:
                    get_lat.append(dt)

        def guarded(mine: list) -> None:
            # a worker's failure must FAIL the bench, not silently
            # shrink the sample set while the acceptance claims stand
            try:
                one(mine)
            except BaseException as e:  # noqa: BLE001 — re-raised
                with mu:
                    worker_errs.append(e)

        ts = [threading.Thread(target=guarded, args=(c,))
              for c in chunks if c]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if worker_errs:
            raise worker_errs[0]
        res = {"put": pctls(put_lat), "get": pctls(get_lat),
               "stalls_injected": nd.stats.stalls}
        return res, sets, nd, payloads

    try:
        out["off"], sets_off, nd_off, _ = run_pass(KNOBS_OFF)
        sets_off.close()

        out["on"], sets, nd, payloads = run_pass(KNOBS_ON)

        # quarantine round trip on the ON cluster: the scan convicts
        # the staller, probation probes fail while it still stalls,
        # pass once it recovers, and re-admission is heal-verified
        mon = DiskMonitor(sets, interval=3600)
        key = healthtrack.disk_key(nd)
        nd.stall_verbs["disk_info"] = stall_s
        mon.scan_once()
        states = [healthtrack.TRACKER.state_of("drive", key)]
        mon.scan_once()                 # probation probe: still slow
        states.append(healthtrack.TRACKER.state_of("drive", key))
        nd.stall_verbs = {}
        nd.disarm()                     # the gray spell ends
        for _ in range(4):
            mon.scan_once()
            states.append(healthtrack.TRACKER.state_of("drive", key))
            if states[-1] == healthtrack.STATE_OK:
                break
        out["quarantine"] = {"states": states,
                             "events": list(mon.quarantine_events)}
        assert states[0] == healthtrack.STATE_SUSPECT, states
        assert states[-1] == healthtrack.STATE_OK, states

        # zero acked-write loss: MRF converges every laggard-abandoned
        # shard, then every acked object reads back byte-identical
        assert sets.drain_mrf(60.0), "MRF did not drain"
        lost = 0
        for i, body in enumerate(payloads):
            _info, s = sets.get_object("bench", f"o-{i:04d}")
            if b"".join(s) != body:
                lost += 1
        out["mrf"] = sets.mrf_stats()
        out["lost_after_mrf"] = lost
        assert lost == 0, f"{lost} acked writes lost"
        sets.close()

        out["get_p99_speedup_x"] = round(
            out["off"]["get"]["p99_ms"]
            / max(out["on"]["get"]["p99_ms"], 1e-9), 2)
        out["put_p99_speedup_x"] = round(
            out["off"]["put"]["p99_ms"]
            / max(out["on"]["put"]["p99_ms"], 1e-9), 2)
        # PUT acks at quorum: the stalled drive no longer binds p99
        out["put_p99_below_stall"] = \
            out["on"]["put"]["p99_ms"] < stall_s * 1e3
    finally:
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for root in roots:
            shutil.rmtree(root, ignore_errors=True)
    return out


def bench_partition_ab(peers: int = 3, rounds: int = 20,
                       deadline: float = 1.0,
                       payload_kb: int = 32) -> dict:
    """Partition-tolerance A/B: cluster-wide metrics-scrape fan-out
    latency in three phases — baseline, one peer partitioned away,
    healed — over an in-process peer mesh driven by NaughtyNet.

    The acceptance bar (asserted here, not just reported): under the
    partition every fan-out stays bounded by the scrape DEADLINE (the
    cut peer fails at the injected dial, then sheds without dialing —
    never a TCP connect/read timeout), the reachable peers keep
    serving, and the healed mesh returns to the full merge at
    baseline-shaped latency."""
    import threading as _threading  # noqa: F401 — parity with siblings

    from minio_tpu.distributed import membership
    from minio_tpu.distributed.naughtynet import NET
    from minio_tpu.distributed.peer_rpc import (NotificationSys,
                                                PeerRPCClient,
                                                PeerRPCServer)
    from minio_tpu.distributed.transport import RPCServer

    ak, sk = "benchak", "benchsecret12345"
    exposition = "".join(
        f"# HELP bench_fake_{i} synthetic series\n"
        f"bench_fake_{i}{{peer=\"x\"}} {i}\n"
        for i in range(max(1, payload_kb * 1024 // 48)))

    def pctls(xs: list) -> dict:
        s = sorted(xs)
        return {"p50_ms": round(s[len(s) // 2] * 1e3, 2),
                "p99_ms": round(s[max(0, int(len(s) * .99) - 1)] * 1e3,
                                2)}

    out: dict = {"config": {"peers": peers, "rounds": rounds,
                            "deadline_s": deadline,
                            "payload_kb": payload_kb}}
    NET.reset()
    membership.TRACKER.reset()
    hosts, clients = [], []
    victim_id = ""
    try:
        for i in range(peers):
            host = RPCServer().start()
            nid = f"127.0.0.1:{host.port}"
            srv = PeerRPCServer(ak, sk, node_id=nid)
            srv.get_metrics_text = lambda: exposition
            host.mount(srv.handler)
            hosts.append(host)
            clients.append(PeerRPCClient("127.0.0.1", host.port, ak, sk,
                                         timeout=10.0,
                                         node_id="bench-observer"))
            if i == 0:
                victim_id = nid
        ns = NotificationSys(clients)

        def phase(n: int) -> tuple[list, int, int]:
            lat, ok, failed = [], 0, 0
            for _ in range(n):
                t0 = time.perf_counter()
                res = ns.metrics_text_all(deadline=deadline)
                lat.append(time.perf_counter() - t0)
                ok += sum(1 for _a, txt in res if txt is not None)
                failed += sum(1 for _a, txt in res if txt is None)
            return lat, ok, failed

        base_lat, base_ok, base_failed = phase(rounds)
        assert base_failed == 0, "baseline scrape must be complete"
        out["baseline"] = pctls(base_lat)

        NET.partition("bench-observer", victim_id, oneway=True)
        part_lat, part_ok, part_failed = phase(rounds)
        out["partitioned"] = pctls(part_lat)
        out["partitioned"]["scrapes_ok"] = part_ok
        out["partitioned"]["scrapes_failed"] = part_failed
        out["net_stats"] = dict(NET.stats)
        # the cut peer failed every round; the rest kept serving
        assert part_failed == rounds, \
            f"cut peer must fail every round ({part_failed}/{rounds})"
        assert part_ok == rounds * (peers - 1), \
            "reachable peers must keep serving under the partition"
        # bounded degradation: every degraded fan-out finished within
        # the scrape deadline (+ scheduling slack) — the failure is the
        # injected dial error + offline shed, never a TCP timeout
        worst = max(part_lat)
        assert worst < deadline + 1.0, \
            f"degraded fan-out took {worst:.2f}s — TCP-timeout " \
            "territory, not deadline-bounded"
        # after the first refused dial the peer is shed WITHOUT dialing
        assert NET.stats["blocked"] >= 1

        NET.heal()
        deadline_mono = time.monotonic() + 20.0
        while not clients[0].rc.online:
            if time.monotonic() > deadline_mono:
                raise AssertionError("victim never re-admitted post-heal")
            time.sleep(0.25)
        heal_lat, heal_ok, heal_failed = phase(rounds)
        assert heal_failed == 0, "healed mesh must restore the full merge"
        out["healed"] = pctls(heal_lat)
        out["partition_p99_bounded_by_deadline"] = \
            out["partitioned"]["p99_ms"] < deadline * 1e3 + 1000.0
        out["healed_vs_baseline_x"] = round(
            out["healed"]["p99_ms"]
            / max(out["baseline"]["p99_ms"], 1e-9), 2)
    finally:
        NET.reset()
        membership.TRACKER.reset()
        for c in clients:
            c.close()
        for h in hosts:
            h.stop()
    return out


def bench_edge_ab(streams=(4, 16), size: int = 1 << 20,
                  rounds: int = 4, idle_conns: int = 400,
                  idle_ratio: int = 20, drives: int = 6,
                  parity: int = 2, block: int = 1 << 18) -> dict:
    """HTTP frontend A/B: the event-loop edge vs the threaded oracle
    over ONE erasure layer (ISSUE 12 success metric).

    Phase 1 — idle keep-alive capacity: each server holds open
    keep-alive connections (edge: `idle_conns`, threaded:
    `idle_conns // idle_ratio` — thread-per-connection makes more
    unkind to the CI host), reporting RSS delta per connection and the
    thread-count delta (the edge's stays flat: sockets, not threads).
    The idle pool stays OPEN through phase 2, so the load runs against
    a mostly-idle connection population like production.

    Phase 2 — matched load: per streams point, signed HTTP PUT + GET
    rounds through persistent keep-alive connections; p50/p99 per op
    for both transports at identical load.

    Phase 3 — shed-before-body probe (edge): the admission gate is
    pinched to one slot and concurrent header-only PUTs (bodies never
    sent) must all shed 503 within the deadline — proving the decision
    precedes the first body byte — with every shed counted in
    minio_tpu_requests_shed_total{reason}."""
    import hashlib
    import http.client
    import shutil
    import socket as socket_mod
    import tempfile
    import threading
    import urllib.parse

    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.s3 import signature as sig
    from minio_tpu.s3.credentials import Credentials
    from minio_tpu.s3.server import S3Server
    from minio_tpu.utils import telemetry

    creds = Credentials("benchedgekey1", "benchedgesecret1")
    region = "us-east-1"

    def rss_kb() -> int:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    def shed_values() -> dict:
        c = telemetry.REGISTRY.counter("minio_tpu_requests_shed_total")
        with c._mu:
            return {dict(k).get("reason", ""): v
                    for k, v in c._series.items()}

    def signed(method, path, port, payload_hash, extra=None):
        hdrs = {"host": f"127.0.0.1:{port}"}
        hdrs.update(extra or {})
        return sig.sign_v4(method, urllib.parse.quote(path), {}, hdrs,
                           payload_hash, creds, region)

    def mk_server(layer, edge: bool) -> S3Server:
        was = os.environ.get("MINIO_TPU_EDGE")
        os.environ["MINIO_TPU_EDGE"] = "on" if edge else "off"
        try:
            return S3Server(layer, creds=creds, region=region).start()
        finally:
            if was is None:
                os.environ.pop("MINIO_TPU_EDGE", None)
            else:
                os.environ["MINIO_TPU_EDGE"] = was

    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    codec_mod.DEVICE_MIN_BYTES = 1 << 60        # host-path isolation
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_edge_", dir=base)
    out: dict = {"config": {"streams": list(streams), "size": size,
                            "rounds": rounds, "idle_conns": idle_conns,
                            "idle_ratio": idle_ratio, "drives": drives,
                            "m": parity}}
    payload = os.urandom(size)
    payload_sha = hashlib.sha256(payload).hexdigest()
    try:
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False)

        def one_server_pass(edge: bool) -> dict:
            srv = mk_server(sets, edge)
            tag = "edge" if edge else "threaded"
            bucket = f"bench-{tag}"
            port = srv.port
            res: dict = {}
            idle: list = []
            try:
                st = _http_put(port, f"/{bucket}", b"", signed, creds)
                assert st == 200, f"bucket create {st}"
                # untimed warm-up: the first PUT through a cold engine
                # pays staging-ring/hasher setup — that's the layer's
                # cost, not the frontend's, and the A/B must not charge
                # it to whichever transport runs first
                for w in range(2):
                    st = _http_put(port, f"/{bucket}/warm-{w}", payload,
                                   signed, creds)
                    assert st == 200, f"warm-up put {st}"
                # -- phase 1: idle keep-alive pool ---------------------
                target = idle_conns if edge else \
                    max(idle_conns // idle_ratio, 2)
                threads0 = threading.active_count()
                rss0 = rss_kb()
                for _ in range(target):
                    s = socket_mod.create_connection(
                        ("127.0.0.1", port), timeout=30)
                    # one real (unsigned -> 403) request marks the conn
                    # established + keep-alive
                    s.sendall((f"GET / HTTP/1.1\r\nHost: "
                               f"127.0.0.1:{port}\r\n\r\n").encode())
                    _read_resp(s)
                    idle.append(s)
                res["idle"] = {
                    "conns": len(idle),
                    "rss_delta_kb": max(rss_kb() - rss0, 0),
                    "rss_per_conn_kb": round(
                        max(rss_kb() - rss0, 0) / max(len(idle), 1), 2),
                    "threads_delta": threading.active_count() - threads0,
                }
                # -- phase 2: matched load over the idle population ----
                res["points"] = []
                for n in streams:
                    lats_put: list = []
                    lats_get: list = []
                    mu = threading.Lock()
                    errs: list = []

                    def worker(sid: int) -> None:
                        try:
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", port, timeout=60)
                            for r in range(rounds):
                                path = f"/{bucket}/o-{sid}-{r}"
                                hdrs = signed("PUT", path, port,
                                              payload_sha)
                                t0 = time.perf_counter()
                                conn.request("PUT", path, body=payload,
                                             headers=hdrs)
                                resp = conn.getresponse()
                                resp.read()
                                dt = time.perf_counter() - t0
                                assert resp.status == 200, resp.status
                                with mu:
                                    lats_put.append(dt)
                            for r in range(rounds):
                                path = f"/{bucket}/o-{sid}-{r}"
                                hdrs = signed("GET", path, port,
                                              sig.UNSIGNED_PAYLOAD)
                                t0 = time.perf_counter()
                                conn.request("GET", path, headers=hdrs)
                                resp = conn.getresponse()
                                body = resp.read()
                                dt = time.perf_counter() - t0
                                assert resp.status == 200 \
                                    and body == payload
                                with mu:
                                    lats_get.append(dt)
                            conn.close()
                        except BaseException as e:  # noqa: BLE001
                            with mu:
                                errs.append(e)

                    ts = [threading.Thread(target=worker, args=(i,))
                          for i in range(n)]
                    t0 = time.perf_counter()
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join()
                    wall = time.perf_counter() - t0
                    if errs:
                        raise errs[0]

                    def pcts(xs):
                        xs = sorted(xs)
                        return {
                            "p50_ms": round(xs[len(xs) // 2] * 1e3, 2),
                            "p99_ms": round(
                                xs[max(0, int(len(xs) * .99) - 1)]
                                * 1e3, 2)}
                    res["points"].append({
                        "streams": n, "wall_s": round(wall, 3),
                        "put": pcts(lats_put), "get": pcts(lats_get),
                        "put_gib_s": round(
                            len(lats_put) * size / wall / (1 << 30), 3),
                    })
                # the idle pool survived the load: a sampled conn still
                # answers on its original socket
                probe = idle[len(idle) // 2]
                probe.sendall((f"GET / HTTP/1.1\r\nHost: "
                               f"127.0.0.1:{port}\r\n\r\n").encode())
                status = _read_resp(probe)
                res["idle"]["alive_after_load"] = status == 403
            finally:
                for s in idle:
                    try:
                        s.close()
                    except OSError:
                        pass
                srv.stop()
            return res

        out["edge"] = one_server_pass(edge=True)
        out["threaded"] = one_server_pass(edge=False)
        out["idle_conn_ratio_x"] = round(
            out["edge"]["idle"]["conns"]
            / max(out["threaded"]["idle"]["conns"], 1), 1)
        top = out["edge"]["points"][-1]
        base_top = out["threaded"]["points"][-1]
        out["put_p99_edge_vs_threaded_x"] = round(
            top["put"]["p99_ms"] / max(base_top["put"]["p99_ms"], 1e-9),
            3)

        # -- phase 3: shed-before-body probe on the edge ---------------
        srv = mk_server(sets, edge=True)
        try:
            srv.api.admission.resize(1)
            srv.api.admission.deadline = 0.1
            hold = srv.api.admission.admit("GET", "/x/y", {}, {})
            before = shed_values()
            refused = 0
            for _ in range(8):
                s = socket_mod.create_connection(
                    ("127.0.0.1", srv.port), timeout=30)
                s.sendall((f"PUT /{'shedb'}/k HTTP/1.1\r\n"
                           f"Host: 127.0.0.1:{srv.port}\r\n"
                           f"Content-Length: {1 << 20}\r\n\r\n"
                           ).encode())   # body NEVER sent
                if _read_resp(s) == 503:
                    refused += 1
                s.close()
            hold.release()
            after = shed_values()
            out["saturation_sheds"] = {
                "refused_503": refused,
                "counter_delta": {
                    k: after.get(k, 0) - before.get(k, 0)
                    for k in after
                    if after.get(k, 0) != before.get(k, 0)},
                "body_bytes_sent": 0,
            }
        finally:
            srv.stop()
    finally:
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_obs_ab(streams: int = 8, size: int = 1 << 20,
                 drives: int = 6, parity: int = 2, block: int = 1 << 18,
                 node_counts: Sequence[int] = (1, 2, 4, 8),
                 put_rounds: int = 4, attrib_reps: int = 12,
                 attrib_batch: int = 8) -> dict:
    """Observability-plane A/B (ISSUE 13): what the cluster
    observability layer itself costs.

    Phase 1 — federated-scrape merge latency vs node count: a real
    node-shaped exposition (this process's live registry render) is
    merged N-ways through utils/promfed — the exact path the admin
    ?cluster=1 route runs after its peer fan-out — reporting merge wall
    time and output size per node count, plus one authenticated HTTP
    scrape of the single live server's admin /metrics route
    (local_scrape_*: render + auth + transport floor — the bench's
    server has no peer plane, so the RPC fan-out itself is not in this
    number; tests/test_obs.py times the real 2-node federated path).

    Phase 2 — trace-follow overhead on the foreground: concurrent
    signed HTTP PUT rounds, p50/p99 WITHOUT vs WITH a live ?follow=1
    subscriber consuming the stream (`mc admin trace` running against
    a busy box must be near-free).

    Phase 3 — telemetry_overhead_x with dispatch attribution on/off:
    identical fused encode batches through two BatchSchedulers, one
    with MINIO_TPU_SCHED_ATTRIB=off — the cost of the stage histograms
    + stage spans themselves (device route forced so the dispatch path
    actually runs on CPU-only hosts; warmed, best-of medians).
    """
    import concurrent.futures as cf
    import hashlib
    import shutil
    import tempfile
    import threading
    import urllib.parse

    from minio_tpu import bitrot as bitrot_mod
    from minio_tpu.madmin import AdminClient
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.parallel.scheduler import BatchScheduler
    from minio_tpu.s3 import signature as sig
    from minio_tpu.s3.admin import mount_admin
    from minio_tpu.s3.credentials import Credentials
    from minio_tpu.s3.server import S3Server
    from minio_tpu.utils import promfed, telemetry

    creds = Credentials("benchobskey12", "benchobssecret12")
    region = "us-east-1"
    out: dict = {"config": {"streams": streams, "size": size,
                            "node_counts": list(node_counts),
                            "put_rounds": put_rounds,
                            "attrib_reps": attrib_reps}}

    def pcts(lat: list[float]) -> dict:
        lat = sorted(lat)
        return {"p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                "p99_ms": round(lat[min(int(len(lat) * 0.99),
                                        len(lat) - 1)] * 1e3, 3)}

    # -- phase 1: merge latency vs node count ---------------------------
    exposition = telemetry.REGISTRY.render()
    merge_points = []
    for n in node_counts:
        nodes = [(f"node{i}:9000", exposition) for i in range(n)]
        reps = []
        merged = ""
        for _ in range(3):
            t0 = time.perf_counter()
            merged = promfed.merge_expositions(nodes)
            reps.append(time.perf_counter() - t0)
        merge_points.append({
            "nodes": n,
            "merge_ms": round(_median(reps) * 1e3, 3),
            "input_bytes": n * len(exposition),
            "output_bytes": len(merged)})
    out["cluster_scrape"] = {"points": merge_points,
                             "exposition_bytes": len(exposition)}

    # -- phases 2+3 need a live server / scheduler ----------------------
    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_obs_", dir=base)
    payload = os.urandom(size)
    was_is_tpu = codec_mod._device_is_tpu
    was_min_bytes = codec_mod.DEVICE_MIN_BYTES
    try:
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False)
        srv = S3Server(sets, creds=creds, region=region).start()
        mount_admin(srv)
        mc = AdminClient("127.0.0.1", srv.port, creds.access_key,
                         creds.secret_key)
        try:
            t0 = time.perf_counter()
            text = mc.node_metrics()
            out["cluster_scrape"]["local_scrape_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3)
            out["cluster_scrape"]["local_scrape_bytes"] = len(text)

            def signed(method, path, port, payload_hash, extra=None):
                hdrs = {"host": f"127.0.0.1:{port}"}
                hdrs.update(extra or {})
                return sig.sign_v4(method, urllib.parse.quote(path), {},
                                   hdrs, payload_hash, creds, region)

            assert _http_put(srv.port, "/bench-obs", b"", signed,
                             creds) == 200
            assert _http_put(srv.port, "/bench-obs/warm", payload,
                             signed, creds) == 200    # engine warm-up

            def put_round(prefix: str) -> list[float]:
                lat: list[float] = []
                mu = threading.Lock()

                def one(i: int) -> None:
                    t0 = time.perf_counter()
                    st = _http_put(srv.port,
                                   f"/bench-obs/{prefix}-{i}", payload,
                                   signed, creds)
                    dt = time.perf_counter() - t0
                    assert st == 200, st
                    with mu:
                        lat.append(dt)

                for r in range(put_rounds):
                    with cf.ThreadPoolExecutor(
                            max_workers=streams) as ex:
                        list(ex.map(one, range(r * streams,
                                               (r + 1) * streams)))
                return lat

            base_lat = put_round("base")
            stop = threading.Event()
            consumed = [0]

            def follower() -> None:
                try:
                    for _e in mc.trace_follow(timeout=120):
                        consumed[0] += 1
                        if stop.is_set():
                            return
                except Exception:  # noqa: BLE001 — stream torn at stop
                    pass

            ft = threading.Thread(target=follower, daemon=True)
            ft.start()
            time.sleep(0.3)                 # subscription armed
            follow_lat = put_round("follow")
            stop.set()
            out["trace_follow"] = {
                "baseline": pcts(base_lat),
                "with_follow": pcts(follow_lat),
                "entries_consumed": consumed[0],
                "put_p99_overhead_x": round(
                    pcts(follow_lat)["p99_ms"]
                    / max(pcts(base_lat)["p99_ms"], 1e-9), 3)}
        finally:
            srv.stop()
            sets.close()

        # -- phase 3: attribution on/off ---------------------------------
        codec_mod._device_is_tpu = lambda: True  # force the device route so
        codec_mod.DEVICE_MIN_BYTES = 0      # dispatches actually happen
        algo = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256
        k = drives - parity
        data = np.random.randint(0, 255,
                                 (attrib_batch, k, block // k),
                                 dtype=np.uint8)
        codec = codec_mod.Codec(k, parity, block)
        attrib_t: dict[str, list[float]] = {"on": [], "off": []}
        for mode in ("on", "off"):
            was = os.environ.get("MINIO_TPU_SCHED_ATTRIB")
            os.environ["MINIO_TPU_SCHED_ATTRIB"] = mode
            try:
                sched = BatchScheduler(max_wait=0.001)
            finally:
                if was is None:
                    os.environ.pop("MINIO_TPU_SCHED_ATTRIB", None)
                else:
                    os.environ["MINIO_TPU_SCHED_ATTRIB"] = was
            try:
                with telemetry.trace(f"bench.obs.attrib.{mode}"):
                    r = sched.submit(codec, data, algo).result(120)
                    assert r is not None, "dispatch declined"
                    for _ in range(attrib_reps):
                        t0 = time.perf_counter()
                        sched.submit(codec, data, algo).result(120)
                        attrib_t[mode].append(
                            time.perf_counter() - t0)
            finally:
                sched.close()
        on_ms = _median(attrib_t["on"]) * 1e3
        off_ms = _median(attrib_t["off"]) * 1e3
        out["attrib"] = {
            "dispatch_ms_attrib_on": round(on_ms, 3),
            "dispatch_ms_attrib_off": round(off_ms, 3),
            "telemetry_overhead_x": round(on_ms / max(off_ms, 1e-9),
                                          3)}
    finally:
        codec_mod._device_is_tpu = was_is_tpu
        codec_mod.DEVICE_MIN_BYTES = was_min_bytes
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_incident_ab(streams: int = 8, size: int = 1 << 20,
                      drives: int = 6, parity: int = 2,
                      block: int = 1 << 18, put_rounds: int = 4,
                      gets: int = 64) -> dict:
    """Incident-plane A/B (ISSUE 18): what the always-on journal +
    SLO engine cost the foreground, and how fast the black box closes.

    Phase 1 — foreground overhead: concurrent signed HTTP PUT and GET
    p50/p99 with MINIO_TPU_EVENTLOG + MINIO_TPU_SLO off, then on (SLO
    evaluator running). The journal is designed to be always-on in
    production, so put_p99_overhead_x is the number that must stay
    ~1.0 (acceptance: <= 1.05).

    Phase 2 — capture latency: with the plane on, a seeded trigger
    event (drive.probation) is emitted and the wall time until the
    flight recorder's bundle lands on disk is reported, along with
    the bundle's journal/span content counts."""
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading
    import urllib.parse

    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.s3 import signature as sig
    from minio_tpu.s3.admin import mount_admin
    from minio_tpu.s3.credentials import Credentials
    from minio_tpu.s3.server import S3Server
    from minio_tpu.utils import eventlog, incidents, slo

    creds = Credentials("benchinckey123", "benchincsecret1")
    region = "us-east-1"
    out: dict = {"config": {"streams": streams, "size": size,
                            "put_rounds": put_rounds, "gets": gets}}

    def pcts(lat: list[float]) -> dict:
        lat = sorted(lat)
        return {"p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                "p99_ms": round(lat[min(int(len(lat) * 0.99),
                                        len(lat) - 1)] * 1e3, 3)}

    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_inc_", dir=base)
    payload = os.urandom(size)
    knob_names = ("MINIO_TPU_EVENTLOG", "MINIO_TPU_SLO")
    saved = {k: os.environ.get(k) for k in knob_names}
    try:
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False)
        srv = S3Server(sets, creds=creds, region=region).start()
        mount_admin(srv)
        try:
            def signed(method, path, port, payload_hash, extra=None):
                hdrs = {"host": f"127.0.0.1:{port}"}
                hdrs.update(extra or {})
                return sig.sign_v4(method, urllib.parse.quote(path),
                                   {}, hdrs, payload_hash, creds,
                                   region)

            assert _http_put(srv.port, "/bench-inc", b"", signed,
                             creds) == 200
            assert _http_put(srv.port, "/bench-inc/warm", payload,
                             signed, creds) == 200   # engine warm-up

            def put_round(prefix: str) -> list[float]:
                lat: list[float] = []
                mu = threading.Lock()

                def one(i: int) -> None:
                    t0 = time.perf_counter()
                    st = _http_put(srv.port,
                                   f"/bench-inc/{prefix}-{i}",
                                   payload, signed, creds)
                    dt = time.perf_counter() - t0
                    assert st == 200, st
                    with mu:
                        lat.append(dt)

                for r in range(put_rounds):
                    with cf.ThreadPoolExecutor(
                            max_workers=streams) as ex:
                        list(ex.map(one, range(r * streams,
                                               (r + 1) * streams)))
                return lat

            def get_round() -> list[float]:
                import hashlib
                import http.client
                lat: list[float] = []
                for i in range(gets):
                    t0 = time.perf_counter()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", srv.port, timeout=60)
                    hdrs = signed("GET", "/bench-inc/warm", srv.port,
                                  hashlib.sha256(b"").hexdigest())
                    conn.request("GET", "/bench-inc/warm",
                                 headers=hdrs)
                    resp = conn.getresponse()
                    resp.read()
                    conn.close()
                    assert resp.status == 200, resp.status
                    lat.append(time.perf_counter() - t0)
                return lat

            for mode, flag in (("off", "off"), ("on", "on")):
                for k in knob_names:
                    os.environ[k] = flag
                if mode == "on":
                    slo.ENGINE.ensure_started()
                out.setdefault("put", {})[mode] = pcts(
                    put_round(mode))
                out.setdefault("get", {})[mode] = pcts(get_round())
            out["put_p99_overhead_x"] = round(
                out["put"]["on"]["p99_ms"]
                / max(out["put"]["off"]["p99_ms"], 1e-9), 3)
            out["get_p99_overhead_x"] = round(
                out["get"]["on"]["p99_ms"]
                / max(out["get"]["off"]["p99_ms"], 1e-9), 3)

            # -- phase 2: seeded-fault capture timing ------------------
            incidents.RECORDER.attach(os.path.join(root, "incidents"))
            known = {i["id"] for i in incidents.RECORDER.list()}
            t0 = time.perf_counter()
            eventlog.emit("drive.probation", drive=f"{root}/d0",
                          set=0)
            bundle = None
            while time.perf_counter() - t0 < 10.0:
                fresh = [i for i in incidents.RECORDER.list()
                         if i["id"] not in known]
                if fresh:
                    bundle = incidents.RECORDER.get(fresh[0]["id"])
                    break
                time.sleep(0.005)
            capture_ms = round((time.perf_counter() - t0) * 1e3, 3)
            out["capture"] = {
                "trigger": "drive.probation",
                "captured": bundle is not None,
                "capture_ms": capture_ms,
                "journal_events": len((bundle or {}).get("events",
                                                        ())),
                "slow_spans": len((bundle or {}).get("slow_spans",
                                                     ())),
            }
        finally:
            srv.stop()
            sets.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(root, ignore_errors=True)
    return out


def bench_tenants_ab(noisy_streams: int = 8, size: int = 1 << 20,
                     drives: int = 6, parity: int = 2,
                     block: int = 1 << 18, polite_ops: int = 24,
                     max_clients: int = 8,
                     overhead_rounds: int = 4) -> dict:
    """Multi-tenant QoS A/B (ISSUE 19): does the weighted-share gate
    actually protect a polite tenant from a noisy neighbor, and what
    does the plane cost a lone tenant.

    Phase 1 — isolation: a noisy IAM tenant hammers PUTs on
    noisy_streams concurrent connections while a polite tenant issues
    one sequential PUT at a time. With MINIO_TPU_QOS off the polite
    stream queues behind the noisy flood at the maxClients semaphore;
    with it on (equal shares) the noisy tenant is bounded to its
    share of the gate and its excess streams shed 503 SlowDown under
    reason=tenant, so the polite p99 must drop. isolation_p99_x is
    polite-p99-off / polite-p99-on (> 1 means the plane helped).

    Phase 2 — lone-tenant overhead: the same concurrent PUT round as
    the incident A/B, single (root) tenant, QoS off vs on. A lone
    tenant borrows the whole gate, so put_p99_overhead_x is pure
    bookkeeping cost (acceptance: <= 1.05)."""
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading
    import urllib.parse

    from minio_tpu.iam.sys import IAMSys
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.s3 import signature as sig
    from minio_tpu.s3.credentials import Credentials
    from minio_tpu.s3.qos import Budget
    from minio_tpu.s3.server import S3Server
    from minio_tpu.utils import telemetry

    creds = Credentials("benchqoskey123", "benchqossecret1")
    noisy_cred = Credentials("noisytenant123", "noisysecret1234")
    polite_cred = Credentials("politetenant12", "politesecret123")
    region = "us-east-1"
    out: dict = {"config": {"noisy_streams": noisy_streams,
                            "size": size, "polite_ops": polite_ops,
                            "max_clients": max_clients}}

    def pcts(lat: list[float]) -> dict:
        lat = sorted(lat)
        return {"p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                "p99_ms": round(lat[min(int(len(lat) * 0.99),
                                        len(lat) - 1)] * 1e3, 3)}

    base = "/dev/shm" if os.path.isdir("/dev/shm") else \
        tempfile.gettempdir()
    root = tempfile.mkdtemp(prefix="bench_qos_", dir=base)
    payload = os.urandom(size)
    saved = os.environ.get("MINIO_TPU_QOS")
    try:
        sets = ErasureSets.from_drives(
            [f"{root}/d{i}" for i in range(drives)], 1, drives, parity,
            block_size=block, enable_mrf=False)
        iam = IAMSys(root_cred=creds)
        iam.add_user(noisy_cred.access_key, noisy_cred.secret_key)
        iam.add_user(polite_cred.access_key, polite_cred.secret_key)
        iam.attach_policy("readwrite", user=noisy_cred.access_key)
        iam.attach_policy("readwrite", user=polite_cred.access_key)
        srv = S3Server(sets, creds=creds, region=region,
                       iam=iam).start()
        srv.api.set_max_clients(max_clients)
        try:
            def mk_signed(cred):
                def signed(method, path, port, payload_hash,
                           extra=None):
                    hdrs = {"host": f"127.0.0.1:{port}"}
                    hdrs.update(extra or {})
                    return sig.sign_v4(method,
                                       urllib.parse.quote(path), {},
                                       hdrs, payload_hash, cred,
                                       region)
                return signed

            signed_root = mk_signed(creds)
            assert _http_put(srv.port, "/bench-qos", b"", signed_root,
                             creds) == 200
            assert _http_put(srv.port, "/bench-qos/warm", payload,
                             signed_root, creds) == 200

            # equal shares: with both tenants active the noisy tenant
            # is bounded to half the gate and its surplus streams shed
            srv.api.qos.registry.set_budget(
                "tenant", Budget(noisy_cred.access_key, share=1.0))
            srv.api.qos.registry.set_budget(
                "tenant", Budget(polite_cred.access_key, share=1.0))

            shed_counter = telemetry.REGISTRY.counter(
                "minio_tpu_requests_shed_total")

            def isolation_phase(mode: str, tag: str) -> dict:
                os.environ["MINIO_TPU_QOS"] = mode
                shed0 = shed_counter.value(reason="tenant")
                stop = threading.Event()
                mu = threading.Lock()
                noisy = {"ok": 0, "shed": 0}
                signed_noisy = mk_signed(noisy_cred)
                signed_polite = mk_signed(polite_cred)

                def noisy_worker(w: int) -> None:
                    i = 0
                    while not stop.is_set():
                        try:
                            st = _http_put(
                                srv.port,
                                f"/bench-qos/n-{tag}-{w}-{i}",
                                payload, signed_noisy, noisy_cred)
                        except OSError:
                            # the gate refused pre-body and closed the
                            # socket while this client was still
                            # streaming the payload — a shed, observed
                            # as a reset instead of the 503
                            st = 503
                        with mu:
                            if st == 200:
                                noisy["ok"] += 1
                            elif st == 503:
                                noisy["shed"] += 1
                        i += 1

                threads = [threading.Thread(target=noisy_worker,
                                            args=(w,), daemon=True)
                           for w in range(noisy_streams)]
                for t in threads:
                    t.start()
                lat: list[float] = []
                signed_p = signed_polite
                for i in range(polite_ops):
                    t0 = time.perf_counter()
                    while True:
                        try:
                            st = _http_put(srv.port,
                                           f"/bench-qos/p-{tag}-{i}",
                                           payload, signed_p,
                                           polite_cred)
                        except OSError:
                            st = 503
                        if st == 200:
                            break
                        assert st == 503, st
                        time.sleep(0.002)
                    lat.append(time.perf_counter() - t0)
                stop.set()
                for t in threads:
                    t.join(timeout=30)
                return {"polite": pcts(lat),
                        "noisy_ok": noisy["ok"],
                        "noisy_shed": noisy["shed"],
                        "shed_total_delta": int(
                            shed_counter.value(reason="tenant")
                            - shed0)}

            for mode in ("off", "on"):
                out.setdefault("isolation", {})[mode] = \
                    isolation_phase(mode, mode)
            out["isolation_p99_x"] = round(
                out["isolation"]["off"]["polite"]["p99_ms"]
                / max(out["isolation"]["on"]["polite"]["p99_ms"],
                      1e-9), 3)
            out["noisy_sheds"] = \
                out["isolation"]["on"]["shed_total_delta"]
            stats = srv.api.qos.stats()
            out["tenant_stats"] = {
                t: {"requests": s["requests"], "shed": s["shed"]}
                for t, s in stats.items()}

            # -- phase 2: lone-tenant overhead ---------------------
            def overhead_round(tag: str) -> list[float]:
                lat: list[float] = []
                mu = threading.Lock()

                def one(i: int) -> None:
                    t0 = time.perf_counter()
                    while True:
                        try:
                            st = _http_put(srv.port,
                                           f"/bench-qos/o-{tag}-{i}",
                                           payload, signed_root,
                                           creds)
                        except OSError:
                            st = 503
                        if st == 200:
                            break
                        # a 503 here is residual staging pressure from
                        # the isolation flood; retry like a client would
                        assert st == 503, st
                        time.sleep(0.01)
                    with mu:
                        lat.append(time.perf_counter() - t0)

                for r in range(overhead_rounds):
                    with cf.ThreadPoolExecutor(
                            max_workers=noisy_streams) as ex:
                        list(ex.map(one,
                                    range(r * noisy_streams,
                                          (r + 1) * noisy_streams)))
                return lat

            for mode in ("off", "on"):
                os.environ["MINIO_TPU_QOS"] = mode
                out.setdefault("overhead", {})[mode] = pcts(
                    overhead_round(mode))
            out["put_p99_overhead_x"] = round(
                out["overhead"]["on"]["p99_ms"]
                / max(out["overhead"]["off"]["p99_ms"], 1e-9), 3)
        finally:
            srv.stop()
            sets.close()
    finally:
        if saved is None:
            os.environ.pop("MINIO_TPU_QOS", None)
        else:
            os.environ["MINIO_TPU_QOS"] = saved
        shutil.rmtree(root, ignore_errors=True)
    return out


def _read_resp(sock) -> int:
    """Read one HTTP response off a raw socket; returns the status."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            return 0
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    length = 0
    for line in lines[1:]:
        if line.lower().startswith("content-length:"):
            length = int(line.split(":", 1)[1])
    while len(rest) < length:
        chunk = sock.recv(65536)
        if not chunk:
            break
        rest += chunk
    return status


def _http_put(port: int, path: str, body: bytes, signed, creds) -> int:
    import hashlib
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    hdrs = signed("PUT", path, port, hashlib.sha256(body).hexdigest())
    conn.request("PUT", path, body=body, headers=hdrs)
    st = conn.getresponse()
    st.read()
    conn.close()
    return st.status


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ab-streams", type=int,
                    default=int(os.environ.get("BENCH_AB_STREAMS", "32")))
    ap.add_argument("--ab-size", type=int,
                    default=int(os.environ.get("BENCH_AB_SIZE",
                                               str(16 << 20))))
    ap.add_argument("--ab-rebalance", action="store_true",
                    help="run ONLY the rebalance-throttle A/B "
                         "(foreground PUT p50/p99 with vs without an "
                         "active pool drain)")
    ap.add_argument("--saturation", action="store_true",
                    help="run ONLY the multi-stream saturation sweep: "
                         "aggregate PUT/GET/degraded-GET GiB/s + batch "
                         "former per-verb occupancy vs stream count, "
                         "with a scheduler-bypassed A/B per point")
    ap.add_argument("--saturation-streams",
                    default=os.environ.get("BENCH_SAT_STREAMS",
                                           "1,2,4,8,16,32"),
                    help="comma-separated stream counts for the sweep")
    ap.add_argument("--saturation-size", type=int,
                    default=int(os.environ.get("BENCH_SAT_SIZE",
                                               str(16 << 20))))
    ap.add_argument("--saturation-smoke", action="store_true",
                    help="tiny 2-point sweep (streams 1,2; 4-block "
                         "objects; 4+2 set) for CI — seconds, not "
                         "minutes")
    ap.add_argument("--ab-list", action="store_true",
                    help="run ONLY the listing A/B (merge-walk vs "
                         "metacache index): page p50/p99 + one "
                         "crawler-cycle wall time + walk counts")
    ap.add_argument("--ab-list-keys", type=int,
                    default=int(os.environ.get("BENCH_LIST_KEYS",
                                               "10000")))
    ap.add_argument("--ab-list-smoke", action="store_true",
                    help="tiny listing A/B (400 keys, 50-key pages) "
                         "for CI — seconds, not minutes")
    ap.add_argument("--ab-select", action="store_true",
                    help="run ONLY the S3 Select A/B (device scan "
                         "plane vs CPU evaluator) at 1..N concurrent "
                         "queries, with scan-verb coalescing counters "
                         "per point")
    ap.add_argument("--ab-select-streams",
                    default=os.environ.get("BENCH_SELECT_STREAMS",
                                           "1,2,4,8"),
                    help="comma-separated concurrency points for "
                         "--ab-select")
    ap.add_argument("--ab-select-rows", type=int,
                    default=int(os.environ.get("BENCH_SELECT_ROWS",
                                               "20000")))
    ap.add_argument("--ab-select-smoke", action="store_true",
                    help="tiny Select A/B (2 points, 3000-row corpus) "
                         "for CI — seconds, not minutes")
    ap.add_argument("--ab-sse", action="store_true",
                    help="encrypted PUT+GET A/B: device-fused "
                    "cipher+RS+digest data path vs the CPU ChaCha20 "
                    "fallback, with launch/coalescing counters")
    ap.add_argument("--ab-sse-smoke", action="store_true",
                    help="tiny CI variant of --ab-sse")
    ap.add_argument("--ab-cache", action="store_true",
                    help="run ONLY the hot-GET A/B (erasure read path "
                         "with the hot-object read cache off vs on, "
                         "decode-stream counter deltas)")
    ap.add_argument("--ab-cache-smoke", action="store_true",
                    help="tiny cache A/B (8 x 256 KiB objects, 60 "
                         "GETs) for CI — seconds, not minutes")
    ap.add_argument("--ab-tier", action="store_true",
                    help="run ONLY the tier-transition-throttle A/B "
                         "(foreground PUT p50/p99 with vs without the "
                         "transition worker draining to a tier)")
    ap.add_argument("--ab-replicate", action="store_true",
                    help="run ONLY the replication A/B (foreground PUT "
                         "p50/p99 with vs without an active resync "
                         "drain to a second in-process site, plus the "
                         "replication lag histogram)")
    ap.add_argument("--ab-replicate-smoke", action="store_true",
                    help="tiny replication A/B (2 streams, 256 KiB "
                         "objects, 8-key resync) for CI — seconds, "
                         "not minutes")
    ap.add_argument("--ab-notify", action="store_true",
                    help="run ONLY the notification A/B (foreground "
                         "PUT p50/p99 with vs without every PUT "
                         "fanning out to a deliberately SLOW webhook, "
                         "plus the delivery-lag histogram)")
    ap.add_argument("--ab-notify-smoke", action="store_true",
                    help="tiny notification A/B (2 streams, 256 KiB "
                         "objects, 10 ms webhook stall) for CI — "
                         "seconds, not minutes")
    ap.add_argument("--ab-edge", action="store_true",
                    help="run ONLY the HTTP frontend A/B (event-loop "
                         "edge vs threaded oracle): idle keep-alive "
                         "capacity at flat RSS, PUT/GET p50/p99 at "
                         "matched load, shed-before-body counters")
    ap.add_argument("--ab-edge-smoke", action="store_true",
                    help="tiny edge A/B (2 streams, 256 KiB objects, "
                         "60 idle conns) for CI — seconds, not minutes")
    ap.add_argument("--ab-gray", action="store_true",
                    help="gray-failure A/B: GET/PUT p50/p99 with one "
                    "drive stalling per I/O, hedging+quorum-ack+"
                    "quarantine on vs off")
    ap.add_argument("--ab-gray-stall", type=float, default=0.5,
                    help="--ab-gray injected per-I/O stall, seconds "
                    "(default 0.5)")
    ap.add_argument("--ab-gray-smoke", action="store_true",
                    help="tiny CI variant of --ab-gray")
    ap.add_argument("--ab-partition", action="store_true",
                    help="partition-tolerance A/B: federated-scrape "
                    "fan-out p50/p99 baseline vs one peer partitioned "
                    "away vs healed; asserts the degraded fan-out is "
                    "bounded by the scrape deadline, not TCP timeouts")
    ap.add_argument("--ab-partition-smoke", action="store_true",
                    help="tiny CI variant of --ab-partition (2 peers, "
                    "6 rounds)")
    ap.add_argument("--ab-obs", action="store_true",
                    help="run ONLY the observability-plane A/B: "
                         "federated-scrape merge latency vs node "
                         "count, trace-follow overhead on foreground "
                         "PUT p99, dispatch-attribution on/off "
                         "overhead")
    ap.add_argument("--ab-obs-smoke", action="store_true",
                    help="tiny observability A/B (2 streams, 256 KiB "
                         "objects, 2 node counts) for CI — seconds, "
                         "not minutes")
    ap.add_argument("--ab-incident", action="store_true",
                    help="run ONLY the incident-plane A/B: foreground "
                         "PUT/GET p50/p99 with the event journal + "
                         "SLO engine off vs on, plus seeded-fault "
                         "capture-to-bundle latency")
    ap.add_argument("--ab-incident-smoke", action="store_true",
                    help="tiny incident A/B (2 streams, 256 KiB "
                         "objects) for CI — seconds, not minutes")
    ap.add_argument("--ab-tenants", action="store_true",
                    help="run ONLY the multi-tenant QoS A/B: a noisy "
                         "tenant on 8 streams vs a polite tenant on "
                         "1, polite PUT p99 with the plane off vs on "
                         "(equal shares), plus lone-tenant overhead")
    ap.add_argument("--ab-tenants-smoke", action="store_true",
                    help="tiny tenants A/B (2 noisy streams, 256 KiB "
                         "objects) for CI — seconds, not minutes")
    args = ap.parse_args()

    if args.ab_gray or args.ab_gray_smoke:
        if args.ab_gray_smoke:
            ab = bench_gray_ab(objects=5, size=1 << 18, gets=20,
                               streams=4, drives=6, block=1 << 16,
                               stall_s=0.3)
        else:
            ab = bench_gray_ab(stall_s=args.ab_gray_stall)
        print(json.dumps({
            "metric": "GET p99 speedup with one drive stalling "
                      f"{ab['config']['stall_s']}s/I-O, gray-failure "
                      "plane on vs off (PUT acks at quorum, zero "
                      "acked-write loss after MRF drain)",
            "value": ab.get("get_p99_speedup_x"),
            "unit": "x",
            "gray_ab": ab,
        }))
        return 0

    if args.ab_partition or args.ab_partition_smoke:
        if args.ab_partition_smoke:
            ab = bench_partition_ab(peers=2, rounds=6, deadline=1.0,
                                    payload_kb=8)
        else:
            ab = bench_partition_ab()
        print(json.dumps({
            "metric": "federated-scrape fan-out p99 with one peer "
                      "partitioned away (deadline-bounded, reachable "
                      "peers keep serving; heal restores the full "
                      "merge)",
            "value": ab["partitioned"]["p99_ms"],
            "unit": "ms",
            "partition_ab": ab,
        }))
        return 0

    if args.ab_obs or args.ab_obs_smoke:
        if args.ab_obs_smoke:
            ab = bench_obs_ab(streams=2, size=1 << 18, drives=6,
                              node_counts=(1, 2), put_rounds=2,
                              attrib_reps=4, block=1 << 16)
        else:
            ab = bench_obs_ab(streams=min(args.ab_streams, 8),
                              size=args.ab_size)
        print(json.dumps({
            "metric": "foreground PUT p99 overhead with a live "
                      "cluster trace-follow subscriber attached "
                      "(observability-plane A/B)",
            "value": ab.get("trace_follow", {}).get(
                "put_p99_overhead_x"),
            "unit": "x",
            "obs_ab": ab,
        }))
        return 0

    if args.ab_incident or args.ab_incident_smoke:
        if args.ab_incident_smoke:
            ab = bench_incident_ab(streams=2, size=1 << 18, drives=6,
                                   put_rounds=2, gets=16,
                                   block=1 << 16)
        else:
            ab = bench_incident_ab(streams=min(args.ab_streams, 8),
                                   size=args.ab_size)
        print(json.dumps({
            "metric": "foreground PUT p99 overhead with the event "
                      "journal + SLO engine on vs off (incident-plane "
                      "A/B; capture_ms = trigger-to-bundle latency)",
            "value": ab.get("put_p99_overhead_x"),
            "unit": "x",
            "incident_ab": ab,
        }))
        return 0

    if args.ab_tenants or args.ab_tenants_smoke:
        if args.ab_tenants_smoke:
            ab = bench_tenants_ab(noisy_streams=2, size=1 << 18,
                                  drives=6, block=1 << 16,
                                  polite_ops=8, max_clients=2,
                                  overhead_rounds=2)
        else:
            ab = bench_tenants_ab(noisy_streams=min(args.ab_streams,
                                                    8),
                                  size=args.ab_size)
        print(json.dumps({
            "metric": "polite-tenant PUT p99 with the QoS plane off "
                      "vs on under a noisy neighbor (isolation_p99_x "
                      "> 1 = the plane helped; put_p99_overhead_x = "
                      "lone-tenant cost)",
            "value": ab.get("isolation_p99_x"),
            "unit": "x",
            "tenants_ab": ab,
        }))
        return 0

    if args.ab_edge or args.ab_edge_smoke:
        if args.ab_edge_smoke:
            ab = bench_edge_ab(streams=(2,), size=1 << 18, rounds=2,
                               idle_conns=60, idle_ratio=20, drives=6,
                               block=1 << 16)
        else:
            ab = bench_edge_ab(streams=(4, 16, 32), size=args.ab_size,
                               idle_conns=2000)
        print(json.dumps({
            "metric": "idle keep-alive connections held by the edge "
                      "per threaded-frontend connection (flat RSS), "
                      "with PUT/GET p99 at matched load",
            "value": ab.get("idle_conn_ratio_x"),
            "unit": "x",
            "edge_ab": ab,
        }))
        return 0

    if args.saturation or args.saturation_smoke:
        if args.saturation_smoke:
            sat = bench_saturation(streams=(1, 2), size=4 << 16,
                                   drives=6, parity=2, block=1 << 16,
                                   force_device=True,
                                   sched_max_wait=0.25)
        else:
            sat = bench_saturation(
                streams=tuple(int(x) for x in
                              args.saturation_streams.split(",") if x),
                size=args.saturation_size)
        top = sat["points"][-1] if sat["points"] else {}
        print(json.dumps({
            "metric": "aggregate degraded-GET GiB/s at max streams "
                      "(multi-verb batch-former saturation sweep)",
            "value": top.get("deg_get_gib_s"),
            "unit": "GiB/s",
            "saturation": sat,
        }))
        return 0

    if args.ab_list or args.ab_list_smoke:
        if args.ab_list_smoke:
            ab = bench_list_ab(keys=400, drives=6, page=50,
                               versions_every=16)
        else:
            ab = bench_list_ab(keys=args.ab_list_keys)
        print(json.dumps({
            "metric": "listing page p50 speedup, metacache index vs "
                      "merge-walk (persisted bucket index A/B)",
            "value": ab.get("page_p50_speedup_x"),
            "unit": "x",
            "list_ab": ab,
        }))
        return 0

    if args.ab_select or args.ab_select_smoke:
        if args.ab_select_smoke:
            ab = bench_select_ab(streams=(1, 2), rows=3000,
                                 queries_per_stream=2)
        else:
            ab = bench_select_ab(
                streams=tuple(int(x) for x in
                              args.ab_select_streams.split(",") if x),
                rows=args.ab_select_rows)
        print(json.dumps({
            "metric": "S3 Select aggregate speedup, device scan plane "
                      "vs CPU evaluator (max over concurrency points)",
            "value": ab.get("max_speedup_x"),
            "unit": "x",
            "select_ab": ab,
        }))
        return 0

    if args.ab_sse or args.ab_sse_smoke:
        if args.ab_sse_smoke:
            ab = bench_sse_ab(streams=(1, 2), size=1 << 18, objects=2,
                              drives=6, parity=2, block=1 << 16)
        else:
            ab = bench_sse_ab()
        print(json.dumps({
            "metric": "encrypted PUT throughput, device-fused "
                      "cipher+RS+digest path vs CPU cipher fallback "
                      "(max concurrency point)",
            "value": ab.get("put_speedup_x"),
            "unit": "x",
            "sse_ab": ab,
        }))
        return 0

    if args.ab_cache or args.ab_cache_smoke:
        if args.ab_cache_smoke:
            ab = bench_cache_ab(objects=8, size=1 << 18, gets=60,
                                streams=2)
        else:
            ab = bench_cache_ab()
        print(json.dumps({
            "metric": "hot-GET speedup with the erasure-path "
                      "hot-object read cache (80/20 workload)",
            "value": ab.get("speedup_x"),
            "unit": "x",
            "cache_ab": ab,
        }))
        return 0

    if args.ab_replicate or args.ab_replicate_smoke:
        if args.ab_replicate_smoke:
            ab = bench_replicate_ab(streams=2, size=1 << 18, drives=6,
                                    preload=8, block=1 << 16)
        else:
            ab = bench_replicate_ab(streams=min(args.ab_streams, 8),
                                    size=args.ab_size)
        print(json.dumps({
            "metric": "foreground PUT p99 degradation with an active "
                      "replication resync drain (active-active plane "
                      "throttle A/B)",
            "value": ab.get("put_p99_degradation_x"),
            "unit": "x",
            "replicate_ab": ab,
        }))
        return 0

    if args.ab_notify or args.ab_notify_smoke:
        if args.ab_notify_smoke:
            ab = bench_notify_ab(streams=2, size=1 << 18, drives=6,
                                 webhook_delay_s=0.01, block=1 << 16)
        else:
            ab = bench_notify_ab(streams=min(args.ab_streams, 8),
                                 size=args.ab_size)
        print(json.dumps({
            "metric": "foreground PUT p99 degradation with every PUT "
                      "fanning out to a slow webhook (notification "
                      "plane isolation A/B)",
            "value": ab.get("put_p99_degradation_x"),
            "unit": "x",
            "notify_ab": ab,
        }))
        return 0

    if args.ab_tier:
        print(json.dumps({
            "metric": "foreground PUT p99 degradation with an active "
                      "tier-transition drain (tiering throttle A/B)",
            "tier_ab": bench_tier_ab(
                streams=min(args.ab_streams, 8), size=args.ab_size),
        }))
        return 0

    if args.ab_rebalance:
        print(json.dumps({
            "metric": "foreground PUT p99 degradation with an active "
                      "pool drain (rebalance throttle A/B)",
            "rebalance_ab": bench_rebalance_ab(
                streams=min(args.ab_streams, 8), size=args.ab_size),
        }))
        return 0

    dev_gib, dev_info = bench_device()
    cpu_gib, cpu_info = bench_cpu_baseline()

    out = {
        "metric": "Erasure encode+bitrot GiB/s per chip "
                  "(EC 12+4, 1 MiB block, PutObject)",
        "value": round(dev_gib, 3),
        "unit": "GiB/s",
        "vs_baseline": round(dev_gib / cpu_gib, 3) if cpu_gib else None,
        "baseline_cpu_gibs": round(cpu_gib, 3),
        "device_info": dev_info,
        "cpu_info": cpu_info,
        "config": {"k": K, "m": M, "block": BLOCK, "batch": BATCH},
        "note": "device value = fused RS encode + HighwayHash256 per-shard "
                "streaming-bitrot digests (byte-identity asserted vs the "
                "host oracle before timing); value = median of round-robin "
                "block_until_ready-closed calls on device-resident input "
                "(device_info names the device); baseline = CPU SIMD "
                "(GFNI + AVX2 HighwayHash) full reference data path, "
                "single core",
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
