"""The seam to the system under test — the only module that imports
`minio_tpu` or JAX.

From the program the benchmark takes the node (`cluster.start_single`,
the call `python -m minio_tpu server /data/d{1...16}` makes), the
batch former's two submit calls for warming, and the program's own
counters: `scheduler.stats()`, the dispatch-stage histogram, the
`device.decline` journal, MRF stats. Everything it computes from them
lives elsewhere in `benchlib/`.
"""

from __future__ import annotations

import os
import shutil
import threading

import numpy as np

from benchlib import loadgen

VERB_OF_OP = {"PUT": "encode", "GET": "decode"}


def devices() -> dict:
    """The device as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CompileListener:
    """Programs built or loaded, by `jax.monitoring`: one
    backend_compile_duration event per program that was not already in
    this process (a persistent-cache load fires it too, and a
    cache_hits event beside it)."""

    def __init__(self):
        import jax.monitoring as mon
        self._mu = threading.Lock()
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._mu:
                self.programs += 1
                self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        with self._mu:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._mu:
            return {"programs": self.programs, "seconds": self.seconds,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}


class Node:
    """One `start_single` node on 16 drive directories under `root`."""

    def __init__(self, config: dict, root: str, tiny: dict | None = None):
        from minio_tpu.cluster import start_single
        from minio_tpu.s3.credentials import Credentials
        self.config, self.root = config, root
        kw = {"parity": config["parity"]}
        if tiny:                       # rehearse.py only
            kw["block_size"] = tiny["block_size"]
        elif config["block_size"] != 1 << 22:
            raise ValueError("the CLI serves 4 MiB blocks; a configuration "
                             "cannot state another size")
        self.node = start_single(
            [os.path.join(root, "d{1...%d}" % config["drives"])],
            "127.0.0.1", 0,
            Credentials(loadgen.ACCESS_KEY, loadgen.SECRET_KEY), **kw)
        self.port = self.node.s3.port
        self.engine = self.node.sets.sets[0]
        self.k = self.engine.data_shards
        self.m = self.engine.parity_shards
        self.block_size = self.engine.block_size
        self.pulled: list[tuple[int, str]] = []
        self._count_decode_submits()

    def _count_decode_submits(self) -> None:
        """Blocks handed to `submit_decode`, by the number r of data
        shards their mask lacks: the former's stats do not say which r
        a launch had, and the bytes a decode must move depend on it."""
        sched, k = self.node.scheduler, self.k
        inner = sched.submit_decode
        self.decode_by_lost: dict[int, int] = {}
        mu = threading.Lock()

        def submit_decode(codec, survivors, present_mask, *a, **kw):
            r = k - bin(present_mask & ((1 << k) - 1)).count("1")
            with mu:
                self.decode_by_lost[r] = self.decode_by_lost.get(r, 0) \
                    + int(survivors.shape[0])
            return inner(codec, survivors, present_mask, *a, **kw)
        sched.submit_decode = submit_decode

    # -- shapes --------------------------------------------------------------

    def launch_sizes(self, verb: str) -> list[int]:
        """Every B a launch of `verb` can have under whole-block traffic:
        streams submit groups of the engine's batch constant and the
        former fuses whole groups up to its cap."""
        from minio_tpu.object import engine as eng
        group = eng.ENCODE_BATCH_BLOCKS if verb == "encode" \
            else eng.GET_BATCH_BLOCKS
        return list(range(group, self.node.scheduler.max_batch + 1, group))

    def warm(self, verb: str, sizes: list[int],
             lost: tuple[int, ...] = (1,)) -> None:
        """One group of each B through the former's normal route, so
        every program the window can launch is built (or loaded from
        the compile cache) before it. `lost`: for decode, the numbers
        of missing data shards to warm (the mix's `warm_lost_shards`)."""
        sched, eng = self.node.scheduler, self.engine
        codec = eng.codec(self.k, self.m)
        s = codec.shard_size
        rng = np.random.default_rng(0)
        one = rng.integers(0, 256, (1, self.k, s), dtype=np.uint8)
        n = self.k + self.m
        futs = []
        for r in (lost if verb == "decode" else (0,)):
            for b in sizes:
                data = np.broadcast_to(one, (b, self.k, s))
                if verb == "encode":
                    # one bucket: a group is resolved before the next is
                    # submitted, or the former would fuse them
                    fut = sched.submit(codec, data, eng.bitrot_algo)
                    fut.result(timeout=1100)
                else:
                    # r data shards gone, a different r of them for every
                    # B: the buckets differ, so the groups cannot fuse
                    # and the former loads two programs at a time
                    gone = [(len(futs) + i) % self.k for i in range(r)]
                    mask = ((1 << n) - 1) & ~sum(1 << g for g in gone)
                    fut = sched.submit_decode(codec, data, mask, s,
                                              eng.bitrot_algo)
                futs.append((b, r, fut))
        for b, r, fut in futs:
            if fut.result(timeout=1100) is None:
                raise RuntimeError(
                    f"warming {verb} B={b} r={r}: the former routed "
                    "the group to the host — no device path")

    # -- a pulled drive ------------------------------------------------------

    def pull_drive(self, index: int) -> None:
        """Drive `index` is gone: its slot is empty, as for a drive that
        did not come up, and its path no longer holds a directory, so
        the disk monitor's re-probe finds nothing to re-admit or to
        format. Not 'present with files missing'."""
        disk = self.engine.disks[index]
        path = getattr(disk, "inner", disk).root
        self.engine.disks[index] = None
        shutil.rmtree(path)
        with open(path, "w"):
            pass
        self.pulled.append((index, path))

    def still_pulled(self) -> bool:
        return all(self.engine.disks[i] is None and not os.path.isdir(p)
                   for i, p in self.pulled)

    # -- the program's own account -------------------------------------------

    def counters(self) -> dict:
        from minio_tpu.parallel import scheduler as sched
        from minio_tpu.utils import eventlog
        stages = {}
        for key, (_b, total, n) in \
                sched._DISPATCH_STAGE_SECONDS.series_snapshot().items():
            lab = dict(key)
            stages[f"{lab['verb']}.{lab['stage']}"] = [n, total]
        st = self.node.scheduler.stats()
        from minio_tpu.utils import healthtrack
        gray = {
            "abandoned_writes": {dict(k)["stage"]: int(v) for k, v in
                                 healthtrack._LAGGARDS.series().items()},
            "hedged_reads": {dict(k)["trigger"]: int(v) for k, v in
                             healthtrack._HEDGED.series().items()}}
        return {"verbs": st["verbs"], "stages": stages, "gray_lane": gray,
                "decode_by_lost": dict(self.decode_by_lost),
                "declines": [e["attrs"] for e in eventlog.JOURNAL.recent(
                    classes={"device.decline"})],
                "mrf": self.node.sets.mrf_stats()}

    def drive_paths(self) -> list[str]:
        return [os.path.join(self.root, f"d{j + 1}")
                for j in range(self.config["drives"])]

    def shutdown(self) -> None:
        self.node.shutdown()
