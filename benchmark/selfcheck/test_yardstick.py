"""Self-checks of the yardstick, runnable without a chip:

    python3 -m pytest benchmark/selfcheck -q

The byte functions against hand-worked 12+4 and 8+8 values, the trace
reduction on a synthetic trace and on a small trace recorded on a v5e,
and the plain reference against public known answers.
"""

import itertools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import (harness, janitor, readers, reference,  # noqa: E402
                      tracered, traffic, workbytes)

MIB4 = 1 << 22


# -- bytes ------------------------------------------------------------------

@pytest.mark.parametrize("k,m,shard,enc,dec", [
    # 12+4: S = ceil(4194304 / 12) = 349526
    #   encode: 12*349526 in + 4*349526 out + 16*32 digests = 5592928
    #   decode r=1: 12*349526 in + 349526 out + 12*32 digests = 4544222
    (12, 4, 349526, 5592928, 4544222),
    # 8+8: S = 524288
    #   encode: 8*524288 + 8*524288 + 16*32 = 8389120
    #   decode r=1: 8*524288 + 524288 + 8*32 = 4718848
    (8, 8, 524288, 8389120, 4718848),
])
def test_bytes_hand_worked(k, m, shard, enc, dec):
    assert workbytes.shard_size(MIB4, k) == shard
    assert workbytes.encode_bytes(1, k, m, MIB4) == enc
    assert workbytes.decode_bytes(1, k, 1, MIB4) == dec
    assert workbytes.verb_bytes("encode", 32, k, m, MIB4) == 32 * enc
    assert workbytes.verb_bytes("decode", 8, k, m, MIB4) == 8 * dec


def test_decode_bytes_follow_the_lost_shards_launched():
    # 12+4, 80 dispatched blocks; of the blocks submitted, 3 in 4 had
    # r = 1 (the pulled drive) and 1 in 4 r = 2 (a hedge beside it):
    #   r=1: 4544222 a block; r=2: 4544222 + 349526 = 4893748
    #   80 * (0.75 * 4544222 + 0.25 * 4893748) = 370528280
    assert workbytes.verb_bytes("decode", 80, 12, 4, MIB4,
                                {1: 60, 2: 20}) == 370528280
    assert workbytes.verb_bytes("decode", 80, 12, 4, MIB4,
                                {"1": 6, "2": 2}) == 370528280
    # nothing counted: r = 1, the least a degraded read can have
    assert workbytes.verb_bytes("decode", 80, 12, 4, MIB4, {}) \
        == 80 * 4544222


def test_hbm_share_arithmetic():
    # 32 encoded 12+4 blocks = 178973696 bytes = 0.218527... ms at
    # 819 GB/s; over 10 ms of device busy time that is 2.185 %
    win = {"verb": "encode", "geometry": {"k": 12, "m": 4,
                                          "block_size": MIB4},
           "peak": {"hbm_bytes_per_s": 819e9},
           "trace": {"busy_s": 0.010, "blocks": {"encode": 32}}}
    assert readers.hbm_share(win, "encode") == pytest.approx(
        100 * (178973696 / 819e9) / 0.010)
    assert readers.hbm_share(win, "decode") is None      # another verb
    win["trace"]["blocks"]["encode"] = 0
    assert readers.hbm_share(win, "encode") is None      # nothing to read


# -- the trace reduction ------------------------------------------------------

SYNTHETIC = [      # (line, op, start ns, duration ns)
    ("XLA Modules", "jit_put_step", 1000, 900),    # covers the two below
    ("XLA Ops", "fusion.1", 1000, 400),
    ("XLA Ops", "custom-call.gf", 1500, 400),
    ("XLA Ops", "fusion.1", 3000, 500),            # gap 1900 -> 3000
    ("XLA Ops", "copy.2", 3400, 600),              # overlaps: ends 4000
]


def test_busy_union_counts_overlap_once():
    busy, merged = tracered.busy_union(SYNTHETIC)
    assert busy == 900 + 1000
    assert [(s, e) for s, e, _a, _b in merged] == [(1000, 1900),
                                                   (3000, 4000)]


def test_reduce_synthetic():
    red = tracered.reduce_planes({"/device:TPU:0": SYNTHETIC})
    assert red["busy_s"] == pytest.approx(1900e-9)
    assert red["window_s"] == pytest.approx(3000e-9)     # 1000 .. 4000
    idle_share = 1 - red["busy_s"] / red["window_s"]
    assert idle_share == pytest.approx(1100 / 3000)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(900e-9)]
    assert red["device_ops"][1] == ["copy.2", pytest.approx(600e-9)]
    assert red["idle_gaps"] == [
        ["after custom-call.gf before fusion.1", pytest.approx(1100e-9)]]
    assert red["outside_s"] == 0 and red["events_span_s"] == red["window_s"]


def test_reduce_cuts_events_to_the_marked_stretch():
    """The profiler records before and after the stretch the counters
    cover; busy time and the window are both taken inside the mark."""
    # mark 1200 .. 3200: fusion.1 loses its first 200, the module its
    # first 200 too (union 1200..1900 = 700); the second fusion.1 keeps
    # 3000..3200 (200); copy.2 is wholly outside
    red = tracered.reduce_planes({"/device:TPU:0": SYNTHETIC},
                                 clip=(1200, 3200))
    assert red["busy_s"] == pytest.approx(900e-9)
    assert red["window_s"] == pytest.approx(2000e-9)
    assert red["outside_s"] == pytest.approx(1000e-9)    # 1900 - 900
    assert red["events_span_s"] == pytest.approx(2000e-9)
    assert dict(red["device_ops"]) == {          # copy.2 is not listed
        "custom-call.gf": pytest.approx(400e-9),
        "fusion.1": pytest.approx(400e-9)}       # 200 of each of its two
    assert red["idle_gaps"] == [
        ["after custom-call.gf before fusion.1", pytest.approx(1100e-9)]]
    # a mark wider than the events: the edges are idle time, listed
    wide = tracered.reduce_planes({"/device:TPU:0": SYNTHETIC},
                                  clip=(0, 10000))
    assert wide["busy_s"] == pytest.approx(1900e-9)
    assert wide["window_s"] == pytest.approx(1e-5) and wide["outside_s"] == 0
    assert wide["idle_gaps"][0] == ["after copy.2 before window end",
                                    pytest.approx(6000e-9)]
    # busy never exceeds the window it is cut to
    for lo, hi in ((0, 1500), (1400, 1450), (1900, 3000), (3900, 9000)):
        r = tracered.reduce_planes({"/device:TPU:0": SYNTHETIC},
                                   clip=(lo, hi))
        assert 0 <= r["busy_s"] <= r["window_s"] == pytest.approx(
            (hi - lo) / 1e9)


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError):
        tracered.reduce_planes({})


def test_recorded_trace():
    """Three 8+8 put_step launches (64 KiB blocks), 20 ms apart, recorded on a TPU v5
    lite (benchmark/selfcheck/small_trace.xplane.pb.gz)."""
    path = os.path.join(HERE, "small_trace.xplane.pb.gz")
    planes = tracered.load_xplane(path)
    assert list(planes) == ["/device:TPU:0"]
    red = tracered.reduce_planes(planes)
    assert 0 < red["busy_s"] < red["window_s"]
    # the launches are 20 ms apart: the device is mostly idle
    assert 1 - red["busy_s"] / red["window_s"] > 0.5
    assert red["device_ops"] and all(s > 0 for _n, s in red["device_ops"])
    assert len(red["idle_gaps"]) >= 2
    summed = sum(e[3] for e in planes["/device:TPU:0"]) / 1e9
    assert red["busy_s"] <= summed          # a union never exceeds a sum
    # the recording predates the harness's mark. Its host plane is on
    # the device plane's clock, which is what cutting to a host-side
    # annotation relies on: both count from the profile's start, and the
    # first launch's host event and its device events lie within 2 ms
    # of each other (the device's stamps lead by about 1 ms — 0.1 % of a
    # 2 s stretch)
    assert tracered.find_mark(path) is None
    launch = tracered.find_mark(path, "PjitFunction(put_step)")
    evs = planes["/device:TPU:0"]
    assert launch is not None
    assert abs(min(e[2] for e in evs) - launch[0]) < 2e6
    # cut to the middle of the recording: less busy time, in a window
    lo = min(e[2] for e in evs)
    hi = max(e[2] + e[3] for e in evs)
    mid = tracered.reduce_planes(planes, clip=(lo + (hi - lo) / 4,
                                               hi - (hi - lo) / 4))
    assert 0 < mid["busy_s"] < red["busy_s"]
    assert mid["window_s"] == pytest.approx((hi - lo) / 2e9)
    assert mid["outside_s"] == pytest.approx(red["busy_s"] - mid["busy_s"])


def test_the_mark_is_read_back_from_a_profile(tmp_path):
    """The harness's annotation, written by the profiler itself (XLA-CPU
    here: no device plane, the host plane is what is read)."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    time.sleep(0.05)                       # the profiler's lead-in
    with jax.profiler.TraceAnnotation(tracered.WINDOW_MARK):
        time.sleep(0.2)
    time.sleep(0.05)
    jax.profiler.stop_trace()
    lo, hi = tracered.find_mark(tracered.find_xplane(str(tmp_path)))
    assert lo >= 0.05e9 and 0.2e9 <= hi - lo < 0.25e9


# -- the plain reference --------------------------------------------------------

PI_100_DECIMALS = (
    "1415926535897932384626433832795028841971693993751058209749445923078164"
    "062862089986280348253421170679")


def test_highwayhash_known_answer():
    """Upstream's bitrot key IS a HighwayHash-256 known answer: the hash
    of the first 100 decimals of pi under a zero key (cmd/bitrot.go)."""
    msg = np.frombuffer(PI_100_DECIMALS.encode(), np.uint8)[None, :]
    assert reference.hh256_many(msg, bytes(32))[0].tobytes() \
        == reference.BITROT_KEY


def test_highwayhash_streams_are_independent():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 256, (5, 150), dtype=np.uint8)   # 4 packets + 22
    many = reference.hh256_many(x)
    for i in range(5):
        assert np.array_equal(reference.hh256_many(x[i:i + 1])[0], many[i])
    assert len({m.tobytes() for m in many}) == 5


@pytest.mark.parametrize("k,m", [(12, 4), (8, 8)])
def test_rs_matrix_is_systematic_and_mds(k, m):
    mat = reference.encode_matrix(k, m)
    assert [row for row in mat[:k]] == [
        [int(i == j) for j in range(k)] for i in range(k)]
    rng = np.random.default_rng(k)
    data = rng.integers(0, 256, (k, 64), dtype=np.uint8)
    full = np.concatenate([data, reference.rs_rows(mat[k:], data)])
    # any k of the n shards give the data back
    for keep in itertools.islice(
            itertools.combinations(range(k + m), k), 0, 4000, 97):
        inv = reference._mat_inv([mat[i] for i in keep])
        assert np.array_equal(reference.rs_rows(inv, full[list(keep)]),
                              data)


def test_part_file_framing_and_placement():
    body = np.random.default_rng(9).bytes(2 * 4096)
    files = reference.part_files(body, 12, 4, 4096)
    s = reference.shard_size(4096, 12)                   # 342
    assert len(files) == 16 and all(len(f) == 2 * (32 + s) for f in files)
    # data shard 0, block 0: the digest, then the first S body bytes
    assert files[0][32:32 + s] == body[:s]
    assert files[0][:32] == reference.hh256_many(
        np.frombuffer(body[:s], np.uint8)[None, :])[0].tobytes()
    # the last data shard ends with the block's zero padding
    assert files[11][32:32 + s] == body[11 * s:4096] + bytes(12 * s - 4096)
    order = reference.hash_order("bench/some-key", 16)
    assert sorted(order) == list(range(1, 17))
    assert order == order[:1] + [1 + (order[0] - 1 + i) % 16
                                 for i in range(1, 16)]


def test_frames_are_the_part_files_block_by_block():
    body = np.random.default_rng(4).bytes(4 * 4096)
    files = reference.part_files(body, 8, 8, 4096)
    data = reference.split_blocks(body, 4096, 8)
    some = reference.frames(data[[1, 3]], 8)             # blocks 1 and 3
    frame = 32 + reference.shard_size(4096, 8)
    for i, b in enumerate((1, 3)):
        for shard in range(16):
            assert some[i, shard].tobytes() \
                == files[shard][b * frame:(b + 1) * frame]


def test_blocks_to_check_spread_over_the_object():
    for seed in (1, 2**31 + 7):
        picks = traffic.blocks_to_check(seed, "put/ab-c0-00008", 16, 2)
        assert len(picks) == 2 and 0 <= picks[0] < 8 <= picks[1] < 16
        assert picks == traffic.blocks_to_check(seed, "put/ab-c0-00008",
                                                16, 2)
    assert traffic.blocks_to_check(1, "k", 16, 16) == list(range(16))


# -- the drive tree: this checkout's alone, and never left behind -------------

def test_stale_trees_of_dead_runs_are_reclaimed_and_live_ones_kept(tmp_path):
    base = tmp_path / "minio_tpu_bench-abc"
    dead = base / "run-999999999"          # no such process
    mine = base / f"run-{os.getpid()}"     # a pid the kernel gave again
    other = base / "not-a-run"
    for d in (dead, mine, other):
        (d / "d1").mkdir(parents=True)
    live = base / f"run-{os.getppid()}"
    live.mkdir()
    alive = harness._run_alive
    try:
        harness._run_alive = lambda pid: pid == os.getppid()
        gone = harness.reclaim_stale(str(base))
    finally:
        harness._run_alive = alive
    assert sorted(gone) == sorted([dead.name, mine.name])
    assert not dead.exists() and not mine.exists()
    assert live.exists() and other.exists()
    assert harness.reclaim_stale(str(tmp_path / "absent")) == []


def test_two_checkouts_never_share_a_drive_base(monkeypatch, tmp_path):
    monkeypatch.setenv("TMPDIR", str(tmp_path))    # a disk: not taken
    a = harness.drive_base()
    monkeypatch.setattr(harness, "REPO", "/somewhere/else")
    b = harness.drive_base()
    assert a != b and os.path.dirname(a) == os.path.dirname(b) \
        == harness.SHM
    monkeypatch.setattr(harness, "_is_tmpfs", lambda p: True)
    assert os.path.dirname(harness.drive_base()) == str(tmp_path)
    assert harness._is_tmpfs.__name__ == "<lambda>"


def _janitor(root, keep_one_in=8):
    spec = {"root": str(root), "drives": [str(root / "d1"), str(root / "d2")],
            "prefix_dir": "bench/put", "keep_one_in": keep_one_in,
            "min_age_s": 0.0}
    return subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(HERE), "benchlib",
                                      "janitor.py"), json.dumps(spec)],
        stdin=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _tree(root):
    for d in ("d1", "d2"):
        for n in (8, 9):
            (root / d / "bench" / "put" / f"ab-c0-{n:05d}").mkdir(
                parents=True)


def test_janitor_expires_objects_and_leaves_the_tree_when_told_to_stop(
        tmp_path):
    _tree(tmp_path)
    p = _janitor(tmp_path)
    deadline = time.time() + 10
    while (tmp_path / "d1/bench/put/ab-c0-00009").exists() \
            and time.time() < deadline:
        time.sleep(0.1)
    p.stdin.write("stop\n")
    p.stdin.close()
    assert p.wait(timeout=10) == 0
    for d in ("d1", "d2"):
        assert (tmp_path / d / "bench/put/ab-c0-00008").exists()   # kept
        assert not (tmp_path / d / "bench/put/ab-c0-00009").exists()
    assert janitor.kept("ab-c0-00009", 0) and janitor.kept("x-00009", 1)


def test_janitor_removes_the_tree_when_the_harness_dies(tmp_path):
    root = tmp_path / "run-1"
    _tree(root)
    p = _janitor(root, keep_one_in=0)
    p.stdin.close()                       # the harness is gone: no "stop"
    assert p.wait(timeout=10) == 0
    assert not root.exists()
    assert "harness is gone" in p.stderr.read()
