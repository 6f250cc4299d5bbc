"""Sanitizer gate for the native C++ library (SURVEY §4: a
TSAN/ASAN-equivalent for the C++ pieces; the reference runs every Go
test under -race, buildscripts/race.sh).

`make -C native sanitize` builds libminio_tpu_native_san.so with
-fsanitize=address,undefined (no recover), and the test runs the GF and
HighwayHash identity matrices against the pure-Python oracles *inside a
subprocess* that LD_PRELOADs the sanitizer runtimes — the GFNI/portable
kernels do raw pointer arithmetic over caller buffers, which is exactly
what ASan/UBSan police. A sanitizer report aborts the subprocess, so a
nonzero exit fails the test.

Run explicitly with `pytest -m native` (included in the default run
too; it skips itself when g++/libasan are absent).
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")
SANLIB = os.path.join(NATIVE, "libminio_tpu_native_san.so")

CHILD = r"""
import ctypes, os, sys
import numpy as np

sys.path.insert(0, os.environ["MINIO_TPU_REPO"])
import minio_tpu.utils.native as native
native._LIB_PATH = os.environ["MINIO_TPU_SANLIB"]

from minio_tpu.ops import gf256
from minio_tpu.ops.highwayhash_py import HighwayHash

assert native.available(), "sanitized library failed to load"
rng = np.random.default_rng(7)

# GF(2^8) matmul: portable (1) and, where the host supports it, GFNI (2)
# paths vs the table oracle, over shapes that stress tail handling
for force in ([1, 2] if native.has_gfni() else [1]):
    for r, k, L in [(4, 12, 1000), (2, 4, 1), (4, 16, 4096),
                    (6, 10, 65543), (1, 1, 17)]:
        m = rng.integers(0, 256, (r, k), dtype=np.uint8)
        d = rng.integers(0, 256, (k, L), dtype=np.uint8)
        got = native.gf_matmul(m, d, force_path=force)
        want = gf256.gf_matmul(m, d)
        assert np.array_equal(got, want), f"gf mismatch {force} {r},{k},{L}"

# HighwayHash-256 single-shot vs pure-python oracle, edge lengths
key = bytes(range(32))
for n in [0, 1, 31, 32, 33, 63, 64, 100, 1029, 4096]:
    data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    h = HighwayHash(key); h.update(data)
    assert native.hh256(key, data) == h.digest256(), f"hh256 len {n}"
    assert native.hh64(key, data) == h.digest64(), f"hh64 len {n}"

# batched rows (strided access in C++)
shards = rng.integers(0, 256, (5, 1029), dtype=np.uint8)
got = native.hh256_batch(key, shards)
for i in range(5):
    h = HighwayHash(key); h.update(shards[i].tobytes())
    assert got[i].tobytes() == h.digest256(), f"batch row {i}"

# rows by address (each its own bytes-backed buffer, odd length)
rows = [np.frombuffer(rng.integers(0, 256, 1029, dtype=np.uint8).tobytes(),
                      dtype=np.uint8) for _ in range(3)]
got = native.hh256_rows(key, rows)
for i, row in enumerate(rows):
    assert got[i].tobytes() == native.hh256(key, row), f"rows row {i}"
out = np.zeros(3 * 1029, dtype=np.uint8)
native.gather_rows(rows, out)
assert np.array_equal(out.reshape(3, 1029), np.stack(rows)), "gather"

# streaming API consistency with single-shot (state layout: 128 bytes,
# update size in bytes — see bitrot._NativeHH256)
lib = native.get_lib()
state = np.zeros(128, dtype=np.uint8)
kb = np.frombuffer(key, dtype=np.uint8)
lib.hh_init(native._u8p(kb), native._u8p(state))
data = rng.integers(0, 256, 96, dtype=np.uint8)
lib.hh_update_packets(native._u8p(state), native._u8p(data), 96)
tail = rng.integers(0, 256, 7, dtype=np.uint8)
out = np.zeros(32, dtype=np.uint8)
lib.hh_final256(native._u8p(state), native._u8p(tail), 7,
                native._u8p(out))
assert out.tobytes() == native.hh256(
    key, np.concatenate([data, tail])), "streaming mismatch"

# snappy block codec: roundtrip fuzz + CRC32C vectors under ASan/UBSan
# (match finding does raw pointer walks over caller buffers)
assert native.crc32c(b"123456789") == 0xE3069283
import random as _random
_rng = _random.Random(11)
for _trial in range(40):
    n = _rng.randrange(0, 65536)
    base = bytes(_rng.randrange(256)
                 for _ in range(_rng.randrange(1, 200)))
    blob = (base * (n // max(len(base), 1) + 1))[:n]
    if _rng.random() < 0.5:
        blob = bytes(_rng.randrange(256) for _ in range(n))
    comp = native.snappy_compress_block(blob)
    assert native.snappy_uncompress_block(comp) == blob, n
# corrupt inputs must error, not overrun
for bad in (b"", b"\xff" * 12, b"\x05\x00", b"\x04\x08ab\x01\x09"):
    try:
        native.snappy_uncompress_block(bad)
    except (ValueError, NotImplementedError):
        pass
    else:
        raise AssertionError(f"corrupt block accepted: {bad!r}")
print("sanitized identity matrices OK")
"""


def _sanitizer_runtimes() -> list[str]:
    libs = []
    for name in ("libasan.so", "libubsan.so"):
        try:
            p = subprocess.run(["g++", f"-print-file-name={name}"],
                               capture_output=True, text=True,
                               timeout=30).stdout.strip()
        except Exception:
            return []
        if not p or p == name or not os.path.exists(p):
            return []
        libs.append(p)
    return libs


@pytest.mark.native
def test_native_library_under_asan_ubsan():
    runtimes = _sanitizer_runtimes()
    if not runtimes:
        pytest.skip("g++ sanitizer runtimes not available")
    build = subprocess.run(["make", "-C", NATIVE, "-s", "sanitize"],
                           capture_output=True, text=True, timeout=300)
    # the toolchain is present (runtimes check above) — a build failure
    # is a regression in the C++ sources, not an environment gap
    assert build.returncode == 0, \
        f"sanitized build failed: {build.stderr[-1500:]}"

    env = dict(os.environ)
    env["LD_PRELOAD"] = " ".join(runtimes)
    # Python itself leaks by design; leak checking would drown real
    # findings. halt_on_error keeps genuine reports fatal.
    env["ASAN_OPTIONS"] = "detect_leaks=0,halt_on_error=1"
    env["UBSAN_OPTIONS"] = "halt_on_error=1,print_stacktrace=1"
    env["MINIO_TPU_REPO"] = REPO
    env["MINIO_TPU_SANLIB"] = SANLIB
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"sanitized run failed\nstdout:\n{proc.stdout[-2000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    assert "identity matrices OK" in proc.stdout
