"""The closed ladder of launch sizes (parallel/ladder.py): the rung
function's shape, padded launches through one former byte-identical to
the plain reference and to the unpadded codec call for every B in
1..cap, pad blocks that no future and no counter ever sees, a closed
set of programs, and boot's load of the encode rungs."""

from __future__ import annotations

import hashlib
import os
import sys
import time

import jax.monitoring
import numpy as np
import pytest

from minio_tpu import bitrot as bitrot_mod
from minio_tpu.features import crypto as sse
from minio_tpu.models import pipeline
from minio_tpu.object import codec as codec_mod
from minio_tpu.object import engine as engine_mod
from minio_tpu.object import healing as healing_mod
from minio_tpu.object.codec import Codec
from minio_tpu.ops import rs_matrix
from minio_tpu.parallel import ladder
from minio_tpu.parallel import scheduler as sched_mod
from minio_tpu.parallel.scheduler import BatchScheduler
from minio_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from benchlib import reference  # noqa: E402

HH = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S
SHA = bitrot_mod.BitrotAlgorithm.SHA256
CAP = sched_mod.MAX_BATCH_BLOCKS
# 12+4 at a block size that leaves S = 346 (not lane-aligned, like
# 349526) and 8+8 at S = 512 (lane-aligned, like 524288)
GEOMETRIES = {"12+4": (12, 4, 346), "8+8": (8, 8, 512)}
SHAPES = [(8, 32), (8, 24), (4, 32), (8, 64), (1, 8), (3, 10), (8, 4)]


_BUILT: list = []        # fun_name of every program this process built


def _on_compile(event: str, _secs: float, fun_name: str = "", **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _BUILT.append(fun_name)


jax.monitoring.register_event_duration_secs_listener(_on_compile)


@pytest.fixture()
def built():
    """-> how many programs of a jitted function were built (compiled,
    or loaded from a compile cache) since the test began; `.steps()`:
    the names of those that take or make device arrays of a launch
    (the fused steps, and anything that would cut their outputs)."""
    start = len(_BUILT)

    def count(name):
        return _BUILT[start:].count(f"jit({name})")
    count.steps = lambda: {n for n in _BUILT[start:]
                           if "step" in n or "head" in n or "cut" in n}
    return count


@pytest.fixture()
def device_codec(monkeypatch):
    """Force the codec's device route (runs on the CPU jax backend)."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    return codec_mod


# ---------------------------------------------------------------------------
# the rung function
# ---------------------------------------------------------------------------

def test_defaults_are_the_documented_ladder():
    assert (engine_mod.ENCODE_BATCH_BLOCKS, engine_mod.GET_BATCH_BLOCKS,
            healing_mod.HEAL_BATCH_BLOCKS, CAP) == (8, 8, 8, 32)
    for verb in ("encode", "decode", "recover"):
        assert ladder.rungs_of(verb) == (1, 2, 4, 6, 8, 12, 16, 20, 24, 32)


@pytest.mark.parametrize("verb", ["encode", "decode", "recover"])
def test_at_most_ten_rungs_a_verb(verb):
    assert len(ladder.rungs_of(verb)) <= 10


@pytest.mark.parametrize("group,cap", SHAPES)
def test_every_multiple_of_the_group_is_a_rung(group, cap):
    rungs = ladder.rungs(group, cap)
    assert set(range(group, cap + 1, group)) <= set(rungs)
    assert rungs[0] == 1 and rungs[-1] == cap


@pytest.mark.parametrize("group,cap", SHAPES)
def test_rung_is_monotone_and_idempotent(group, cap, monkeypatch):
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", group)
    at = [ladder.rung("encode", b, cap) for b in range(1, cap + 1)]
    assert all(r >= b for b, r in zip(range(1, cap + 1), at))
    assert at == sorted(at)
    assert [ladder.rung("encode", r, cap) for r in at] == at
    assert set(at) == set(ladder.rungs(group, cap))


@pytest.mark.parametrize("group,cap", SHAPES)
def test_a_launch_pads_by_at_most_a_third(group, cap, monkeypatch):
    monkeypatch.setattr(engine_mod, "ENCODE_BATCH_BLOCKS", group)
    for b in range(2, cap + 1):
        assert 3 * (ladder.rung("encode", b, cap) - b) <= b, b


def test_a_lone_group_over_the_cap_rounds_to_the_group():
    assert ladder.rung("encode", 33, 32) == 40
    assert ladder.rung("encode", 40, 32) == 40


def test_the_rungs_follow_the_programs_constants(monkeypatch):
    monkeypatch.setattr(engine_mod, "GET_BATCH_BLOCKS", 3)
    assert ladder.rungs_of("decode", 12) == (1, 2, 3, 4, 6, 9, 12)
    assert ladder.rungs_of("encode", 12) == (1, 2, 4, 6, 8, 12)


# ---------------------------------------------------------------------------
# padded launches through one former
# ---------------------------------------------------------------------------

def _digests(rows: np.ndarray, algo) -> np.ndarray:
    """(N, S) -> (N, 32) by the plain reference (HighwayHash-256 under
    the bitrot key) or hashlib (SHA-256)."""
    if algo is SHA:
        return np.stack([np.frombuffer(hashlib.sha256(r.tobytes()).digest(),
                                       np.uint8) for r in rows])
    return reference.hh256_many(np.ascontiguousarray(rows))


def _blocks(seed: int, b: int, k: int, s: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, (b, k, s), dtype=np.uint8)


@pytest.fixture(scope="module")
def encode_want():
    """Reference parity + digests of CAP blocks a geometry and hash,
    computed once: launch B uses its first B blocks."""
    cache: dict = {}

    def want(geometry: str, algo):
        if (geometry, algo) not in cache:
            k, m, s = GEOMETRIES[geometry]
            data = _blocks(k * 1000 + s, CAP, k, s)
            parity = reference.rs_rows(
                reference.encode_matrix(k, m)[k:], data)
            full = np.concatenate([data, parity], axis=1)
            cache[geometry, algo] = data, parity, _digests(
                full.reshape(-1, s), algo).reshape(CAP, k + m, 32)
        return cache[geometry, algo]
    return want


@pytest.mark.parametrize("algo", [HH, SHA], ids=["hh256", "sha256"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_encode_launch_of_every_b_matches_the_reference(
        device_codec, encode_want, built, geometry, algo):
    k, m, s = GEOMETRIES[geometry]
    data, parity, digests = encode_want(geometry, algo)
    codec = Codec(k, m, k * s)
    sched = BatchScheduler(max_wait=0.001)
    try:
        for b in range(1, CAP + 1):
            before = sched.stats()["verbs"]["encode"]
            got_p, got_d = sched.submit(codec, data[:b], algo).result(60)
            assert got_p.shape == (b, m, s) and got_d.shape == (b, k + m, 32)
            assert np.array_equal(got_p, parity[:b]), b
            assert np.array_equal(got_d, digests[:b]), b
            # the same bytes as the codec alone, padded there or not
            alone_p, alone_d = codec.encode_and_hash_batch(data[:b], algo)
            assert np.array_equal(alone_p, got_p)
            assert np.array_equal(alone_d, got_d)
            after = sched.stats()["verbs"]["encode"]
            rung = ladder.rung("encode", b)
            assert after["blocks"] - before["blocks"] == b
            assert after["pad_blocks"] - before["pad_blocks"] == rung - b
            # the rung's whole result crosses back, pad rows and all
            assert after["fetched_bytes"] - before["fetched_bytes"] \
                == (got_p.nbytes + got_d.nbytes) * rung // b
            assert after["staged_bytes"] - before["staged_bytes"] \
                == (0 if rung == b else rung * k * s)
        assert sched.stats()["dispatched_blocks"] == CAP * (CAP + 1) // 2
    finally:
        sched.close()
    # every B in 1..cap launched, by the former and by the codec alone:
    # the process holds one program a rung, not one a block count
    assert built("put_step") == len(ladder.rungs_of("encode"))
    # and no program beside the step: a padded launch's pad rows are
    # cut off the host's view
    assert built.steps() == {"jit(put_step)"}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_cut_of_a_padded_launch_is_a_view_of_the_steps_output(geometry):
    """What replaced the cut program: the step's `out_info` at a rung
    says what crosses back — parity as 32-bit words, S rounded up to
    the word — and `host_rows(...)[:n]` of an array of that form is
    the launch's (n, m, S) uint8 with no byte copied."""
    import jax
    k, m, s = GEOMETRIES[geometry]
    lowered = pipeline.put_step.lower(
        jax.ShapeDtypeStruct((6, k, s), np.uint8), k, m, algo="highwayhash")
    parity, digests = lowered.out_info
    assert (parity.dtype, parity.shape) == (np.uint32, (6, m, -(-s // 4)))
    assert (digests.dtype, digests.shape) == (np.uint8, (6, k + m, 32))
    crossed = np.arange(6 * m * -(-s // 4), dtype=np.uint32
                        ).reshape(parity.shape)
    cut = pipeline.host_rows(crossed, s)[:5]
    assert (cut.dtype, cut.shape) == (np.uint8, (5, m, s))
    assert np.shares_memory(cut, crossed)
    assert cut.tobytes() == crossed.view(np.uint8)[:5, :, :s].tobytes()
    dig = np.zeros(digests.shape, np.uint8)
    assert pipeline.host_rows(dig, s) is dig


def test_program_signatures_are_the_rung_set(device_codec, monkeypatch):
    k, m, s = 6, 3, 130
    codec = Codec(k, m, k * s)
    seen = set()
    real = pipeline.put_step

    def put_step(data, *a, **kw):
        seen.add(tuple(data.shape))
        return real(data, *a, **kw)
    monkeypatch.setattr(pipeline, "put_step", put_step)
    sched = BatchScheduler(max_wait=0.001)
    try:
        for b in range(1, CAP + 1):
            assert sched.submit(codec, _blocks(b, b, k, s), HH
                                ).result(60)[0].shape[0] == b
    finally:
        sched.close()
    assert seen == {(r, k, s) for r in ladder.rungs_of("encode")}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_gathered_groups_resolve_with_their_own_blocks(
        device_codec, encode_want, geometry):
    """Groups of 2, 1 and 2 blocks fuse into one launch of 5 at rung 6:
    each future gets exactly its blocks, and the pad block none."""
    k, m, s = GEOMETRIES[geometry]
    data, parity, digests = encode_want(geometry, HH)
    codec = Codec(k, m, k * s)
    sched = BatchScheduler(max_wait=0.3)
    try:
        cuts = [(0, 2), (2, 3), (3, 5)]
        futs = [sched.submit(codec, data[a:b], HH) for a, b in cuts]
        for (a, b), fut in zip(cuts, futs):
            got_p, got_d = fut.result(60)
            assert np.array_equal(got_p, parity[a:b])
            assert np.array_equal(got_d, digests[a:b])
        st = sched.stats()["verbs"]["encode"]
        assert (st["batches"], st["coalesced"], st["blocks"],
                st["pad_blocks"]) == (1, 2, 5, 1)
        assert st["staged_bytes"] == 6 * k * s
        assert st["fetched_bytes"] == 6 * (m * s + (k + m) * 32)
    finally:
        sched.close()


def test_transfer_span_says_rung_and_pad(device_codec):
    k, m, s = GEOMETRIES["8+8"]
    codec = Codec(k, m, k * s)
    sched = BatchScheduler(max_wait=0.001)
    try:
        with telemetry.trace("test.root") as root:
            sched.submit(codec, _blocks(3, 3, k, s), HH).result(60)
        (transfer,) = [sp for sp in root.walk()
                       if sp.name == "sched.transfer"]
        assert transfer.attrs["rung"] == 4
        assert transfer.attrs["pad_blocks"] == 1
        assert transfer.attrs["bytes"] == 4 * k * s
    finally:
        sched.close()


def _survivors(geometry: str, lost: tuple, b: int):
    k, m, s = GEOMETRIES[geometry]
    data = _blocks(len(lost) * 100 + s, b, k, s)
    enc = reference.encode_matrix(k, m)
    full = np.concatenate([data, reference.rs_rows(enc[k:], data)], axis=1)
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    return full, mask


@pytest.mark.parametrize("lost", [(1,), (0, 3)], ids=["r1", "r2"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_decode_launch_of_every_b_matches_the_reference(
        device_codec, geometry, lost):
    k, m, s = GEOMETRIES[geometry]
    full, mask = _survivors(geometry, lost, CAP)
    _dm, used, missing = rs_matrix.missing_data_matrix(k, m, mask)
    assert tuple(missing) == lost
    surv = np.ascontiguousarray(full[:, list(used)])
    want_d = reference.hh256_many(surv.reshape(-1, s)).reshape(CAP, k, 32)
    codec = Codec(k, m, k * s)
    sched = BatchScheduler(max_wait=0.001)
    try:
        for b in range(1, CAP + 1):
            got, idx, dig = sched.submit_decode(
                codec, surv[:b], mask, s, HH).result(60)
            assert list(idx) == list(lost)
            assert got.shape == (b, len(lost), s)
            assert np.array_equal(got, full[:b, list(lost)]), b
            # survivors' digests of the real blocks only: a pad block
            # has no frame to be compared with
            assert np.array_equal(dig, want_d[:b]), b
            alone = codec.verify_and_decode_batch(surv[:b], mask, s, HH)
            assert np.array_equal(alone[0], got)
            assert np.array_equal(alone[2], dig)
        st = sched.stats()["verbs"]["decode"]
        assert st["blocks"] == CAP * (CAP + 1) // 2
        assert st["pad_blocks"] == sum(
            ladder.rung("decode", b) - b for b in range(1, CAP + 1))
    finally:
        sched.close()


@pytest.mark.parametrize("lost", [(2,), (1, 9)], ids=["r1", "r2"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_heal_launch_of_every_b_matches_the_reference(
        device_codec, geometry, lost):
    k, m, s = GEOMETRIES[geometry]
    full, mask = _survivors(geometry, lost, CAP)
    _rec, used, _missing = rs_matrix.recover_matrix(k, m, mask)
    surv = np.ascontiguousarray(full[:, list(used)])
    want_s = reference.hh256_many(surv.reshape(-1, s)).reshape(CAP, k, 32)
    want_o = reference.hh256_many(np.ascontiguousarray(
        full[:, list(lost)]).reshape(-1, s)).reshape(CAP, len(lost), 32)
    codec = Codec(k, m, k * s)
    sched = BatchScheduler(max_wait=0.001)
    try:
        for b in range(1, CAP + 1):
            out, idxs, sdig, odig = sched.submit_recover(
                codec, surv[:b], mask, set(lost), s, HH).result(60)
            assert list(idxs) == list(lost)
            assert np.array_equal(out, full[:b, list(lost)]), b
            assert np.array_equal(sdig, want_s[:b]), b
            assert np.array_equal(odig, want_o[:b]), b
        st = sched.stats()["verbs"]["recover"]
        assert st["blocks"] == CAP * (CAP + 1) // 2
        assert st["pad_blocks"] == sum(
            ladder.rung("recover", b) - b for b in range(1, CAP + 1))
    finally:
        sched.close()


def test_sse_encode_of_two_keys_in_one_padded_launch(device_codec,
                                                     monkeypatch):
    """Two encrypted PUTs under different keys, 2 + 1 blocks, gather
    into one launch of 3 at rung 4: each gets the ciphertext rows of
    its own blocks under its own key, and their parity."""
    monkeypatch.setenv("MINIO_TPU_SSE_CIPHER", "chacha20")
    k, m, block = 4, 2, 1 << 16
    codec = Codec(k, m, block)
    s = codec.shard_size
    rng = np.random.default_rng(29)
    specs = [sse.DeviceSSE(rng.bytes(32), rng.bytes(12)) for _ in range(2)]
    datas = [rng.integers(0, 256, (b, k, s), dtype=np.uint8)
             for b in (2, 1)]
    sched = BatchScheduler(max_wait=0.3)
    try:
        futs = []
        for spec, data in zip(specs, datas):
            keys, nonces = spec.batch_params(0, data.shape[0], block)
            futs.append(sched.submit(codec, data, HH,
                                     sse=(keys, nonces, sse.PKG_SIZE)))
        enc = reference.encode_matrix(k, m)
        for spec, data, fut in zip(specs, datas, futs):
            full, dig = fut.result(60)
            b = data.shape[0]
            assert full.shape == (b, k + m, s) and dig.shape == (b, k + m, 32)
            want = data.reshape(b, -1).copy()
            spec.cpu_encrypt_rows(want, 0)
            assert np.array_equal(full[:, :k].reshape(b, -1), want)
            assert np.array_equal(
                full[:, k:], reference.rs_rows(enc[k:], full[:, :k]))
            assert np.array_equal(dig.reshape(-1, 32), reference.hh256_many(
                np.ascontiguousarray(full).reshape(-1, s)))
        st = sched.stats()["verbs"]["encode"]
        assert (st["batches"], st["blocks"], st["pad_blocks"]) == (1, 3, 1)
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# boot
# ---------------------------------------------------------------------------

def test_a_step_holds_one_copy_of_the_hash_round():
    """What boot pays a program is Python: tracing and lowering the
    step. The HighwayHash round is a function the step calls from
    every unrolled packet, not a copy a packet."""
    import re

    import jax
    data = jax.ShapeDtypeStruct((4, 12, 346), np.uint8)
    text = pipeline.put_step.lower(data, 12, 4, algo="highwayhash").as_text()
    assert len(re.findall(r"func\.func private @_update\b", text)) == 1
    # S = 346: 10 whole packets (unroll 2 on the CPU), a remainder,
    # and the finalize loop's body
    assert len(re.findall(r"call @_update\b", text)) == 4
    assert len(text) < 200_000


@pytest.fixture()
def asked(monkeypatch):
    """What boot asks the codec to load, without loading it."""
    calls: list = []

    def load_encode_program(self, blocks, algo, ragged=False):
        calls.append(("encode", blocks, self.k, self.m, self.shard_size,
                      algo, ragged))
    monkeypatch.setattr(Codec, "load_encode_program", load_encode_program)
    return calls


def _boot(tmp_path):
    from minio_tpu.cluster import start_single
    from tests.test_s3 import CREDS
    return start_single([str(tmp_path / "d{1...6}")], "127.0.0.1", 0,
                        CREDS, parity=2, block_size=1 << 16)


def test_boot_on_a_cpu_host_loads_nothing(tmp_path, asked, built):
    telemetry.SPANS.record_begin()
    try:
        nd = _boot(tmp_path)
        nd.shutdown()
    finally:
        win = telemetry.SPANS.record_end()
    assert asked == []
    assert built("put_step") == 0 and built.steps() == set()
    names = {sp["name"] for sp in win["spans"]}
    assert "node.boot" in names and "boot.load_programs" not in names


def test_boot_on_a_tpu_asks_for_the_encode_rungs_once_each(
        tmp_path, asked, monkeypatch):
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    telemetry.SPANS.record_begin()
    try:
        nd = _boot(tmp_path)
        nd.shutdown()
    finally:
        win = telemetry.SPANS.record_end()
    rungs = ladder.rungs_of("encode")
    assert sorted(c[1] for c in asked) == list(rungs)
    assert {c[0] for c in asked} == {"encode"}
    assert {c[2:5] for c in asked} == {(4, 2, (1 << 16) // 4)}
    assert {c[5] for c in asked} == {bitrot_mod.DEFAULT_BITROT_ALGORITHM}
    # the static row only: the ragged rungs wait for a short block
    assert {c[6] for c in asked} == {False}
    # one program a rung and no other: every block count pads up to one
    assert {ladder.rung("encode", b) for b in range(1, CAP + 1)} \
        == {c[1] for c in asked}
    spans = win["spans"]
    (boot,) = [sp for sp in spans if sp["name"] == "node.boot"]
    (load,) = [sp for sp in spans if sp["name"] == "boot.load_programs"]
    assert load["parent_id"] == boot["span_id"]
    assert (load["attrs"]["row"], load["attrs"]["trigger"]) \
        == ("encode_and_hash_batch", "boot")
    kids = [sp for sp in spans if sp["name"] == "boot.load_program"]
    assert all(sp["parent_id"] == load["span_id"] for sp in kids)
    assert sorted(sp["attrs"]["B"] for sp in kids) == list(rungs)
    assert {(sp["attrs"]["verb"], sp["attrs"]["S"], sp["attrs"]["cached"])
            for sp in kids} == {("encode", (1 << 16) // 4, "resident")}


def test_boot_with_the_mesh_route_on_loads_nothing(asked, monkeypatch):
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    monkeypatch.setattr(codec_mod, "_mesh_active", lambda: object())
    assert ladder.load_encode(Codec(4, 2, 1 << 12), HH) == []
    assert asked == []


def test_a_program_that_does_not_load_does_not_stop_boot(device_codec,
                                                         monkeypatch):
    def load_encode_program(self, blocks, algo, ragged=False):
        if blocks == 6:
            raise RuntimeError("compiler on fire")
    monkeypatch.setattr(Codec, "load_encode_program", load_encode_program)
    with telemetry.trace("test.boot") as root:
        loaded = ladder.load_encode(Codec(4, 2, 1 << 12), HH)
    assert {rec["B"]: rec["cached"] for rec in loaded} == {
        b: "error" if b == 6 else "resident"
        for b in ladder.rungs_of("encode")}
    (bad,) = [sp for sp in root.walk() if sp.error]
    assert bad.attrs["B"] == 6 and root.has_error


def test_a_loaded_program_is_the_one_a_launch_hits(device_codec, built):
    """load_encode compiles without running; the first launch of every
    B after it finds its step in the process, and needs no other."""
    k, m, s = 5, 2, 200
    codec = Codec(k, m, k * s)
    loaded = ladder.load_encode(codec, HH, workers=2)
    rungs = ladder.rungs_of("encode")
    assert sorted(rec["B"] for rec in loaded) == list(rungs)
    assert {rec["cached"] for rec in loaded} <= {"compiled", "hit"}
    # one step a rung, and nothing for a padded block count
    assert built("put_step") == len(rungs)
    assert built.steps() == {"jit(put_step)"}
    assert [rec["cached"] for rec in ladder.load_encode(codec, HH)] \
        == ["resident"] * len(rungs)
    sched = BatchScheduler(max_wait=0.001)
    try:
        for b in range(1, CAP + 1):
            data = _blocks(b, b, k, s)
            alone, dig = codec.encode_and_hash_batch(data, HH)
            parity, dig2 = sched.submit(codec, data, HH).result(60)
            assert parity.shape == (b, m, s) and dig.shape == (b, k + m, 32)
            assert np.array_equal(alone, parity)
            assert np.array_equal(dig, dig2)
    finally:
        sched.close()
    assert built("put_step") == len(rungs)
    assert built.steps() == {"jit(put_step)"}


# ---------------------------------------------------------------------------
# the rungs of the row that carries short blocks: loaded once, by the
# first short block a former sees, never at boot
# ---------------------------------------------------------------------------

@pytest.fixture()
def no_ragged_loads(monkeypatch):
    """Each test starts as a fresh process does: no geometry's ragged
    rungs asked for yet."""
    monkeypatch.setattr(ladder, "_RAGGED", {})


def _loader_done() -> None:
    """The background load has run to its end, its span closed."""
    import threading
    for t in threading.enumerate():
        if t.name == "boot-load-ragged":
            t.join(120)
            assert not t.is_alive()


def _short_group(seed: int, b: int, k: int, s: int, s_t: int):
    data = _blocks(seed, b, k, s)
    data[-1, :, s_t:] = 0
    lengths = np.full(b, s, np.int32)
    lengths[-1] = s_t
    return data, lengths


@pytest.mark.ragged_loads
def test_the_first_short_block_loads_the_ragged_rungs_once(
        device_codec, asked, no_ragged_loads):
    k, m, s = 4, 2, 160
    codec = Codec(k, m, k * s)
    rungs = ladder.rungs_of("encode")
    sched = BatchScheduler(max_wait=0.001)
    telemetry.SPANS.record_begin()
    try:
        # whole groups, with lengths or without: nothing is asked for
        sched.submit(codec, _blocks(1, 2, k, s), HH).result(60)
        sched.submit(codec, _blocks(2, 2, k, s), HH,
                     lengths=np.full(2, s, np.int32)).result(60)
        assert asked == [] and ladder._RAGGED == {}
        # the first launch with a short block asks for every ragged
        # rung; later ones for nothing
        for seed in (3, 4, 5):
            data, lengths = _short_group(seed, 3, k, s, 77)
            parity, digests = sched.submit(codec, data, HH,
                                           lengths=lengths).result(60)
            assert parity.shape == (3, m, s)
        _loader_done()
    finally:
        sched.close()
        win = telemetry.SPANS.record_end()
    assert sorted(c[1] for c in asked) == list(rungs)
    assert {c[6] for c in asked} == {True}
    assert {c[2:5] for c in asked} == {(k, m, s)}
    (load,) = [sp for sp in win["spans"]
               if sp["name"] == "boot.load_programs"]
    assert (load["attrs"]["row"], load["attrs"]["trigger"]) \
        == ("encode_and_hash_batch.ragged", "first_short_block")
    kids = [sp for sp in win["spans"] if sp["name"] == "boot.load_program"]
    assert sorted(sp["attrs"]["B"] for sp in kids) == list(rungs)
    assert all(sp["parent_id"] == load["span_id"] for sp in kids)


@pytest.mark.ragged_loads
def test_a_ragged_launch_waits_for_its_rungs_load(device_codec,
                                                  no_ragged_loads,
                                                  monkeypatch):
    """A launch at a rung whose ragged program is still loading waits
    for that load and for no other rung's."""
    import threading
    k, m, s = 4, 2, 96
    codec = Codec(k, m, k * s)
    gate = threading.Event()
    loading = threading.Event()
    real = Codec.load_encode_program

    def load_encode_program(self, blocks, algo, ragged=False):
        if ragged and blocks == 4:
            loading.set()
            assert gate.wait(60)
        return real(self, blocks, algo, ragged=ragged)
    monkeypatch.setattr(Codec, "load_encode_program", load_encode_program)
    assert ladder.load_encode_ragged(codec, HH)
    assert not ladder.load_encode_ragged(codec, HH)      # once
    assert loading.wait(60)
    data, lengths = _short_group(9, 3, k, s, 50)         # rung 4
    out: list = []
    launch = threading.Thread(target=lambda: out.append(
        codec.encode_and_hash_batch(data, HH, lengths=lengths)))
    launch.start()
    # a launch at another rung is not held up by rung 4's load
    one, one_len = _short_group(10, 1, k, s, 50)
    ladder.await_ragged(codec, HH, 1)
    assert codec.encode_and_hash_batch(one, HH, lengths=one_len) is not None
    launch.join(0.3)
    assert launch.is_alive() and out == []
    gate.set()
    launch.join(60)
    assert not launch.is_alive() and out[0][0].shape == (3, m, s)
    _loader_done()


@pytest.mark.ragged_loads
def test_the_rung_a_launch_waits_for_loads_next(device_codec,
                                                no_ragged_loads,
                                                monkeypatch):
    """The loader takes its rungs largest first, but one a launch is
    waiting for before the rest: the rung of the launch that started
    the load, and any asked for while it runs."""
    import threading
    codec = Codec(4, 2, 4 * 64)
    gate = threading.Event()
    order: list = []

    def load_encode_program(self, blocks, algo, ragged=False):
        order.append(blocks)
        assert gate.wait(60)
    monkeypatch.setattr(Codec, "load_encode_program", load_encode_program)
    assert ladder.load_encode_ragged(codec, HH, want=4)
    deadline = time.monotonic() + 60
    while len(order) < ladder.LOAD_WORKERS and time.monotonic() < deadline:
        time.sleep(0.01)
    assert order[0] == 4 and sorted(order[1:]) == [20, 24, 32]
    waiter = threading.Thread(target=ladder.await_ragged,
                              args=(codec, HH, 2))
    waiter.start()
    while 2 not in ladder._RAGGED[4, 2, 64, HH.value].wanted \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    gate.set()
    waiter.join(60)
    _loader_done()
    assert not waiter.is_alive()
    assert order[4] == 2 and sorted(order) == list(ladder.rungs_of("encode"))


@pytest.mark.ragged_loads
def test_a_loaded_ragged_program_is_the_one_a_launch_hits(
        device_codec, built, no_ragged_loads):
    """The ragged rungs load through the call form a launch uses: after
    the load, launches of every B with a short block build nothing."""
    k, m, s = 5, 2, 136
    codec = Codec(k, m, k * s)
    rungs = ladder.rungs_of("encode")
    ladder.load_encode(codec, HH, workers=2)              # boot
    assert (built("put_step"), built("put_step_ragged")) == (len(rungs), 0)
    assert ladder.load_encode_ragged(codec, HH)
    _loader_done()
    assert built("put_step_ragged") == len(rungs)
    enc = reference.encode_matrix(k, m)
    for b in range(1, CAP + 1):
        data, lengths = _short_group(b, b, k, s, 1 + 5 * b % s)
        parity, digests = codec.encode_and_hash_batch(data, HH,
                                                      lengths=lengths)
        n = int(lengths[-1])
        assert np.array_equal(parity[:-1], reference.rs_rows(
            enc[k:], data[:-1]))
        last = np.ascontiguousarray(data[-1:, :, :n])
        want = np.concatenate(
            [last, reference.rs_rows(enc[k:], last)], axis=1)
        assert np.array_equal(parity[-1, :, :n], want[0, k:])
        assert not parity[-1, :, n:].any()
        assert np.array_equal(digests[-1],
                              reference.hh256_many(want[0]))
    assert (built("put_step"), built("put_step_ragged")) \
        == (len(rungs), len(rungs))
    assert built.steps() == {"jit(put_step)", "jit(put_step_ragged)"}


@pytest.mark.ragged_loads
def test_boot_never_loads_the_ragged_rungs(tmp_path, asked, monkeypatch,
                                           no_ragged_loads):
    """A store that never sees a short block never pays for the row
    that carries them: boot on a TPU asks for the static rungs alone."""
    monkeypatch.setattr(codec_mod, "_device_is_tpu", lambda: True)
    telemetry.SPANS.record_begin()
    try:
        nd = _boot(tmp_path)
        nd.shutdown()
    finally:
        win = telemetry.SPANS.record_end()
    assert asked and not any(c[6] for c in asked)
    assert ladder._RAGGED == {}
    assert [sp["attrs"]["trigger"] for sp in win["spans"]
            if sp["name"] == "boot.load_programs"] == ["boot"]
