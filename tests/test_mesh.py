"""Multi-device mesh path (VERDICT r4 #1): geometry matrix for the
sharded put/get/heal steps on the virtual CPU mesh, plus the serving
integration — Codec and BatchScheduler dispatching through
parallel/mesh.py when more than one device is visible.

Runs under conftest.py's 8-device virtual CPU mesh
(xla_force_host_platform_device_count). Sub-meshes of {2, 4} devices
and explicit (dp, sp) factorizations cover both axes; geometries
include shard counts that do NOT divide the sp axis (the pad-row
digest path) on both the put and get sides.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from minio_tpu import bitrot as bitrot_mod
from minio_tpu.ops import rs_matrix, rs_ref
from minio_tpu.parallel import mesh as pmesh

HH = bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256


def _mesh(n, sp=None):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    return pmesh.make_mesh(n, sp=sp)


def _full(data, k, m):
    """Host oracle: (B, k, S) -> (B, k+m, S) data+parity."""
    return np.concatenate(
        [data, np.stack([rs_ref.encode(d, m)[k:] for d in data])],
        axis=1)


def _rand(b, k, s, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (b, k, s)).astype(np.uint8)


# ---------------------------------------------------------------------------
# sharded_put_step: encode + digest matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev,sp,k,m", [
    (2, None, 4, 2),     # sp=2, n=6 divides
    (4, None, 4, 2),     # sp=4, n=6 does NOT divide -> pad rows
    (8, None, 12, 4),    # sp=8, n=16 divides
    (8, 4, 12, 4),       # dp=2 x sp=4: both axes live
    (8, None, 16, 4),    # sp=8, n=20 does NOT divide -> pad rows
])
def test_sharded_put_matrix(n_dev, sp, k, m):
    mesh = _mesh(n_dev, sp)
    dp, sp_sz = mesh.devices.shape
    b, s = dp * 2, sp_sz * 64
    data = _rand(b, k, s, seed=n_dev * 100 + k)
    darr = pmesh.shard_array(mesh, data, P("dp", None, "sp"))
    parity, digests, _ = pmesh.sharded_put_step(mesh, k, m)(darr)
    parity, digests = np.asarray(parity), np.asarray(digests)
    full = _full(data, k, m)
    assert (parity == full[:, k:]).all()
    assert digests.shape == (b, k + m, 32)
    # every shard's digest against the host bitrot oracle — including
    # the last parity row (the first row dropped by n%sp padding)
    for bi in (0, b - 1):
        for si in (0, k - 1, k, k + m - 1):
            assert digests[bi, si].tobytes() == bitrot_mod.hash_shard(
                full[bi, si], HH), (bi, si)


def test_sharded_put_sha256():
    mesh = _mesh(4)
    k, m = 4, 2
    s = mesh.devices.shape[1] * 64
    data = _rand(2, k, s, seed=7)
    darr = pmesh.shard_array(mesh, data, P("dp", None, "sp"))
    _, digests, _ = pmesh.sharded_put_step(mesh, k, m, "sha256")(darr)
    full = _full(data, k, m)
    want = bitrot_mod.hash_shard(full[0, k],
                                 bitrot_mod.BitrotAlgorithm.SHA256)
    assert np.asarray(digests)[0, k].tobytes() == want


# ---------------------------------------------------------------------------
# sharded_get_step: verify+decode mask matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev,sp,k,m,lost", [
    (4, None, 4, 2, [0, 2]),        # k%sp==0, two data rows lost
    (4, None, 4, 2, [1, 4]),        # data + parity lost
    (8, None, 12, 4, [3]),          # k=12 % sp=8 != 0 -> pad digests
    (8, None, 12, 4, [0, 5, 9, 13]),  # max m losses
    (8, 2, 16, 4, [1, 17]),         # dp=4 x sp=2
])
def test_sharded_get_matrix(n_dev, sp, k, m, lost):
    mesh = _mesh(n_dev, sp)
    dp, sp_sz = mesh.devices.shape
    b, s = dp * 2, sp_sz * 64
    data = _rand(b, k, s, seed=sum(lost) + k)
    full = _full(data, k, m)
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    _, used = rs_matrix.decode_matrix(k, m, mask)
    survivors = np.ascontiguousarray(full[:, list(used), :])
    sarr = pmesh.shard_array(mesh, survivors, P("dp", None, "sp"))
    run, missing = pmesh.sharded_get_step(mesh, k, m, mask)
    out, sdig = run(sarr)
    out, sdig = np.asarray(out), np.asarray(sdig)
    assert list(missing) == [i for i in lost if i < k]
    for row, idx in enumerate(missing):
        assert (out[:, row, :] == full[:, idx, :]).all(), idx
    assert sdig.shape == (b, k, 32)
    for si in (0, k - 1):
        assert sdig[0, si].tobytes() == bitrot_mod.hash_shard(
            survivors[0, si], HH)


# ---------------------------------------------------------------------------
# sharded_heal_step: verify+recover+rehash
# ---------------------------------------------------------------------------

def test_sharded_heal_all_rows_and_digests():
    mesh = _mesh(8, 4)               # dp=2 x sp=4
    k, m = 12, 4
    lost = [1, 5, 13]
    s = mesh.devices.shape[1] * 64
    data = _rand(4, k, s, seed=3)
    full = _full(data, k, m)
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    _, used = rs_matrix.decode_matrix(k, m, mask)
    survivors = np.ascontiguousarray(full[:, list(used), :])
    sarr = pmesh.shard_array(mesh, survivors, P("dp", None, "sp"))
    run, idxs = pmesh.sharded_heal_step(mesh, k, m, mask)
    out, sdig, odig = run(sarr)
    out, sdig, odig = map(np.asarray, (out, sdig, odig))
    assert idxs == lost
    for row, idx in enumerate(lost):
        assert (out[:, row, :] == full[:, idx, :]).all(), idx
        # rebuilt-shard digests are what the healer writes into the
        # new bitrot frames
        assert odig[0, row].tobytes() == bitrot_mod.hash_shard(
            full[0, idx], HH)
    assert sdig[0, 0].tobytes() == bitrot_mod.hash_shard(
        survivors[0, 0], HH)


def test_sharded_heal_row_filter():
    mesh = _mesh(4)
    k, m = 4, 2
    lost = [1, 5]
    s = mesh.devices.shape[1] * 64
    data = _rand(2, k, s, seed=11)
    full = _full(data, k, m)
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    _, used = rs_matrix.decode_matrix(k, m, mask)
    survivors = np.ascontiguousarray(full[:, list(used), :])
    sarr = pmesh.shard_array(mesh, survivors, P("dp", None, "sp"))
    run, idxs = pmesh.sharded_heal_step(mesh, k, m, mask, rows=(5,))
    out, _sdig, odig = run(sarr)
    assert idxs == [5]
    assert (np.asarray(out)[:, 0, :] == full[:, 5, :]).all()
    assert np.asarray(odig).shape == (2, 1, 32)


# ---------------------------------------------------------------------------
# serving dispatch helpers: batch padding + unshardable fallback
# ---------------------------------------------------------------------------

def test_mesh_helper_pads_uneven_batch():
    mesh = _mesh(8, 4)               # dp=2: B=3 needs padding
    k, m = 4, 2
    s = mesh.devices.shape[1] * 64
    data = _rand(3, k, s, seed=5)
    out = pmesh.mesh_encode_and_hash(mesh, data, k, m)
    assert out is not None
    parity, digests = out
    full = _full(data, k, m)
    assert parity.shape == (3, m, s)
    assert (parity == full[:, k:]).all()
    assert digests.shape == (3, k + m, 32)
    assert digests[2, k].tobytes() == bitrot_mod.hash_shard(
        full[2, k], HH)


def test_mesh_helper_rejects_unshardable_columns():
    mesh = _mesh(8)                  # sp=8
    data = _rand(2, 4, 100, seed=6)  # 100 % 8 != 0
    assert pmesh.mesh_encode_and_hash(mesh, data, 4, 2) is None


# ---------------------------------------------------------------------------
# serving integration: Codec + BatchScheduler route through the mesh
# ---------------------------------------------------------------------------

@pytest.fixture()
def mesh_serving(monkeypatch):
    from minio_tpu.object import codec as codec_mod
    monkeypatch.setenv("MINIO_TPU_MESH", "1")
    monkeypatch.setattr(codec_mod, "DEVICE_MIN_BYTES", 0)
    return codec_mod


def test_codec_fused_paths_dispatch_on_mesh(mesh_serving):
    codec_mod = mesh_serving
    k, m = 4, 2
    s = 1 << 10                      # divides every sp <= 8
    codec = codec_mod.Codec(k, m, s * k)
    data = _rand(2, k, s, seed=8)
    before = pmesh.DISPATCHES.value

    out = codec.encode_and_hash_batch(data, HH)
    assert out is not None and pmesh.DISPATCHES == before + 1
    parity, digests = out
    full = _full(data, k, m)
    assert (parity == full[:, k:]).all()
    assert digests[0, 0].tobytes() == bitrot_mod.hash_shard(
        full[0, 0], HH)

    lost = [1, 4]
    mask = sum(1 << i for i in range(k + m) if i not in lost)
    _, used = rs_matrix.decode_matrix(k, m, mask)
    survivors = np.ascontiguousarray(full[:, list(used), :])
    got = codec.verify_and_decode_batch(survivors, mask, s, HH)
    assert got is not None and pmesh.DISPATCHES == before + 2
    out_rows, missing, sdig = got
    assert list(missing) == [1]
    assert (out_rows[:, 0, :] == full[:, 1, :]).all()
    assert sdig[0, 0].tobytes() == bitrot_mod.hash_shard(
        survivors[0, 0], HH)

    got = codec.verify_and_recover_batch(survivors, mask, {1, 4}, s, HH)
    assert got is not None and pmesh.DISPATCHES == before + 3
    out_rows, idxs, sdig, odig = got
    assert idxs == [1, 4]
    for row, idx in enumerate(idxs):
        assert (out_rows[:, row, :] == full[:, idx, :]).all()
        assert odig[0, row].tobytes() == bitrot_mod.hash_shard(
            full[0, idx], HH)


def test_scheduler_routes_through_mesh(mesh_serving):
    from minio_tpu.parallel.scheduler import BatchScheduler
    codec_mod = mesh_serving
    k, m = 4, 2
    s = 1 << 10
    codec = codec_mod.Codec(k, m, s * k)
    data = _rand(2, k, s, seed=9)
    sched = BatchScheduler(max_wait=0.01)
    try:
        before = pmesh.DISPATCHES.value
        out = sched.encode_and_hash(codec, data, HH)
        assert out is not None
        assert pmesh.DISPATCHES > before
        # the mesh route returns its own join; the former hands a
        # stream the parity rows of it
        parity_got, digests = out
        full = _full(data, k, m)
        assert (parity_got == full[:, k:]).all()
        assert digests[1, k + m - 1].tobytes() == bitrot_mod.hash_shard(
            full[1, k + m - 1], HH)
    finally:
        sched.close()


def test_e2e_multidevice_server_roundtrip(mesh_serving, tmp_path):
    """A live multi-device 'server': ErasureSets put/get/degraded-get
    with the codec forced onto the virtual CPU mesh — proves the
    serving stack (engine -> scheduler -> codec -> mesh collectives)
    round-trips objects when more than one device exists."""
    import os
    from minio_tpu.object.sets import ErasureSets

    sets = ErasureSets.from_drives(
        [str(tmp_path / f"md{i}") for i in range(6)], 1, 6, 2,
        block_size=1 << 16)
    try:
        sets.make_bucket("meshbkt")
        # a group that ends in a short block launches the ragged row,
        # which has no mesh program (codec.FUSED): it runs on one
        # device, and must round-trip all the same
        before = pmesh.DISPATCHES.value
        payload = os.urandom((1 << 16) * 3 + 12345)
        sets.put_object("meshbkt", "obj", payload)
        _info, stream = sets.get_object("meshbkt", "obj")
        assert b"".join(stream) == payload
        assert pmesh.DISPATCHES.value == before
        # whole blocks shard over the mesh
        whole = os.urandom((1 << 16) * 3)
        sets.put_object("meshbkt", "whole", whole)
        _info, stream = sets.get_object("meshbkt", "whole")
        assert b"".join(stream) == whole
        assert pmesh.DISPATCHES > before, \
            "PUT did not dispatch through the mesh"

        # degraded read: lose one drive directory
        import shutil
        shutil.rmtree(tmp_path / "md1")
        _info, stream = sets.get_object("meshbkt", "obj")
        assert b"".join(stream) == payload
    finally:
        sets.close()
