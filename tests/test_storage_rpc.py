"""Storage RPC tests: remote StorageAPI verbs client<->server in one
process (the reference's cmd/storage-rest_test.go pattern), then a full
erasure object engine over remote drives."""

from __future__ import annotations

import hashlib
import io
import json
import uuid

import pytest

from minio_tpu.distributed.storage_rpc import (RemoteStorage,
                                               StorageRPCServer,
                                               fi_from_dict, fi_to_dict)
from minio_tpu.distributed.transport import RPCServer
from minio_tpu.storage import errors as serr
from minio_tpu.storage.datatypes import (ChecksumInfo, FileInfo,
                                         new_file_info)
from minio_tpu.storage import new_format_erasure_v3
from minio_tpu.storage.xl_storage import XLStorage

AK, SK = "nodekey", "nodesecret12345"
N = 6


@pytest.fixture()
def cluster(tmp_path):
    """One serving node with N formatted local drives + N remote
    clients."""
    fmts = new_format_erasure_v3(1, N)
    locals_ = {}
    for i in range(N):
        d = XLStorage(str(tmp_path / f"d{i}"))
        d.write_format(fmts[0][i])
        locals_[f"/d{i}"] = d
    srv = StorageRPCServer(locals_, AK, SK)
    host = RPCServer().start()
    host.mount(srv.handler)
    remotes = [RemoteStorage("127.0.0.1", host.port, f"/d{i}", AK, SK)
               for i in range(N)]
    yield locals_, remotes
    for r in remotes:
        r.close()
    host.stop()
    for d in locals_.values():
        d.close()


def test_vol_verbs(cluster):
    _, remotes = cluster
    r = remotes[0]
    r.make_vol("vol1")
    assert "vol1" in [v.name for v in r.list_vols()]
    assert r.stat_vol("vol1").name == "vol1"
    with pytest.raises(serr.VolumeExists):
        r.make_vol("vol1")
    r.delete_vol("vol1")
    with pytest.raises(serr.VolumeNotFound):
        r.stat_vol("vol1")


def test_file_verbs(cluster):
    _, remotes = cluster
    r = remotes[1]
    r.make_vol("v")
    r.write_all("v", "f.bin", b"hello remote")
    assert r.read_all("v", "f.bin") == b"hello remote"
    assert r.read_file("v", "f.bin", 6, 6) == b"remote"
    r.append_file("v", "f.bin", b"!more")
    assert r.read_all("v", "f.bin") == b"hello remote!more"
    r.create_file("v", "dir/stream.bin", 4, io.BytesIO(b"abcd"))
    assert r.read_all("v", "dir/stream.bin") == b"abcd"
    r.rename_file("v", "dir/stream.bin", "v", "dir/renamed.bin")
    assert r.read_all("v", "dir/renamed.bin") == b"abcd"
    assert "dir/" in r.list_dir("v", "")
    r.delete_file("v", "f.bin")
    with pytest.raises(serr.FileNotFound):
        r.read_all("v", "f.bin")


def test_metadata_verbs(cluster):
    _, remotes = cluster
    r = remotes[2]
    r.make_vol("v")
    fi = new_file_info("v/obj", 4, 2)
    fi.volume, fi.name = "v", "obj"
    fi.size = 42
    fi.mod_time = 1234567890.5
    fi.data_dir = "11111111-2222-3333-4444-555555555555"
    fi.metadata = {"etag": "deadbeef", "content-type": "x/y"}
    fi.add_object_part(1, "deadbeef", 42, 42)
    fi.erasure.checksums = [ChecksumInfo(1, "highwayhash256S", b"")]
    r.write_metadata("v", "obj", fi)
    got = r.read_version("v", "obj")
    assert got.size == 42
    assert got.metadata["etag"] == "deadbeef"
    assert got.erasure.data_blocks == 4
    assert [v.name for v in r.read_versions("v", "obj")] == ["obj"]
    # walk sees it
    names = [w.name for w in r.walk("v")]
    assert "obj" in names
    r.delete_version("v", "obj", got)
    with pytest.raises((serr.FileNotFound, serr.FileVersionNotFound)):
        r.read_version("v", "obj")


def _commit_fi():
    fi = new_file_info("v/obj", 4, 2)
    fi.volume, fi.name, fi.size = "v", "obj", 5
    fi.mod_time = 1234567890.5
    fi.data_dir = "11111111-2222-3333-4444-555555555555"
    fi.metadata = {"etag": "deadbeef"}
    fi.add_object_part(1, "deadbeef", 5, 5)
    fi.erasure.index = 4
    fi.erasure.checksums = [ChecksumInfo(1, "highwayhash256S", b"")]
    return fi


def test_commit_verbs_round_trip_fi(cluster):
    """rename_data's `fi` crosses the wire: the remote drive commits a
    staging directory that holds the shards alone, as a PUT stages it,
    and a call without it reads the staged journal back as before."""
    locals_, remotes = cluster
    r, local = remotes[3], locals_["/d3"]
    r.make_vol("v")
    local.make_vol_bulk(".minio.sys/tmp")
    seen = []
    real_wm, real_rd = local.write_metadata, local.rename_data

    def write_metadata(volume, path, fi):
        seen.append(("wm",))
        return real_wm(volume, path, fi)

    def rename_data(sv, sp, dd, dv, dp, version_id="", fi=None):
        seen.append(("rd", fi))
        return real_rd(sv, sp, dd, dv, dp, version_id, fi)

    local.write_metadata, local.rename_data = write_metadata, rename_data
    fi = _commit_fi()
    tmp = ".minio.sys/tmp"
    r.append_file(tmp, f"stg/{fi.data_dir}/part.1", b"shard")
    r.rename_data(tmp, "stg", fi.data_dir, "v", "obj", fi=fi)
    assert len(seen) == 1 and seen[0][0] == "rd"
    assert fi_to_dict(seen[0][1]) == fi_to_dict(fi)
    got = r.read_version("v", "obj")
    assert got.erasure.index == 4 and got.data_dir == fi.data_dir
    assert r.read_all("v", f"obj/{fi.data_dir}/part.1") == b"shard"
    assert r.list_dir(tmp, "") == []
    # the same commit, nothing handed over: byte-identical journal
    committed = local.read_all("v", "obj/xl.meta")
    r.delete_version("v", "obj", got)
    del seen[:]
    r.append_file(tmp, f"stg/{fi.data_dir}/part.1", b"shard")
    r.write_metadata(tmp, "stg", fi)
    r.rename_data(tmp, "stg", fi.data_dir, "v", "obj")
    assert seen == [("wm",), ("rd", None)]
    assert local.read_all("v", "obj/xl.meta") == committed


def test_writemetadata_from_a_peer_that_sends_fresh_merges(cluster):
    """A peer that still sends writemetadata's old `fresh` argument is
    heard as any other: the journal already there is merged, not
    replaced."""
    locals_, remotes = cluster
    r, local = remotes[3], locals_["/d3"]
    local.make_vol_bulk(".minio.sys/tmp")
    tmp = ".minio.sys/tmp"
    first, second = _commit_fi(), _commit_fi()
    first.version_id, second.version_id = str(uuid.uuid4()), \
        str(uuid.uuid4())
    second.mod_time += 1
    r.write_metadata(tmp, "stg", first)
    r._call("writemetadata", {"volume": tmp, "path": "stg",
                              "fresh": "true"},
            json.dumps(fi_to_dict(second)).encode())
    assert {v.version_id for v in local.read_versions(tmp, "stg")} \
        == {first.version_id, second.version_id}


def test_fi_codec_roundtrip():
    fi = new_file_info("b/o", 12, 4)
    fi.volume, fi.name, fi.size = "b", "o", 999
    fi.metadata = {"etag": "abc", "x": "y"}
    fi.add_object_part(1, "abc", 999, 999)
    fi.erasure.checksums = [ChecksumInfo(1, "sha256", b"\x01\x02")]
    back = fi_from_dict(fi_to_dict(fi))
    assert back.erasure.distribution == fi.erasure.distribution
    assert back.erasure.checksums[0].hash == b"\x01\x02"
    assert back.parts[0].size == 999
    assert back.metadata == fi.metadata


def test_network_error_is_disk_not_found(cluster):
    _, remotes = cluster
    dead = RemoteStorage("127.0.0.1", 1, "/d0", AK, SK, timeout=0.5)
    with pytest.raises(serr.DiskNotFound):
        dead.read_all("v", "x")
    assert not dead.is_online()


def test_auth_failure(cluster):
    _, remotes = cluster
    bad = RemoteStorage("127.0.0.1", remotes[0].rc.port, "/d0", AK,
                        "wrongsecret1234")
    with pytest.raises(serr.UnexpectedError):
        bad.list_vols()


def test_erasure_engine_over_remote_drives(cluster):
    """The full PUT/GET/heal path where every drive is an RPC client —
    the reference's distributed XL over storage REST."""
    from minio_tpu.object import ErasureSetObjects

    locals_, remotes = cluster
    eng = ErasureSetObjects(list(remotes), data_shards=4, parity_shards=2,
                            block_size=1 << 16)
    eng.make_bucket("rb")
    data = b"remote drive payload " * 9973
    info = eng.put_object("rb", "obj", data)
    assert info.etag == hashlib.md5(data).hexdigest()
    _, it = eng.get_object("rb", "obj")
    assert b"".join(it) == data

    # kill one remote drive's data dir and heal through RPC
    import shutil
    victim = locals_["/d0"]
    shutil.rmtree(victim.root + "/rb", ignore_errors=True)
    _, it = eng.get_object("rb", "obj")
    assert b"".join(it) == data        # reconstructs around the hole
    eng.heal_object("rb", "obj")
    _, it = eng.get_object("rb", "obj")
    assert b"".join(it) == data

    objs, _, _ = eng.list_objects("rb")
    assert [o.name for o in objs] == ["obj"]
    eng.delete_object("rb", "obj")
    eng.delete_bucket("rb")
