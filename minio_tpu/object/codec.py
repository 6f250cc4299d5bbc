"""Host-side erasure codec: split/join + encode/reconstruct routing.

The engine-facing seam shaped like the reference's codec wrapper
(cmd/erasure-coding.go:28-112: EncodeData / DecodeDataBlocks /
DecodeDataAndParityBlocks / split semantics). Two backends, picked per
call by batch size — the generalized accelerator-offload pattern of the
fork's QAT engine gate (pkg/hash/reader.go:189-206):

  * native C++ GFNI/AVX-512 (utils/native.py) — low latency, small
    batches / single blocks;
  * TPU kernels (ops/rs_tpu.py) — batched blocks, amortizing dispatch.

Both produce byte-identical shards (tests/test_rs_tpu.py oracle checks).
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple, Optional

import jax
import numpy as np

from ..models import pipeline
from ..ops import gf256, rs_matrix, rs_ref, rs_tpu
from ..utils import device, eventlog, knobs, native

# Batches at least this large go to the device (dispatch+transfer amortized).
DEVICE_MIN_BYTES = knobs.get_int("MINIO_TPU_DEVICE_MIN_BYTES")

# Objects under one block are laid at their S rung (parallel/ladder.
# s_rungs). Up to this many columns the host encodes and hashes them
# faster than a launch at the rung (a lone block: 0.25-6.0 ms against
# 2.4-8.2 ms at S rungs 16384-262144 at 12+4 on a v5e; at the full S
# 12.0 against 10.2 ms — tools/subblock_crossover.py, PERF.md §6): a
# rung up to it goes to the host at submit, one above it to the device,
# whatever the launch's block count.
SUBBLOCK_HOST_MAX_S = 262144


def _device_is_tpu() -> bool:
    """The routing predicate every device gate reads (scheduler, SSE,
    scan import it from here). Tests patch it to drive the device route
    on XLA-CPU; kernel flavour keeps reading the probe itself."""
    return device.probe().is_tpu


def _mesh_active():
    """Mesh the fused batches dispatch over, or None for the
    single-device path — the default on any device count.
    MINIO_TPU_MESH=1 asks for the (dp, sp) mesh over every visible
    device (the virtual CPU mesh tests, the driver dryrun). It is
    opt-in because the route has not passed on real chips: on a
    four-chip v5e host the sharded heal step wrote zero digest frames
    for rebuilt shards and 12+4's S = 349526 does not shard at all
    (PERF.md, PR 21; ROADMAP A9/B8)."""
    if knobs.get_str("MINIO_TPU_MESH") != "1":
        return None
    from ..parallel import mesh as pmesh
    return pmesh.default_mesh()


def data_path_line() -> str:
    """The boot banner's one line on what the data path runs on."""
    dp = device.probe()
    if dp.reason:
        return f"data path: host CPU — no accelerator: {dp.reason}"
    mesh = _mesh_active()
    used = int(mesh.devices.size) if mesh is not None else 1
    of = "" if used == dp.count else f" of {dp.count}"
    kernel = "pallas" if mesh is None else "xla matmul + all_to_all"
    return (f"data path: {dp.platform} ({dp.device_kind}) x{used}{of}, "
            f"{kernel}")


def subblock_on_device(s: int) -> bool:
    """Whether a launch of objects under one block at S rung `s`
    (parallel/ladder.s_rungs) is the device's: the measured crossover,
    a rung at a time — never the launch's block count, which is how
    many happened to coalesce in the grace window."""
    return s > SUBBLOCK_HOST_MAX_S


class _Fused(NamedTuple):
    """One fused device program of the data path: what differs between
    the five, and nothing else — `Codec._launch` holds what they share.
    `FUSED` names a row by the Codec method that enters it (the ragged
    row: by its method and `.ragged` — `encode_and_hash_batch` takes
    it when a block of the launch is short); `static` below is that
    method's arguments between the data and the bitrot algorithm, the
    per-row arrays left out.

    step       jitted step in models/pipeline.py, looked up there at
               call time
    verb       the launch ladder's, and the batch former's, verb
    operands   (codec, *static) -> (shared, lead, tail), or None when
               the launch has nothing to rebuild: the step's operands
               before and after the per-row arrays (`_step_call`), and
               the value every block of the result shares
    mesh       sharded program in parallel/mesh.py, called with
               (mesh, data, k, m, *static, kernel); "": it has none
    keep       the step's outputs that cross back; (): all of them
    shared_at  where `shared` joins the result; every other element
               of it is per block
    rows_at    where the per-row word arrays (keys, nonces) sit among
               `static` in the method's signature
    """
    step: str
    verb: str
    operands: Callable
    mesh: str = ""
    keep: tuple = ()
    shared_at: Optional[int] = None
    rows_at: int = 0


def _encode_operands(codec, *pkg_bytes):
    # the steps build the parity matrix themselves, from k and m
    return None, (), (codec.k, codec.m, *pkg_bytes)


def _decode_operands(codec, present_mask: int, shard_len: int,
                     *pkg_bytes):
    dm, used, missing = rs_matrix.missing_data_matrix(
        codec.k, codec.m, present_mask)
    if not missing:
        return None     # plain verify has no matmul to fuse with
    src = ()
    if pkg_bytes:
        # static reassembly map: data shard j comes from the survivors
        # stack (decode `used` order) or the reconstructed rows
        # (`missing` order)
        src = (tuple((0, used.index(j)) if j in used
                     else (1, missing.index(j))
                     for j in range(codec.k)),)
    return (missing, (rs_tpu._bit_expand_cached(dm.tobytes(), dm.shape),),
            (dm.shape[0], codec.k, *src, *pkg_bytes, shard_len))


def _recover_operands(codec, present_mask: int, rows, shard_len: int):
    rec, idxs = codec._recover_rows(present_mask, rows)
    if not idxs:
        return None
    return (idxs, (rs_tpu._bit_expand_cached(rec.tobytes(), rec.shape),),
            (rec.shape[0], codec.k, shard_len))


FUSED = {
    "encode_and_hash_batch": _Fused(
        "put_step", "encode", _encode_operands,
        mesh="mesh_encode_and_hash"),
    "encrypt_encode_and_hash_batch": _Fused(
        "sse_put_step", "encode", _encode_operands),
    "verify_and_decode_batch": _Fused(
        "get_step", "decode", _decode_operands,
        mesh="mesh_verify_and_decode", shared_at=1),
    # the step also returns the rebuilt CIPHERTEXT rows, which stay
    "verify_decode_decrypt_batch": _Fused(
        "sse_get_step", "decode", _decode_operands,
        keep=(0, 2), shared_at=1, rows_at=2),
    "verify_and_recover_batch": _Fused(
        "heal_step", "recover", _recover_operands,
        mesh="mesh_verify_and_recover", shared_at=1),
    # a launch some of whose blocks are short (an object's short last
    # block rides the group of its whole blocks): each block's shard
    # length rides as a per-row array. The mesh has no ragged program:
    # on a mesh host this launches on one device
    "encode_and_hash_batch.ragged": _Fused(
        "put_step_ragged", "encode", _encode_operands),
}


class Codec:
    """RS(k, m) over GF(2^8), klauspost-compatible matrices."""

    def __init__(self, data_shards: int, parity_shards: int,
                 block_size: int):
        if not (1 <= data_shards <= 256 and 0 <= parity_shards
                and data_shards + parity_shards <= 256):
            raise ValueError("unsupported erasure geometry")
        self.k = data_shards
        self.m = parity_shards
        self.block_size = block_size
        self.shard_size = -(-block_size // data_shards)
        self._parity_matrix = np.asarray(
            rs_matrix.parity_matrix(self.k, self.m), dtype=np.uint8)

    # -- split / join ------------------------------------------------------

    def split(self, block: bytes | memoryview) -> np.ndarray:
        """block -> (k, S) zero-padded shards, S = ceil(len/k)
        (klauspost Split semantics via reference EncodeData,
        cmd/erasure-coding.go:70-84)."""
        n = len(block)
        if n == 0:
            return np.zeros((self.k, 0), dtype=np.uint8)
        shard = -(-n // self.k)
        buf = np.zeros(self.k * shard, dtype=np.uint8)
        buf[:n] = np.frombuffer(block, dtype=np.uint8)
        return buf.reshape(self.k, shard)

    @staticmethod
    def join(data_shards: np.ndarray, size: int) -> bytes:
        """Concatenate data shards and trim padding."""
        return data_shards.reshape(-1).tobytes()[:size]

    # -- encode ------------------------------------------------------------

    def encode_batch(self, data: np.ndarray, *, force: str = ""
                     ) -> np.ndarray:
        """(B, k, S) or (k, S) data shards -> parity appended (…, k+m, S).

        force: "" auto-route, "native", "device", "numpy" (tests)."""
        if self.m == 0:
            return data
        single = data.ndim == 2
        batch = data[None] if single else data
        path = force or self._route(batch.nbytes)
        if path == "device":
            out = np.asarray(rs_tpu.encode(batch, self.k, self.m))
        elif path == "native" and native.available():
            b, k, s = batch.shape
            parity = np.empty((b, self.m, s), dtype=np.uint8)
            for i in range(b):
                parity[i] = native.gf_matmul(self._parity_matrix, batch[i])
            out = np.concatenate([batch, parity], axis=1)
        else:
            out = np.stack([rs_ref.encode(batch[i], self.m)
                            for i in range(batch.shape[0])])
        return out[0] if single else out

    def encode_parity_batch(self, data: np.ndarray, *, force: str = ""
                            ) -> np.ndarray:
        """(B, k, S) data shards -> (B, m, S) parity ONLY — the PUT hot
        path writes data rows straight out of the read buffer, so no
        full-array concat happens (encode_batch's concatenate was one
        whole extra pass over the payload)."""
        b, _k, s = data.shape
        if self.m == 0:
            return np.zeros((b, 0, s), dtype=np.uint8)
        path = force or self._route(data.nbytes)
        if path == "device":
            return np.asarray(
                rs_tpu.encode(data, self.k, self.m))[:, self.k:]
        parity = np.empty((b, self.m, s), dtype=np.uint8)
        if path == "native" and native.available():
            for i in range(b):
                parity[i] = native.gf_matmul(self._parity_matrix, data[i])
        else:
            for i in range(b):
                parity[i] = rs_ref.encode(data[i], self.m)[self.k:]
        return parity

    def _route(self, nbytes: int) -> str:
        if _device_is_tpu() and nbytes >= DEVICE_MIN_BYTES:
            return "device"
        if native.available():
            return "native"
        return "numpy"

    def _mesh_route(self, nbytes: int, force: str):
        """Mesh for a fused dispatch, or None. Mesh dispatch applies
        ONLY to the fused put/get/heal batches (the paths with sharded
        SPMD programs) — the plain encode/decode fallbacks keep their
        native/numpy routing, so forcing the mesh on a CPU-only host
        never demotes them to single-device XLA."""
        if force not in ("", "device"):
            return None
        if not force and nbytes < DEVICE_MIN_BYTES:
            return None
        return _mesh_active()

    # -- fused encode + bitrot (device) ------------------------------------

    @staticmethod
    def _device_hash_kernel(algo) -> Optional[str]:
        """Device kernel name for a bitrot algorithm, or None when the
        algorithm has no device implementation."""
        from .. import bitrot as bitrot_mod
        if algo in (bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256,
                    bitrot_mod.BitrotAlgorithm.HIGHWAYHASH256S):
            return "highwayhash"
        if algo is bitrot_mod.BitrotAlgorithm.SHA256:
            return "sha256"
        return None

    @staticmethod
    def _step_call(step, arrays, lead, tail, kernel):
        """THE call form of the five fused steps: the data, the matrix
        operand where the step takes one, the per-row arrays, the
        static operands. `step` is the jitted function (a launch) or
        its `.lower` (boot's load), so both make the same program."""
        return step(arrays[0], *lead, *arrays[1:], *tail, algo=kernel)

    def load_encode_program(self, blocks: int, algo,
                            ragged: bool = False) -> None:
        """Lower and compile, without running it, the program an
        encode launch at rung `blocks` runs: the fused step through
        the table row and call form `_launch` uses — so the loaded
        executable is the one a request hits (a padded launch needs
        no other: its pad rows are cut off the host view). Boot's
        loader (parallel/ladder.load_encode) asks; `ragged`: for the
        row of a launch that carries short blocks."""
        row = FUSED["encode_and_hash_batch" + (".ragged" if ragged else "")]
        _shared, lead, tail = row.operands(self)
        arrays = (jax.ShapeDtypeStruct(
            (blocks, self.k, self.shard_size), np.uint8),)
        if ragged:
            arrays += (jax.ShapeDtypeStruct((blocks,), np.int32),)
        self._step_call(getattr(pipeline, row.step).lower, arrays, lead,
                        tail, self._device_hash_kernel(algo)).compile()

    def _launch(self, row: _Fused, data: np.ndarray, row_arrays, static,
                algo, *, force: str = "", stage_cb=None,
                blocks: Optional[int] = None,
                nbytes: Optional[int] = None):
        """One launch of the fused program `row` names over data
        (B, k, S), the per-row arrays beside it and the entry's static
        arguments -> the entry's result tuple, or None when the batch
        doesn't route to the device, the bitrot algorithm has no device
        kernel, or the row has nothing to do.

        force: "" auto-route, "device" (tests).

        stage_cb(stage, seconds, **attrs), when given, is called as
        each stage ENDS: "h2d" (the fused input's upload, waited for —
        one extra host wake-up per launch, so without a callback the
        arrays go to the step as they are), "compute" (launch + device
        program + sync) and "fetch" (what is left of the device→host
        readback once the program has ended; `form=`: the array the
        first output crossed as) — the batch scheduler's dispatch
        attribution. The readback is asked for AT the launch
        (`copy_to_host_async`), so it starts when the program ends
        with no host wake-up in between: the two stages' SUM is the
        time from launch to host arrays. The mesh route reports a
        single "compute" stage (its sharded programs return host
        arrays in one step).

        The single-device launch runs at its ladder rung
        (parallel/ladder.py): the arrays are padded here, with zero
        blocks, up to the rung of their block count. `blocks`, when
        given, says that `data` is padded to it already (the batch
        former's staging buffer) and how many of its rows are real; the
        small per-row arrays are still brought up to it. The rung's
        whole result crosses back in the steps' link form
        (models/pipeline.link_rows) and is handed on as VIEWS of what
        crossed: each S-wide output (B, r, S) uint8 again, a padded
        launch's pad rows cut off, no byte copied — so no result holds
        a pad row.

        `nbytes`, when given, is what the launch holds of REAL bytes
        (a ragged launch: its blocks' own lengths, not the zero
        columns beside them) and is what the route is asked with."""
        kernel = self._device_hash_kernel(algo)
        if kernel is None:
            return None
        real = data if blocks is None else data[:blocks]
        if nbytes is None:
            nbytes = real.nbytes
        mesh = self._mesh_route(nbytes, force) if row.mesh else None
        if mesh is not None:
            from ..parallel import mesh as pmesh
            t0 = time.perf_counter()
            out = getattr(pmesh, row.mesh)(mesh, real, self.k, self.m,
                                           *static, kernel)
            if out is not None:
                if stage_cb is not None:
                    stage_cb("compute", time.perf_counter() - t0)
                return out
        if (force or self._route(nbytes)) != "device":
            return None
        operands = row.operands(self, *static)
        if operands is None:
            return None
        shared, lead, tail = operands
        from ..parallel import ladder
        n, to = ladder.launch_size(row.verb, data.shape[0], blocks)
        arrays = tuple(ladder.pad_blocks(a, to)
                       for a in (data, *row_arrays))
        if stage_cb is not None:
            t0 = time.perf_counter()
            arrays = jax.block_until_ready(
                tuple(jax.device_put(a) for a in arrays))
            stage_cb("h2d", time.perf_counter() - t0)
        t0 = time.perf_counter()
        outs = self._step_call(getattr(pipeline, row.step), arrays, lead,
                               tail, kernel)
        if row.keep:
            outs = tuple(outs[i] for i in row.keep)
        for o in outs:
            o.copy_to_host_async()
        if stage_cb is not None:
            jax.block_until_ready(outs)
            t1 = time.perf_counter()
            stage_cb("compute", t1 - t0)
        crossed = tuple(np.asarray(o) for o in outs)
        host = tuple(pipeline.host_rows(a, data.shape[2])[:n]
                     for a in crossed)
        if stage_cb is not None:
            stage_cb("fetch", time.perf_counter() - t1,
                     form=f"{crossed[0].dtype.name}"
                          f"{list(crossed[0].shape)}")
        if row.shared_at is None:
            return host
        return (*host[:row.shared_at], shared, *host[row.shared_at:])

    # The five entries: arguments -> table row + operands -> `_launch`,
    # whose keyword arguments (force, stage_cb, blocks) they pass on.

    def encode_and_hash_batch(self, data: np.ndarray, algo, lengths=None,
                              subblock: bool = False, **launch):
        """Fused device path for the PUT hot loop: one program computes
        parity AND every shard's HighwayHash256 digest (the reference's
        Erasure.Encode + streaming-bitrot work, cmd/erasure-encode.go:75 +
        cmd/bitrot-streaming.go:46, as a single device step).

        data: (B, k, S). Returns (parity (B, m, S), digests
        (B, k+m, 32)) on every route, or None. Only parity + digests
        cross back from the device: the k data rows stay the caller's
        own bytes.

        lengths: (B,) each block's own shard length, for a launch that
        carries SHORT blocks (an object's last block, laid out as
        `split` lays it in the first lengths[b] columns of its rows,
        zero beyond): the digests then cover lengths[b] bytes a row and
        the caller keeps parity[b, :, :lengths[b]]. A launch none of
        whose blocks is short runs the static program as if `lengths`
        had not been given; one with a short block runs the ragged row
        at the same rung, is routed by its real bytes, and — when the
        bitrot algorithm has no ragged kernel — goes to the host whole.

        subblock: every block of the launch is an object under one
        block, laid at its S rung (`data.shape[2]`, parallel/ladder.
        s_rungs): the launch runs the ragged row at that S, on that
        rung's B ladder, and is routed by the rung
        (`subblock_on_device`), not by its bytes.
        """
        if self.m == 0:
            return None
        if lengths is not None:
            from ..parallel import ladder
            n, to = ladder.launch_size(
                "encode", data.shape[0], launch.get("blocks"),
                subblock and data.shape[2] < self.shard_size)
            lengths = np.asarray(lengths, np.int32)[:n]
            if subblock or (lengths < data.shape[2]).any():
                return self._encode_ragged(data, algo, lengths, to,
                                           subblock, **launch)
        return self._launch(FUSED["encode_and_hash_batch"], data, (), (),
                            algo, **launch)

    def _encode_ragged(self, data: np.ndarray, algo, lengths: np.ndarray,
                       to: int, subblock: bool = False, **launch):
        """`encode_and_hash_batch` for a launch with a short block:
        `lengths` of its real blocks, `to` the rung it runs at;
        `subblock`: of objects under one block, at their S rung."""
        from ..parallel import ladder
        if self._device_hash_kernel(algo) != "highwayhash":
            eventlog.emit_once("device.decline", stage="encode",
                               reason="no-ragged-kernel")
            return None
        s = data.shape[2]
        # pad blocks at the full length, as the static program has them
        at_rung = np.full(to, s, np.int32)
        at_rung[:len(lengths)] = lengths
        real = int(lengths.sum()) * self.k
        force = launch.pop("force", "")
        if subblock:
            route = "device" if _device_is_tpu() \
                and subblock_on_device(s) else "host"
        else:
            route = self._route(real)
        if not force:
            if route != "device":
                return None
            # the first launch with a short block that the device takes
            # at an S starts the load of the row's rungs there (a node's
            # own small objects are short blocks too, and stay on the
            # host: boot loads none of this), and every launch waits for
            # its own
            ladder.load_encode_ragged(self, algo, want=to, s=s)
            ladder.await_ragged(self, algo, to, s=s)
        if launch.get("blocks") is None:
            # the direct route: brought up to the rung here, so that a
            # launch at an S rung pads on its own ladder
            data = ladder.pad_blocks(data, to)
            launch["blocks"] = len(lengths)
        return self._launch(FUSED["encode_and_hash_batch.ragged"], data,
                            (at_rung,), (), algo, force=force or "device",
                            nbytes=real, **launch)

    def encrypt_encode_and_hash_batch(self, data: np.ndarray, keys,
                                      nonces, pkg_bytes: int, algo,
                                      **launch):
        """Fused device path for the ENCRYPTED PUT hot loop: ChaCha20
        cipher + parity + per-shard digests in one launch
        (models/pipeline.sse_put_step) — an encrypted batch costs the
        same single dispatch as a plaintext one.

        data: (B, k, S) staged PLAINTEXT shards; keys (B, 8) / nonces
        (B, P, 3) u32 word arrays (features/crypto.DeviceSSE.
        batch_params — P·pkg_bytes plaintext bytes per row). Returns
        (full (B, k+m, S) — CIPHERTEXT data rows with parity appended,
        digests (B, k+m, 32)), or None (the caller's CPU cipher path is
        the oracle). The data rows DO cross back here: the caller staged
        plaintext and must write (and Poly1305-tag) the ciphertext. The
        mesh has no sse program: on a mesh host this routes as on any
        other, to one device or to None.
        """
        if self.m == 0:
            return None
        return self._launch(FUSED["encrypt_encode_and_hash_batch"], data,
                            (keys, nonces), (pkg_bytes,), algo, **launch)

    def verify_decode_decrypt_batch(self, survivors: np.ndarray,
                                    present_mask: int, shard_len: int,
                                    keys, nonces, pkg_bytes: int, algo,
                                    **launch):
        """Fused device path for the ENCRYPTED degraded GET: bitrot-
        verify survivors, reconstruct the missing data rows, and
        decipher the reassembled data shards in one launch
        (models/pipeline.sse_get_step).

        survivors: (B, k, S) in missing_data_matrix `used` order.
        Returns (plain (B, k, S) deciphered data shards in shard-index
        order, missing_idx, survivor_digests (B, k, 32)), or None
        (also when nothing is missing). Package tags still verify
        host-side before any of this output is served
        (features/crypto.chacha_decrypt_ranged discipline).
        """
        return self._launch(FUSED["verify_decode_decrypt_batch"], survivors,
                            (keys, nonces),
                            (present_mask, shard_len, pkg_bytes), algo,
                            **launch)

    def verify_and_decode_batch(self, survivors: np.ndarray,
                                present_mask: int, shard_len: int, algo,
                                **launch):
        """Fused device path for the degraded-GET hot loop: ONE program
        bitrot-hashes every survivor shard AND reconstructs only the
        missing data rows (models/pipeline.get_step — the device form of
        cmd/erasure-decode.go:111-150's verify-then-decode).

        survivors: (B, k, S) stacked in missing_data_matrix `used` order.
        Returns (missing (B, r, S), missing_idx, survivor_digests
        (B, k, 32)) as numpy arrays, or None (also when nothing is
        missing: plain verify has no matmul to fuse with).
        """
        return self._launch(FUSED["verify_and_decode_batch"], survivors, (),
                            (present_mask, shard_len), algo, **launch)

    def verify_and_recover_batch(self, survivors: np.ndarray,
                                 present_mask: int, rows: "set[int]",
                                 shard_len: int, algo, **launch):
        """Fused device path for heal: verify survivors, rebuild exactly
        the requested lost rows, and digest the rebuilt shards for their
        new bitrot frames (models/pipeline.heal_step).

        Returns (out (B, R, S), idxs, survivor_digests (B, k, 32),
        out_digests (B, R, 32)) or None.
        """
        return self._launch(FUSED["verify_and_recover_batch"], survivors,
                            (), (present_mask, rows, shard_len), algo,
                            **launch)

    def _recover_rows(self, present_mask: int, rows: "set[int]"
                      ) -> tuple[np.ndarray, list[int]]:
        """Recover matrix filtered to the requested shard rows — the
        row-selection invariant lives in rs_matrix.recover_rows, shared
        with the mesh heal step."""
        return rs_matrix.recover_rows(self.k, self.m, present_mask,
                                      rows)

    # -- batched decode (degraded GET) -------------------------------------

    def decode_stacked(self, survivors: np.ndarray, present_mask: int,
                       *, force: str = "") -> np.ndarray:
        """(B, k, S) survivors — stacked in decode_matrix `used` order —
        -> (B, k, S) data shards. The degraded-GET hot path: a batch of
        blocks sharing one erasure pattern reconstructs in ONE device
        matmul (cmd/erasure-decode.go's per-block ReconstructData,
        batched for the MXU)."""
        path = force or self._route(survivors.nbytes)
        if path == "device":
            return np.asarray(rs_tpu.reconstruct_data(
                survivors, present_mask, self.k, self.m))
        d, _used = rs_matrix.decode_matrix(self.k, self.m, present_mask)
        d = np.asarray(d, dtype=np.uint8)
        if path == "native" and native.available():
            return np.stack([native.gf_matmul(d, s) for s in survivors])
        return np.stack([gf256.gf_matmul(d, s) for s in survivors])

    def recover_stacked(self, survivors: np.ndarray, present_mask: int,
                        rows: "set[int]", *, force: str = ""
                        ) -> tuple[np.ndarray, list[int]]:
        """(B, k, S) survivors (recover_matrix `used` order) -> exactly
        the requested missing shard rows, one batched matmul — the heal
        hot path over many blocks (cmd/erasure-lowlevel-heal.go's
        decode→re-encode collapsed AND batched). Returns (out (B, R, S),
        shard indices for each output row)."""
        rec, idxs = self._recover_rows(present_mask, rows)
        path = force or self._route(survivors.nbytes)
        if path == "device":
            out = np.asarray(rs_tpu.apply_matrix(rec, survivors))
        elif path == "native" and native.available():
            out = np.stack([native.gf_matmul(rec, s) for s in survivors])
        else:
            out = np.stack([gf256.gf_matmul(rec, s) for s in survivors])
        return out, idxs

    # -- reconstruct -------------------------------------------------------

    def reconstruct(self, shards: list[np.ndarray | None],
                    data_only: bool = False, *, force: str = "",
                    rows: Optional[set[int]] = None) -> list[np.ndarray]:
        """Fill in missing (None) shards from >= k survivors.

        shards: length k+m list in shard-index order; returns the full
        list (or just data shards) — reference DecodeDataAndParityBlocks /
        DecodeDataBlocks (cmd/erasure-coding.go:89-112). With `rows`, only
        those shard indices are rebuilt (the heal path's exact-rows form;
        others stay None).
        """
        n = self.k + self.m
        if len(shards) != n:
            raise ValueError("bad shard count")
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) < self.k:
            from . import api_errors
            raise api_errors.InsufficientReadQuorum(
                f"{len(present)} shards < k={self.k}")
        wanted = [i for i in range(n) if shards[i] is None
                  and (not data_only or i < self.k)
                  and (rows is None or i in rows)]
        if not wanted:
            return list(shards)  # type: ignore[arg-type]

        mask = sum(1 << i for i in present)
        rec, used, rec_missing = rs_matrix.recover_matrix(self.k, self.m,
                                                          mask)
        keep = [r for r, idx in enumerate(rec_missing) if idx in wanted]
        rec = rec[keep]
        rec_missing = tuple(idx for idx in rec_missing if idx in wanted)
        stacked = np.stack([shards[i] for i in used])
        path = force or self._route(stacked.nbytes)
        if path == "device":
            out = np.asarray(rs_tpu.apply_matrix(np.asarray(rec), stacked))
        elif path == "native" and native.available():
            out = native.gf_matmul(np.asarray(rec, dtype=np.uint8), stacked)
        else:
            out = gf256.gf_matmul(np.asarray(rec, dtype=np.uint8), stacked)
        result = list(shards)
        for row, idx in enumerate(rec_missing):
            result[idx] = out[row]
        return result  # type: ignore[return-value]
