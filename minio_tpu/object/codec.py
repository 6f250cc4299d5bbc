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

from typing import Optional

import numpy as np

from ..ops import gf256, rs_matrix, rs_ref, rs_tpu
from ..utils import device, knobs, native

# Batches at least this large go to the device (dispatch+transfer amortized).
DEVICE_MIN_BYTES = knobs.get_int("MINIO_TPU_DEVICE_MIN_BYTES")


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


class EncodedRows:
    """First element of the plain encode route's result: the parity the
    device made and a reference to the caller's own data rows — no
    (B, k+m, S) array exists unless somebody asks for one. `np.array()`
    / `np.asarray()` answer with the join [data ‖ parity] (the shape
    the tuple had before PR 26), which is what lets a wrapper that
    treats the result as a plain ndarray keep working; the batch former
    and the engine read `.parity` and never pay for the join."""

    __slots__ = ("data", "parity")

    def __init__(self, data: np.ndarray, parity: np.ndarray):
        self.data = data
        self.parity = parity

    def __array__(self, dtype=None, copy=None):
        full = np.concatenate([self.data, self.parity], axis=1)
        return full if dtype is None else full.astype(dtype, copy=False)


def parity_rows(rows, k: int) -> np.ndarray:
    """(B, m, S) parity of an encode result's first element: an
    EncodedRows' own array, or the rows past k of a plain (B, k+m, S)
    join (the mesh route's result, a wrapped codec's)."""
    if isinstance(rows, EncodedRows):
        return rows.parity
    return rows[:, k:]


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
    def _upload(stage_cb, *arrays):
        """Stage "h2d" of the single-device jit path: put the fused
        input on the device and wait for it, so the upload is timed
        apart from the program (one extra host wake-up per launch; the
        two were serial already). Without a callback the arrays go to
        the step as they are — the hot path pays nothing."""
        if stage_cb is None:
            return arrays
        import time as _time
        import jax
        t0 = _time.perf_counter()
        on_device = jax.block_until_ready(
            tuple(jax.device_put(a) for a in arrays))
        stage_cb("h2d", _time.perf_counter() - t0)
        return on_device

    @staticmethod
    def _staged(stage_cb, t0: float, outputs) -> float:
        """Compute/fetch boundary for the single-device jit path: wait
        for the device values and report "compute" (launch + program +
        sync, from `t0`); the caller's numpy conversions (fetch =
        device→host readback) run after and end in `_fetch`. No-op
        without a callback."""
        import time as _time
        if stage_cb is None:
            return 0.0
        import jax
        jax.block_until_ready(outputs)
        t1 = _time.perf_counter()
        stage_cb("compute", t1 - t0)
        return t1

    @staticmethod
    def _on_rung(verb: str, blocks: Optional[int], *arrays):
        """-> (n, arrays at the launch's rung). `blocks` None: the
        arrays hold n real blocks each and are padded here, with zero
        blocks, up to the ladder's rung for n (parallel/ladder.py). A
        caller that gathered the launch itself (the batch former's
        staging buffer) has padded already and says how many of the
        rows are real; the small per-row arrays beside the data (SSE
        key and nonce words) are still brought up to it."""
        from ..parallel import ladder
        if blocks is None:
            n = arrays[0].shape[0]
            to = ladder.rung(verb, n)
        else:
            n, to = blocks, arrays[0].shape[0]
        return n, tuple(ladder.pad_blocks(a, to) for a in arrays)

    @staticmethod
    def _fetch(stage_cb, t1: float, n: int, *outputs):
        """Stage "fetch" of the single-device jit path: the device→host
        readback of the first n blocks of every output. A padded
        launch's pad rows are cut off ON THE DEVICE, so no stream sees
        one and none crosses back."""
        if outputs[0].shape[0] != n:
            from ..models.pipeline import head_blocks
            outputs = head_blocks(outputs, n)
        host = tuple(np.asarray(o) for o in outputs)
        if stage_cb is not None:
            import time as _time
            stage_cb("fetch", _time.perf_counter() - t1)
        return host

    def load_encode_program(self, blocks: int, cuts, algo) -> None:
        """Lower and compile, without running them, the programs an
        encode launch at rung `blocks` can need: the fused step through
        the jitted entry point and call form `encode_and_hash_batch`
        uses — so the loaded executable is the one a request hits — and
        the cut of its outputs to each real count in `cuts`. Boot's
        loader (parallel/ladder.load_encode) asks."""
        import jax
        from ..models.pipeline import head_blocks, put_step
        kernel = self._device_hash_kernel(algo)
        data = jax.ShapeDtypeStruct(
            (blocks, self.k, self.shard_size), np.uint8)
        step = put_step.lower(data, self.k, self.m, algo=kernel)
        step.compile()
        for n in cuts:
            head_blocks.lower(tuple(step.out_info), n).compile()

    def encode_and_hash_batch(self, data: np.ndarray, algo,
                              *, force: str = "", stage_cb=None,
                              blocks: Optional[int] = None):
        """Fused device path for the PUT hot loop: one program computes
        parity AND every shard's HighwayHash256 digest (the reference's
        Erasure.Encode + streaming-bitrot work, cmd/erasure-encode.go:75 +
        cmd/bitrot-streaming.go:46, as a single device step).

        data: (B, k, S). Returns (rows, digests (B, k+m, 32)), or None
        when the batch doesn't route to the device or the bitrot
        algorithm has no device kernel. `rows` is an EncodedRows —
        parity (B, m, S) fetched from the device beside a reference to
        `data`; the k data rows never cross back and are not copied —
        or, on the mesh route, that route's own (B, k+m, S) join.
        `parity_rows(rows, k)` reads either.

        stage_cb(stage, seconds), when given, is called as each stage
        ENDS: "h2d" (the fused input's upload, waited for), "compute"
        (launch + device program + sync) and "fetch" (the device→host
        readback of parity + digests) — the batch scheduler's dispatch
        attribution. The mesh path reports a single "compute" stage (its
        sharded programs return host arrays in one step).

        The single-device launch runs at its ladder rung (`_on_rung`):
        `blocks`, when given, says that `data` is padded to it already
        and how many of its rows are real — true of every fused route
        below, whose results hold the real blocks only.
        """
        import time as _time
        kernel = self._device_hash_kernel(algo)
        if kernel is None or self.m == 0:
            return None
        real = data if blocks is None else data[:blocks]
        mesh = self._mesh_route(real.nbytes, force)
        if mesh is not None:
            from ..parallel import mesh as pmesh
            t0 = _time.perf_counter()
            out = pmesh.mesh_encode_and_hash(mesh, real, self.k, self.m,
                                             kernel)
            if out is not None:
                if stage_cb is not None:
                    stage_cb("compute", _time.perf_counter() - t0)
                return out
        path = force or self._route(real.nbytes)
        if path != "device":
            return None
        from ..models.pipeline import put_step
        n, (data,) = self._on_rung("encode", blocks, data)
        (dev,) = self._upload(stage_cb, data)
        t0 = _time.perf_counter()
        parity, digests = put_step(dev, self.k, self.m, algo=kernel)
        t1 = self._staged(stage_cb, t0, (parity, digests))
        # only parity + digests cross back from the device; the k data
        # rows stay the caller's own bytes, referenced and not copied
        parity, digests = self._fetch(stage_cb, t1, n, parity, digests)
        return EncodedRows(real, parity), digests

    def encrypt_encode_and_hash_batch(self, data: np.ndarray, keys,
                                      nonces, pkg_bytes: int, algo,
                                      *, force: str = "",
                                      stage_cb=None,
                                      blocks: Optional[int] = None):
        """Fused device path for the ENCRYPTED PUT hot loop: ChaCha20
        cipher + parity + per-shard digests in one launch
        (models/pipeline.sse_put_step) — an encrypted batch costs the
        same single dispatch as a plaintext one.

        data: (B, k, S) staged PLAINTEXT shards; keys (B, 8) / nonces
        (B, P, 3) u32 word arrays (features/crypto.DeviceSSE.
        batch_params — P·pkg_bytes plaintext bytes per row). Returns
        (full (B, k+m, S) — CIPHERTEXT data rows with parity appended,
        digests (B, k+m, 32)), or None when the batch doesn't route to
        the device (the caller's CPU cipher path is the oracle). The
        mesh has no sse program yet, so mesh-routed hosts fall back to
        the CPU path too.
        """
        import time as _time
        kernel = self._device_hash_kernel(algo)
        if kernel is None or self.m == 0:
            return None
        real = data if blocks is None else data[:blocks]
        path = force or self._route(real.nbytes)
        if path != "device":
            return None
        from ..models.pipeline import sse_put_step
        n, fused = self._on_rung("encode", blocks, data, keys, nonces)
        dev, dkeys, dnonces = self._upload(stage_cb, *fused)
        t0 = _time.perf_counter()
        full, digests = sse_put_step(dev, dkeys, dnonces, self.k,
                                     self.m, pkg_bytes, algo=kernel)
        t1 = self._staged(stage_cb, t0, (full, digests))
        # the data rows DO cross back here: the caller staged plaintext
        # and must write (and Poly1305-tag) the ciphertext
        return self._fetch(stage_cb, t1, n, full, digests)

    def verify_decode_decrypt_batch(self, survivors: np.ndarray,
                                    present_mask: int, shard_len: int,
                                    keys, nonces, pkg_bytes: int, algo,
                                    *, force: str = "", stage_cb=None,
                                    blocks: Optional[int] = None):
        """Fused device path for the ENCRYPTED degraded GET: bitrot-
        verify survivors, reconstruct the missing data rows, and
        decipher the reassembled data shards in one launch
        (models/pipeline.sse_get_step).

        survivors: (B, k, S) in missing_data_matrix `used` order.
        Returns (plain (B, k, S) deciphered data shards in shard-index
        order, missing_idx, survivor_digests (B, k, 32)), or None when
        not device-routed / no device hash kernel / nothing missing.
        Package tags still verify host-side before any of this output
        is served (features/crypto.chacha_decrypt_ranged discipline).
        """
        import time as _time
        kernel = self._device_hash_kernel(algo)
        if kernel is None:
            return None
        real = survivors if blocks is None else survivors[:blocks]
        path = force or self._route(real.nbytes)
        if path != "device":
            return None
        dm, used, missing = rs_matrix.missing_data_matrix(
            self.k, self.m, present_mask)
        if not missing:
            return None
        # static reassembly map: data shard j comes from the survivors
        # stack (decode `used` order) or the reconstructed rows
        # (`missing` order)
        data_src = tuple(
            (0, used.index(j)) if j in used else (1, missing.index(j))
            for j in range(self.k))
        m2 = rs_tpu._bit_expand_cached(dm.tobytes(), dm.shape)
        from ..models.pipeline import sse_get_step
        n, fused = self._on_rung("decode", blocks, survivors, keys,
                                 nonces)
        dev, dkeys, dnonces = self._upload(stage_cb, *fused)
        t0 = _time.perf_counter()
        plain, _ct_missing, digests = sse_get_step(
            dev, m2, dkeys, dnonces, dm.shape[0], self.k,
            data_src, pkg_bytes, shard_len, algo=kernel)
        t1 = self._staged(stage_cb, t0, (plain, digests))
        plain, digests = self._fetch(stage_cb, t1, n, plain, digests)
        return plain, missing, digests

    # -- fused verify + decode / recover (device) --------------------------

    def verify_and_decode_batch(self, survivors: np.ndarray,
                                present_mask: int, shard_len: int, algo,
                                *, force: str = "", stage_cb=None,
                                blocks: Optional[int] = None):
        """Fused device path for the degraded-GET hot loop: ONE program
        bitrot-hashes every survivor shard AND reconstructs only the
        missing data rows (models/pipeline.get_step — the device form of
        cmd/erasure-decode.go:111-150's verify-then-decode).

        survivors: (B, k, S) stacked in missing_data_matrix `used` order.
        Returns (missing (B, r, S), missing_idx, survivor_digests
        (B, k, 32)) as numpy arrays, or None when the batch doesn't route
        to the device / the algorithm has no device kernel / nothing is
        missing (plain verify has no matmul to fuse with).
        """
        import time as _time
        kernel = self._device_hash_kernel(algo)
        if kernel is None:
            return None
        real = survivors if blocks is None else survivors[:blocks]
        mesh = self._mesh_route(real.nbytes, force)
        if mesh is not None:
            from ..parallel import mesh as pmesh
            t0 = _time.perf_counter()
            out = pmesh.mesh_verify_and_decode(
                mesh, real, self.k, self.m, present_mask,
                shard_len, kernel)
            if out is not None:
                if stage_cb is not None:
                    stage_cb("compute", _time.perf_counter() - t0)
                return out
        path = force or self._route(real.nbytes)
        if path != "device":
            return None
        dm, _used, missing = rs_matrix.missing_data_matrix(
            self.k, self.m, present_mask)
        if not missing:
            return None
        m2 = rs_tpu._bit_expand_cached(dm.tobytes(), dm.shape)
        from ..models.pipeline import get_step
        n, (survivors,) = self._on_rung("decode", blocks, survivors)
        (dev,) = self._upload(stage_cb, survivors)
        t0 = _time.perf_counter()
        out, digests = get_step(dev, m2, dm.shape[0], self.k,
                                shard_len, algo=kernel)
        t1 = self._staged(stage_cb, t0, (out, digests))
        out, digests = self._fetch(stage_cb, t1, n, out, digests)
        return out, missing, digests

    def verify_and_recover_batch(self, survivors: np.ndarray,
                                 present_mask: int, rows: "set[int]",
                                 shard_len: int, algo, *,
                                 force: str = "", stage_cb=None,
                                 blocks: Optional[int] = None):
        """Fused device path for heal: verify survivors, rebuild exactly
        the requested lost rows, and digest the rebuilt shards for their
        new bitrot frames (models/pipeline.heal_step).

        Returns (out (B, R, S), idxs, survivor_digests (B, k, 32),
        out_digests (B, R, 32)) or None when not device-routed.
        """
        import time as _time
        kernel = self._device_hash_kernel(algo)
        if kernel is None:
            return None
        real = survivors if blocks is None else survivors[:blocks]
        mesh = self._mesh_route(real.nbytes, force)
        if mesh is not None:
            from ..parallel import mesh as pmesh
            t0 = _time.perf_counter()
            out = pmesh.mesh_verify_and_recover(
                mesh, real, self.k, self.m, present_mask, rows,
                shard_len, kernel)
            if out is not None:
                if stage_cb is not None:
                    stage_cb("compute", _time.perf_counter() - t0)
                return out
        path = force or self._route(real.nbytes)
        if path != "device":
            return None
        rec, idxs = self._recover_rows(present_mask, rows)
        if not idxs:
            return None
        m2 = rs_tpu._bit_expand_cached(rec.tobytes(), rec.shape)
        from ..models.pipeline import heal_step
        n, (survivors,) = self._on_rung("recover", blocks, survivors)
        (dev,) = self._upload(stage_cb, survivors)
        t0 = _time.perf_counter()
        out, sdig, odig = heal_step(dev, m2, rec.shape[0], self.k,
                                    shard_len, algo=kernel)
        t1 = self._staged(stage_cb, t0, (out, sdig, odig))
        out, sdig, odig = self._fetch(stage_cb, t1, n, out, sdig, odig)
        return out, idxs, sdig, odig

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
