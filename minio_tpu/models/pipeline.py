"""Flagship device pipelines: the "models" of this framework.

Where an ML framework has model families, an object store has data-path
pipelines. Each is one jitted function over batched shard tensors, one
launch a batch:

  * put_step      — PUT hot loop: a batch of blocks -> parity shards +
    every shard's bitrot digest (the reference's Erasure.Encode loop,
    cmd/erasure-encode.go:75-146, with cmd/bitrot-streaming.go's hash).
  * sse_put_step  — the same with the ChaCha20 cipher in front of it.
  * get_step      — GET with failures: verify the survivor shards and
    rebuild the missing data shards (cmd/erasure-decode.go).
  * sse_get_step  — the same with the decipher behind it.
  * heal_step     — verify, rebuild exactly the lost shards through the
    recover matrix, and digest them for their new frames
    (cmd/erasure-lowlevel-heal.go:28-48 collapsed to one device op).

object/codec.py's table of fused programs (`FUSED`) says how each is
called; `Codec._launch` is their one caller. All are shape-static per
(k, m, S, B) and cached. The B ladder lives in parallel/ladder.py: a
launch of any block count is padded with zero blocks up to its rung (by
the batch former in its staging buffer, by object/codec.py on the
direct route), so a geometry launches a closed set of programs at
full-block S — which boot loads for the encode verb.

Every S-wide output of a step (parity, rebuilt rows, ciphertext)
leaves it in the LINK FORM, `link_rows`: the rows' bytes as 32-bit
words. `host_rows` is the other side: a (B, r, S) uint8 view of what
crossed, no byte copied. Digests are 32 bytes a row and stay uint8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import chacha20_jax, rs_matrix, rs_tpu


# ---------------------------------------------------------------------------
# The flagship jittable step (what __graft_entry__.entry() exposes)
# ---------------------------------------------------------------------------

# Name scopes of the fused steps: metadata only (the programs' outputs
# are byte-identical), so a profiler trace groups device time by what
# the work IS — `rs_matmul` (the GF(2^8) matmul; its Pallas call is
# named `gf_matmul`), `bitrot_hash`, `pack` (the concatenate / reshape
# that feeds the hash and unpacks its digests), `cipher` — instead of
# by XLA's serial numbers (`while.83`), which any edit renumbers.


# bytes of a word of the link form
_WORD = 4
# a result of fewer blocks AND fewer rows a block than this is brought
# to words at this many blocks (see `link_rows`)
_SMALL = 8


def link_rows(rows: jax.Array) -> jax.Array:
    """(B, r, S) uint8 rows -> (B, r, ceil(S / 4)) uint32: the form an
    S-wide output crosses the host-device link in. The same bytes in
    the same order — a row's four consecutive bytes are one
    little-endian word; an S that is no multiple of the word is padded
    with zero columns (12+4: 349526 -> 349528) — so the host gets its
    rows back as a view (`host_rows`). Why: a uint8 array lies on the
    chip with four ROWS' bytes interleaved in each word (tiling
    (8,128)(4,1)); a result of 32 MiB or more in that form (8+8's
    parity) reads back at 0.64 GiB/s, a uint32 array of the same
    bytes at 2.4-3.0 (PERF.md §6, PR 34: tools/readback_probe.py).

    A SMALL result — under 8 blocks of under 8 rows: the low rungs at
    12+4, a decode of a few blocks — is padded with zero blocks to 8
    for the conversion and cut back, still on the device: XLA's TPU
    lowering of this conversion takes 30-55 s to COMPILE at those
    shapes and under 2 s at 8 blocks (the conversion's run time is
    flat in B), and boot loads those rungs."""
    b, r, s = rows.shape
    with jax.named_scope("pack"):
        more = _SMALL - b if b < _SMALL and r < _SMALL else 0
        if more or s % _WORD:
            rows = jnp.pad(rows, ((0, more), (0, 0), (0, -s % _WORD)))
        return jax.lax.bitcast_convert_type(
            rows.reshape(b + more, r, -1, _WORD), jnp.uint32)[:b]


def host_rows(crossed: np.ndarray, shard_len: int) -> np.ndarray:
    """What a step's output crossed back as -> what it IS, as a view:
    an array in the link form (uint32 words) -> (B, r, shard_len)
    uint8, every row C-contiguous; any other output (the digests) as
    it is."""
    if crossed.dtype != np.uint32:
        return crossed
    return crossed.view(np.uint8)[..., :shard_len]


def _rs_matmul(matrix_bits, shards, r: int, k: int):
    with jax.named_scope("rs_matmul"):
        return rs_tpu._apply_matrix_impl(
            jnp.asarray(matrix_bits), shards, r, k,
            rs_tpu.default_use_pallas())


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def put_step(data: jax.Array, k: int, m: int, shard_len: int = 0,
             key: bytes = b"", algo: str = "highwayhash"
             ) -> tuple[jax.Array, jax.Array]:
    """One PUT device step: RS-encode a batch of blocks AND compute each
    shard's streaming-bitrot digest — the full reference per-block PUT
    work (cmd/erasure-encode.go:75-146 + cmd/bitrot-streaming.go:46-58)
    as one device program.

    data: (B, k, S) uint8 data shards. S may include right zero-padding
    (GF coding is column-independent, so padded columns encode to zeros);
    shard_len (< = S, default S) is the true shard byte-length the bitrot
    digests must cover. algo: "highwayhash" (keyed HH256, the default
    bitrot) or "sha256".
    Returns (parity (B, m, S) in the link form, digests (B, k+m, 32)
    uint8 in shard order data-then-parity) — byte-identical to the CPU
    bitrot path (minio_tpu/bitrot.py). The caller already holds the
    data rows, so only parity + digests cross back to the host.
    """
    b, k_, s = data.shape
    assert k_ == k
    shard_len = shard_len or s
    pm = np.asarray(rs_matrix.parity_matrix(k, m))
    m2 = rs_tpu._bit_expand_cached(pm.tobytes(), pm.shape)
    parity = _rs_matmul(m2, data, m, k)

    # one hash scan over data+parity rows together: splitting into two
    # scans measures slower (the small parity-only scan underfills the
    # vector lanes and doubles loop overhead)
    with jax.named_scope("pack"):
        rows = jnp.concatenate([data, parity],
                               axis=-2).reshape(b * (k + m), s)
    digests = _hash_rows(rows, shard_len, key, algo)
    with jax.named_scope("pack"):
        return link_rows(parity), digests.reshape(b, k + m, 32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def sse_put_step(data: jax.Array, keys: jax.Array, nonces: jax.Array,
                 k: int, m: int, pkg_bytes: int, shard_len: int = 0,
                 key: bytes = b"", algo: str = "highwayhash"
                 ) -> tuple[jax.Array, jax.Array]:
    """One ENCRYPTED PUT device step: ChaCha20-cipher each block, RS-
    encode the ciphertext, and digest every shard — the tentpole fusion.
    An encrypted batch costs the same single launch as a plaintext one;
    the host's only remaining cipher work is the Poly1305 tag trailer
    over the ciphertext this step returns (no laundered auth).

    data:   (B, k, S) uint8 staged shards whose flat (B, k·S) view holds
            the plaintext block in its first P·pkg_bytes bytes, zeros
            after (codec.split pad discipline). Only the plaintext span
            is ciphered — the keystream is zero-padded to k·S, so pad
            columns stay zero and the stored stream is byte-identical
            to the CPU ChaChaEncryptor path.
    keys:   (B, 8) uint32 per-row ChaCha20 key words; nonces (B, P, 3)
            uint32 per-row per-package nonce words (features/crypto.
            DeviceSSE.batch_params — rows of DIFFERENT objects coalesce
            because the bucket key carries only these arrays' shapes).
    Returns (full (B, k+m, S) in the link form — ciphertext data
    shards with parity appended, digests (B, k+m, 32)). Unlike put_step
    the data rows DO cross back: the caller staged plaintext and must
    write (and tag) ciphertext.
    """
    b, k_, s = data.shape
    assert k_ == k
    p = nonces.shape[1]
    ct_bytes = p * pkg_bytes
    with jax.named_scope("cipher"):
        ks = chacha20_jax.keystream_u8(keys, nonces, ct_bytes, pkg_bytes)
        if ct_bytes < k * s:
            ks = jnp.concatenate(
                [ks, jnp.zeros((b, k * s - ct_bytes), jnp.uint8)],
                axis=-1)
        ct = (jnp.asarray(data, jnp.uint8).reshape(b, k * s)
              ^ ks).reshape(b, k, s)
    pm = np.asarray(rs_matrix.parity_matrix(k, m))
    m2 = rs_tpu._bit_expand_cached(pm.tobytes(), pm.shape)
    parity = _rs_matmul(m2, ct, m, k)
    with jax.named_scope("pack"):
        rows = jnp.concatenate([ct, parity], axis=-2)
        flat = rows.reshape(b * (k + m), s)
    digests = _hash_rows(flat, shard_len or s, key, algo)
    with jax.named_scope("pack"):
        return link_rows(rows), digests.reshape(b, k + m, 32)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def sse_get_step(survivors: jax.Array, matrix_bits: jax.Array,
                 keys: jax.Array, nonces: jax.Array, r: int, k: int,
                 data_src: tuple = (), pkg_bytes: int = 0,
                 shard_len: int = 0, key: bytes = b"",
                 algo: str = "highwayhash"
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One ENCRYPTED degraded-GET device step: verify → decode →
    decipher fused. Reconstructs the missing rows from the survivors,
    reassembles the kd ciphertext data shards, and XORs the per-package
    keystream back off — the plaintext block leaves the device in the
    same launch that verified and decoded it. (Poly1305 package tags
    still verify host-side against the trailer BEFORE any output of
    this step is served.)

    data_src: static tuple with one (src, idx) per data shard — src 0
    takes survivors[:, idx] (shard arrived intact, in decode `used`
    order), src 1 takes reconstructed[:, idx] (in `missing` order).
    keys (B, 8) / nonces (B, P, 3): word arrays for the block's
    packages, plaintext span = P·pkg_bytes of the flat (B, kd·S) view.
    Returns (plain (B, kd, S) deciphered data shards in the link form,
    missing (B, r, S) uint8 reconstructed CIPHERTEXT shards — what a
    heal would write back; they stay on the device, so as they are —
    survivor digests (B, k, 32) for host bitrot comparison).
    """
    b, k_, s = survivors.shape
    assert k_ == k
    out, digests = _reconstruct_and_hash(
        survivors, matrix_bits, r, k, shard_len, key, algo)
    kd = len(data_src)
    with jax.named_scope("pack"):
        stacked = jnp.stack(
            [survivors[:, i] if src == 0 else out[:, i]
             for src, i in data_src], axis=1)
    ct_bytes = nonces.shape[1] * pkg_bytes
    with jax.named_scope("cipher"):
        ks = chacha20_jax.keystream_u8(keys, nonces, ct_bytes, pkg_bytes)
        if ct_bytes < kd * s:
            ks = jnp.concatenate(
                [ks, jnp.zeros((b, kd * s - ct_bytes), jnp.uint8)],
                axis=-1)
        plain = (stacked.reshape(b, kd * s) ^ ks).reshape(b, kd, s)
    return link_rows(plain), out, digests[:, :k]


def _hash_rows(rows: jax.Array, shard_len: int, key: bytes,
               algo: str) -> jax.Array:
    """(N, S) rows -> (N, 32) bitrot digests over the first shard_len
    bytes, on device (shared by put/get/heal steps)."""
    from ..bitrot import MAGIC_HIGHWAYHASH_KEY
    with jax.named_scope("bitrot_hash"):
        if algo == "sha256":
            from ..ops import sha256_jax
            return sha256_jax._sha256_impl(rows, shard_len)
        from ..ops import highwayhash_jax
        return highwayhash_jax._hh256_impl(
            rows, shard_len, bytes(key or MAGIC_HIGHWAYHASH_KEY))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def get_step(survivors: jax.Array, matrix_bits: jax.Array, r: int,
             k: int, shard_len: int = 0, key: bytes = b"",
             algo: str = "highwayhash") -> tuple[jax.Array, jax.Array]:
    """One degraded-GET device step: verify AND reconstruct in a single
    dispatch — the reference treats bitrot verification as inseparable
    from decode (streamingBitrotReader.ReadAt inside Erasure.Decode,
    cmd/bitrot-streaming.go:111-150 + cmd/erasure-decode.go:211), so the
    device program fuses them: one pass over the survivor rows feeds both
    the bitrot hash scan and the missing-row GF matmul.

    survivors:   (B, k, S) uint8 — the k surviving shards of each block,
                 stacked in missing_data_matrix `used` order.
    matrix_bits: (8r, 8k) 0/1 — bit-expanded missing-data matrix (only
                 the rows a GET actually needs, not the full k x k).
    shard_len:   true payload bytes per shard frame (digest coverage).
    Returns (missing (B, r, S) in the link form — the reconstructed
    shards in `missing` index order, digests (B, k, 32) uint8 —
    computed frame digests of the survivors, for the host to compare
    against the frame digests read from disk).
    """
    missing, digests = _reconstruct_and_hash(
        survivors, matrix_bits, r, k, shard_len, key, algo)
    return link_rows(missing), digests[:, :k]


def _reconstruct_and_hash(survivors, matrix_bits, r, k, shard_len,
                          key, algo):
    """Shared fused core of get_step/heal_step: matmul the requested
    rows, then ONE hash scan over [survivors ‖ reconstructed]. Hashing
    the concat (not a reshaped view of the input argument) matters:
    the argument's layout pins the scan and measures ~4-5x slower on
    TPU — the concat lets XLA pick the scan-friendly layout, and the r
    extra hashed rows are noise (r << k). Returns (reconstructed
    (B, r, S), digests (B, k+r, 32) — survivors first)."""
    b, k_, s = survivors.shape
    assert k_ == k
    shard_len = shard_len or s
    out = _rs_matmul(matrix_bits, survivors, r, k)
    with jax.named_scope("pack"):
        rows = jnp.concatenate([survivors, out],
                               axis=-2).reshape(b * (k + r), s)
    digests = _hash_rows(rows, shard_len, key, algo)
    with jax.named_scope("pack"):
        return out, digests.reshape(b, k + r, 32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def heal_step(survivors: jax.Array, matrix_bits: jax.Array, r: int,
              k: int, shard_len: int = 0, key: bytes = b"",
              algo: str = "highwayhash"
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One heal device step: verify the survivors, recover the lost
    shards, AND digest the recovered shards for their new bitrot frames —
    the reference's decode→pipe→re-encode→rehash
    (cmd/erasure-lowlevel-heal.go:28-48 + both bitrot sides) as one
    program. The recovered rows never leave the device between the matmul
    and their frame digests.

    survivors:   (B, k, S) uint8 in recover_matrix `used` order.
    matrix_bits: (8r, 8k) bit-expanded recover matrix (r = lost shards,
                 data and parity rows both).
    Returns (recovered (B, r, S) in the link form, survivor_digests
    (B, k, 32), recovered_digests (B, r, 32)) — the last are the
    digests the healer writes into the rebuilt shards'
    streaming-bitrot frames.
    """
    b, k_, s = survivors.shape
    recovered, digests = _reconstruct_and_hash(
        survivors, matrix_bits, r, k, shard_len, key, algo)
    return link_rows(recovered), digests[:, :k], digests[:, k:]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def put_step_ragged(data: jax.Array, lengths: jax.Array, k: int, m: int,
                    key: bytes = b"", algo: str = "highwayhash"
                    ) -> tuple[jax.Array, jax.Array]:
    """`put_step` for a launch some of whose blocks are SHORT: the
    short last block of an object rides the group of its whole blocks.

    data: (B, k, S) uint8 at the geometry's full-block S; a short
    block's shards fill the first lengths[b] columns of its rows and
    the rest is zero. lengths: (B,) int32, each block's own shard
    length (ceil(block bytes / k); S for a whole block and for a pad
    block). GF coding is column-independent, so the zero columns encode
    to zero parity and the first lengths[b] columns are exactly what
    the block alone would encode to; the digests cover lengths[b] bytes
    of each of the block's k+m rows (ops/highwayhash_jax.
    hh256_batch_ragged — the lengths are an operand, so one program a
    (B, k, S) serves every mix of short blocks). Returns (parity
    (B, m, S) in the link form, digests (B, k+m, 32)) as `put_step`
    does; the host keeps parity[b, :, :lengths[b]]. Only HighwayHash
    has the ragged kernel.
    """
    b, k_, s = data.shape
    assert k_ == k and algo == "highwayhash"
    from ..bitrot import MAGIC_HIGHWAYHASH_KEY
    from ..ops import highwayhash_jax
    pm = np.asarray(rs_matrix.parity_matrix(k, m))
    m2 = rs_tpu._bit_expand_cached(pm.tobytes(), pm.shape)
    parity = _rs_matmul(m2, data, m, k)
    with jax.named_scope("pack"):
        rows = jnp.concatenate([data, parity],
                               axis=-2).reshape(b * (k + m), s)
        row_lengths = jnp.repeat(lengths.astype(jnp.int32), k + m)
    with jax.named_scope("bitrot_hash"):
        digests = highwayhash_jax._hh256_ragged_impl(
            rows, row_lengths, bytes(key or MAGIC_HIGHWAYHASH_KEY))
    with jax.named_scope("pack"):
        return link_rows(parity), digests.reshape(b, k + m, 32)
