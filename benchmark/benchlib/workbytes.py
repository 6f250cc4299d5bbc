"""The least bytes a verb's device work must move, from the blocks the
former dispatched and the geometry — whatever program implements it.

Per ENCODED block: k*S data bytes in, m*S parity bytes out, (k+m)*32
digest bytes out (the data rows are on the host already and need not
come back). Per DECODED block with r lost data shards: k*S survivor
bytes in, r*S rebuilt bytes out, k*32 survivor digests out; r is what
each launch was given (the pulled drive's shard, and a second where
the read plan hedged), so a window's decode blocks are priced by the
shares of r among the blocks submitted in it. The bound
is bytes (HBM traffic): GF(2^8) coding and the hash are integer work
with no published peak to hold them to; the byte floor is the one
quantity a v5e data sheet prices.
"""

from __future__ import annotations

DIGEST = 32


def shard_size(block_size: int, k: int) -> int:
    return -(-block_size // k)


def encode_bytes(blocks: int, k: int, m: int, block_size: int) -> int:
    s = shard_size(block_size, k)
    return blocks * (k * s + m * s + (k + m) * DIGEST)


def decode_bytes(blocks: int, k: int, r: int, block_size: int) -> int:
    s = shard_size(block_size, k)
    return blocks * (k * s + r * s + k * DIGEST)


def verb_bytes(verb: str, blocks: int, k: int, m: int, block_size: int,
               by_lost: dict | None = None) -> float:
    """`by_lost` (decode): {r: blocks submitted with r data shards
    lost}; the dispatched `blocks` are priced in those shares. Without
    it every decoded block is priced at r = 1, the least there is."""
    if verb == "encode":
        return encode_bytes(blocks, k, m, block_size)
    if verb == "decode":
        by_lost = {int(r): n for r, n in (by_lost or {}).items() if n}
        total = sum(by_lost.values())
        if not total:
            return decode_bytes(blocks, k, 1, block_size)
        return sum(decode_bytes(blocks, k, r, block_size) * n / total
                   for r, n in by_lost.items())
    raise ValueError(f"no byte function for verb {verb!r}")
