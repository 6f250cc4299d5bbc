"""The least bytes an encode launch must move when some of its blocks
are SHORT — an object's last block, whose shard length S_t is its own
(`ceil(bytes / k)`), not the full block's S.

`workbytes.encode_bytes` prices every dispatched block at the full
block; a store whose objects are not whole numbers of blocks would be
over-read by it (a 10 MiB object at 4 MiB blocks: 3 / 2.5). Here each
block is priced at ITS OWN shard length: k*S_b data bytes in, m*S_b
parity bytes out, (k+m)*32 digest bytes out — what the work is,
whatever program does it (zero columns a program pads a short block
with are its own cost, not the work's).

The former counts, per window, the blocks it dispatched, how many of
them were short and the sum of their shard lengths; a traced stretch
knows only its blocks, so the window's share of short blocks and their
mean shard length are applied to the stretch's blocks, as
`workbytes.verb_bytes` applies `decode_by_lost`'s shares.
"""

from __future__ import annotations

from benchlib import workbytes


def block_bytes(shard_len: float, k: int, m: int) -> float:
    """Least bytes of one encoded block whose shards are `shard_len`."""
    return (k + m) * (shard_len + workbytes.DIGEST)


def encode_bytes(blocks: float, short_blocks: float,
                 short_shard_bytes: float, k: int, m: int,
                 block_size: int) -> float:
    """`blocks` dispatched, `short_blocks` of them short with
    `short_shard_bytes` shard bytes (sum of S_t) between them."""
    s = workbytes.shard_size(block_size, k)
    return (blocks - short_blocks) * block_bytes(s, k, m) \
        + (k + m) * (short_shard_bytes + short_blocks * workbytes.DIGEST)


def stretch_bytes(stretch_blocks: float, window_blocks: float,
                  window_short_blocks: float,
                  window_short_shard_bytes: float, k: int, m: int,
                  block_size: int) -> float:
    """A traced stretch's `stretch_blocks`, priced with the window's
    share of short blocks and their mean shard length."""
    if not window_blocks:
        return 0.0
    scale = stretch_blocks / window_blocks
    return encode_bytes(stretch_blocks, window_short_blocks * scale,
                        window_short_shard_bytes * scale, k, m, block_size)
