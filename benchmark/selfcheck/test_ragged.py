"""The yardstick of the cell whose objects end in a short block: the
byte function that prices every block at its own shard length, and the
four readers of what the former counts about short blocks."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from benchlib import harness, workbytes, workbytes_ragged  # noqa: E402

K, M, BLOCK = 12, 4, 1 << 22
S = workbytes.shard_size(BLOCK, K)                  # 349526
S_T = workbytes.shard_size(BLOCK // 2, K)           # 174763


@pytest.mark.parametrize("blocks", [1, 3, 16, 1000])
def test_all_whole_blocks_are_workbytes_exactly(blocks):
    assert workbytes_ragged.encode_bytes(blocks, 0, 0, K, M, BLOCK) \
        == workbytes.encode_bytes(blocks, K, M, BLOCK)
    assert workbytes_ragged.stretch_bytes(blocks, 7 * blocks, 0, 0,
                                          K, M, BLOCK) \
        == workbytes.encode_bytes(blocks, K, M, BLOCK)


def test_a_2p5_block_object_is_2p5_of_3_blocks_plus_the_digests():
    got = workbytes_ragged.encode_bytes(3, 1, S_T, K, M, BLOCK)
    digests = 3 * (K + M) * workbytes.DIGEST
    three = workbytes.encode_bytes(3, K, M, BLOCK)
    # shard bytes: 2 S + S_t of 3 S a row; the digests are a block's own
    assert got - digests == (K + M) * (2 * S + S_T)
    assert 2 * S_T == S
    assert got - digests == (three - digests) * 2.5 / 3
    # workbytes alone would over-read the cell by 3 / 2.5
    assert three / got == pytest.approx(3 / 2.5, rel=1e-3)


def test_a_stretch_is_priced_by_the_windows_shares():
    # window: 300 blocks, 100 of them short at S_t; the stretch saw 30
    got = workbytes_ragged.stretch_bytes(30, 300, 100, 100 * S_T,
                                         K, M, BLOCK)
    assert got == pytest.approx(
        workbytes_ragged.encode_bytes(30, 10, 10 * S_T, K, M, BLOCK))
    assert workbytes_ragged.stretch_bytes(30, 0, 0, 0, K, M, BLOCK) == 0.0


def _verbs(**over):
    base = {"batches": 0, "blocks": 0, "cpu_routed": 0}
    base.update(over)
    return {"encode": base}


def _win(v0: dict, v1: dict, puts: int = 0, trace=None) -> dict:
    rec = (0.0, 1.0, 10 << 20, 200, True, "k", 0, 0)
    return {"op": "PUT", "verb": "encode", "records": [rec] * puts,
            "c0": {"verbs": _verbs(**v0)}, "c1": {"verbs": _verbs(**v1)},
            "geometry": {"k": K, "m": M, "block_size": BLOCK},
            "peak": {"hbm_bytes_per_s": 819e9}, "trace": trace}


NEW = {"groups": 0, "ragged_batches": 0, "short_blocks": 0,
       "short_shard_bytes": 0, "pad_bytes": 0, "uploaded_bytes": 0}


def test_readers_on_a_hand_made_window():
    v0 = dict(NEW, batches=10, blocks=30, groups=10, ragged_batches=10,
              short_blocks=10, short_shard_bytes=10 * S_T,
              uploaded_bytes=40 * K * S, pad_bytes=15 * K * S)
    v1 = dict(NEW, batches=110, blocks=630, groups=210, ragged_batches=100,
              short_blocks=210, short_shard_bytes=210 * S_T,
              uploaded_bytes=740 * K * S, pad_bytes=215 * K * S)
    trace = {"busy_s": 0.1, "blocks": {"encode": 60}}
    win = _win(v0, v1, puts=200, trace=trace)
    read = harness.load_reader
    assert read("ragged_launch_share.put")(win) == pytest.approx(90.0)
    assert read("groups_per_put.put")(win) == pytest.approx(1.0)
    assert read("pad_byte_share.put")(win) == pytest.approx(
        100 * 200 / 700)
    # 600 blocks in the window, 200 of them short: a third of the
    # stretch's 60 blocks are priced at S_t
    least = workbytes_ragged.encode_bytes(60, 20, 20 * S_T, K, M, BLOCK)
    assert read("ragged_step_hbm_share.put")(win) == pytest.approx(
        100 * least / 819e9 / 0.1)
    # an untraced window has no device time to hold the bytes to
    assert read("ragged_step_hbm_share.put")(_win(v0, v1, 200)) is None


@pytest.mark.parametrize("name", [
    "ragged_launch_share.put", "groups_per_put.put", "pad_byte_share.put",
    "ragged_step_hbm_share.put"])
def test_readers_find_nothing_at_a_program_without_the_counters(name):
    """The parent's `stats()` has no such fields: None, no exception."""
    old = {"batches": 5, "blocks": 12}
    trace = {"busy_s": 0.1, "blocks": {"encode": 6}}
    assert harness.load_reader(name)(
        _win(old, dict(old, batches=50, blocks=120), 40, trace)) is None
    # and in a cell of the other verb
    win = _win(dict(NEW), dict(NEW, batches=3, blocks=9), 3, trace)
    win.update(op="GET", verb="decode")
    assert harness.load_reader(name)(win) is None
