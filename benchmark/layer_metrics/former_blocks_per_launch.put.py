"""Batch former: encode blocks per device launch over the window (scheduler.stats)."""

from benchlib import readers


def read(win):
    return readers.blocks_per_launch(win, "encode")
