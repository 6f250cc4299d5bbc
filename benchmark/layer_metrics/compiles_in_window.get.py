"""Fused step: programs built or loaded from the compile cache between the window's marks (expected 0)."""

from benchlib import readers


def read(win):
    return readers.compiles(win, "decode")
