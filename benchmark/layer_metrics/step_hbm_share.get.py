"""Fused step: least bytes the dispatched decode blocks must move / HBM peak / device busy time, over the traced stretch."""

from benchlib import readers


def read(win):
    return readers.hbm_share(win, "decode")
