"""Fused step: least bytes the dispatched encode blocks must move / HBM peak / device busy time, over the traced stretch."""

from benchlib import readers


def read(win):
    return readers.hbm_share(win, "encode")
