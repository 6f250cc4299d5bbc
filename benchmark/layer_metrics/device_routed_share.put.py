"""Codec route: encode batches launched on the device over all encode batches (device + cpu_routed)."""

from benchlib import readers


def read(win):
    return readers.device_routed_share(win, "encode")
