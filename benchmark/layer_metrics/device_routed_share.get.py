"""Codec route: decode batches launched on the device over all decode batches (device + cpu_routed)."""

from benchlib import readers


def read(win):
    return readers.device_routed_share(win, "decode")
