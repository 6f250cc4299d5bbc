"""Host<->device + launch: mean of dispatch_seconds stage=compute for encode — launch + H2D + kernel + sync on the HOST clock, not kernel time."""

from benchlib import readers


def read(win):
    return readers.stage_mean_ms(win, "encode", "compute")
