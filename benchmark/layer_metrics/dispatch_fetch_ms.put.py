"""Host-device transfer and launch: mean device-to-host readback and result assembly of a encode launch (dispatch_seconds stage=fetch)."""

from benchlib import readers


def read(win):
    return readers.stage_mean_ms(win, "encode", "fetch")
