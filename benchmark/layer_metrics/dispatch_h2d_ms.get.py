"""Host-device transfer and launch: mean upload of a decode launch's fused input, waited for before the step (dispatch_seconds stage=h2d); dispatch_compute_ms is launch + kernel + sync after it."""

from benchlib import readers


def read(win):
    return readers.stage_mean_ms(win, "decode", "h2d")
