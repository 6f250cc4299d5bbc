"""Host-device transfer and launch: mean host-side assembly of a decode launch's fused input (np.concatenate of the groups) before the upload (dispatch_seconds stage=transfer)."""

from benchlib import readers


def read(win):
    return readers.stage_mean_ms(win, "decode", "transfer")
