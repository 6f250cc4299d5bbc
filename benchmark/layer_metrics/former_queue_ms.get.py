"""Batch former: mean submit -> dispatch-start wait of decode groups (dispatch_seconds stage=queue)."""

from benchlib import readers


def read(win):
    return readers.stage_mean_ms(win, "decode", "queue")
