"""Batch former: mean wait of decode groups from the moment the collector turned to them until their dispatch began — the group's own wait on an in-flight slot plus the pool hand-off (dispatch_seconds stage=slot); former_queue_ms is this plus stage=collect."""

from benchlib import readers


def read(win):
    return readers.stage_mean_ms(win, "decode", "slot")
