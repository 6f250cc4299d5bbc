"""Batch former: share of the window its one collector thread spent blocked acquiring an in-flight slot for a decode group (dispatch_seconds stage=collector_blocked, summed) — while it lasts no bucket of any verb is drained."""

from benchlib import spanview


def read(win):
    return spanview.stage_share_pct(win, "decode", "collector_blocked")
