"""Drives: mean wait of one drive-pool task for a thread (`drive_pool.wait`: submit -> start on a worker, every fan-out stage) under the window's GETs."""

from benchlib import spanview


def read(win):
    return spanview.group_ms(win, "GET", "drive_pool.wait")
