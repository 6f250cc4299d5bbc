"""Drives: share of the wall time of the window's GETs' `disk.shard_read` spans that their threads spent off the CPU (duration minus `cpu_ns`)."""

from benchlib import hostwait


def read(win):
    return hostwait.offcpu_share_pct(win, "GET", ("disk.shard_read",))
