"""Drives: share of the wall time of the window's PUTs' `disk.shard_write` and `disk.rename_data` spans that their threads spent off the CPU (duration minus `cpu_ns`)."""

from benchlib import hostwait


def read(win):
    return hostwait.offcpu_share_pct(
        win, "PUT", ("disk.shard_write", "disk.rename_data"))
