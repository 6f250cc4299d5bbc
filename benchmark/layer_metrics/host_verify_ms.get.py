"""Object engine: `get.host_verify` a GET, summed over its groups — the host's batch bitrot verify of the shards no device launch covered. Mean over the GETs that have one."""

from benchlib import spanview


def read(win):
    return spanview.request_ms(win, "GET", "get.host_verify", "dur")
