"""Object engine: `get.decode_wait` a GET, summed over its groups — the stream's wait on the batch former's future of its degraded blocks. Mean over the GETs that have one (the objects that lost a data shard)."""

from benchlib import spanview


def read(win):
    return spanview.request_ms(win, "GET", "get.decode_wait", "dur")
