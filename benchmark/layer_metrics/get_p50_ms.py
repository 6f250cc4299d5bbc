"""S3 endpoint, from outside: median of all GET latencies of the window (the steadier statistic beside get_p90_ms)."""

from benchlib import readers


def read(win):
    return readers.latency_ms(win, "GET", 50)
