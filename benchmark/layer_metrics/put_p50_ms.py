"""S3 endpoint, from outside: median of all PUT latencies of the window, on the clients' clocks."""

from benchlib import readers


def read(win):
    return readers.latency_ms(win, "PUT", 50)
