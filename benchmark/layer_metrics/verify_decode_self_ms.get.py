"""Object engine: `pipeline.verify_decode`'s own time a GET, summed over its groups — what is left when its children (`get.decode_wait`, `get.host_verify`) are taken out: the gather of the decode launches' survivors, the digest comparisons, the re-read of a corrupt block. Mean over the window's GETs."""

from benchlib import spanview


def read(win):
    return spanview.request_ms(win, "GET", "pipeline.verify_decode", "self")
