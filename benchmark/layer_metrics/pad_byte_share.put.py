"""Host-device transfer and launch: zero bytes (pad blocks up to the rung, a short block's columns past its own length) over all bytes of the encode launches' uploaded data arrays, over the window (scheduler.stats: pad_bytes / uploaded_bytes). None where the program has no such counters."""


def read(win):
    v0, v1 = win["c0"]["verbs"]["encode"], win["c1"]["verbs"]["encode"]
    if win["verb"] != "encode" or "uploaded_bytes" not in v1:
        return None
    uploaded = v1["uploaded_bytes"] - v0["uploaded_bytes"]
    if not uploaded:
        return None
    return 100.0 * (v1["pad_bytes"] - v0["pad_bytes"]) / uploaded
