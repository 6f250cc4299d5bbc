"""The host's waits inside a request's drive tasks, from the window's
spans: what share of a drive task's wall time its thread spent off
the CPU. Plain arithmetic on the recorder's flat span list, as in
`spanview`; nothing imports the program."""

from __future__ import annotations

from benchlib import spanview


def offcpu_share_pct(win: dict, op: str,
                     names: tuple[str, ...]) -> float | None:
    """100 x sum(duration - cpu_ns) / sum(duration) over the spans
    called one of `names` that carry `cpu_ns`, under the requests of
    `op` that ended in the window. On tmpfs a drive task's time off
    the CPU is its wait for the interpreter lock, a core or a kernel
    lock, not for the medium. None without such spans."""
    w = spanview._window(win, op)
    if w is None:
        return None
    spans, root_name, lo, hi = w
    kids = spanview.children_of(spans)
    wall = off = 0
    for root in spanview.roots(spans, root_name, lo, hi):
        for sp in spanview.subtree(root, kids):
            if sp["name"] in names and "cpu_ns" in sp:
                dur = sp["t1_ns"] - sp["t0_ns"]
                wall += dur
                off += dur - sp["cpu_ns"]
    return 100.0 * off / wall if wall > 0 else None
