"""The keeper of the RAM-backed drive tree, a process of its own (no
JAX, nothing of the program) beside every run.

1. It outlives a harness that is killed. The drives are tmpfs: a tree
   left behind is RAM that every later run on the machine lacks. The
   harness holds this process's stdin; it says "stop" before it removes
   the tree itself. When stdin closes WITHOUT that word the harness is
   gone (a kill skips its `finally`), the server died with it, and this
   process removes the tree.

2. During a PUT window (`keep_one_in` > 0) it expires objects, as a
   bucket lifecycle rule would, because no window worth measuring fits
   in the host's memory otherwise: at 450 MiB/s of PUTs a 12+4 set
   takes 600 MiB/s of shards, an 8+8 set 900. It removes, straight from
   the drive directories, the objects of the window's PUT prefix that
   are older than `min_age_s` and that the output check will not
   sample — one in `keep_one_in` survives. The server sees nothing of
   it: an acknowledged object that nobody reads again disappears from
   under it. What this costs the measured rate is in PERF.md section 4
   (a pair of runs with and without it).

Run as a script: argv[1] is {"root": ..., "drives": [...], "prefix_dir":
"bench/put", "keep_one_in": 8, "min_age_s": 3.0}.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time

SWEEP_S = 0.5


def sequence_number(key: str) -> int:
    return int(key.rsplit("-", 1)[1])


def kept(key: str, keep_one_in: int) -> bool:
    return keep_one_in <= 1 or sequence_number(key) % keep_one_in == 0


def sweep(spec: dict) -> int:
    """One pass over the first drive's listing; -> objects removed."""
    first = os.path.join(spec["drives"][0], spec["prefix_dir"])
    try:
        names = os.listdir(first)
    except FileNotFoundError:
        return 0
    now, removed = time.time(), 0
    for name in names:
        try:
            if kept(name, spec["keep_one_in"]) or now - os.stat(
                    os.path.join(first, name)).st_mtime < spec["min_age_s"]:
                continue
        except (ValueError, FileNotFoundError):
            continue
        # the first drive's copy goes last: it is the listing
        for drive in reversed(spec["drives"]):
            shutil.rmtree(os.path.join(drive, spec["prefix_dir"], name),
                          ignore_errors=True)
        removed += 1
    return removed


def main(spec: dict) -> None:
    done = threading.Event()
    said = []

    def listen() -> None:
        said.append(sys.stdin.readline().strip())
        done.set()
    threading.Thread(target=listen, daemon=True).start()
    removed = 0
    while not done.wait(SWEEP_S):
        if spec.get("keep_one_in", 0) > 1:
            removed += sweep(spec)
    if said != ["stop"]:
        shutil.rmtree(spec["root"], ignore_errors=True)
        try:
            os.rmdir(os.path.dirname(spec["root"]))    # if no other run's
        except OSError:
            pass
        print(f"janitor: the harness is gone — removed {spec['root']}",
              file=sys.stderr, flush=True)
    print(f"janitor removed {removed} objects", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main(json.loads(sys.argv[1]))
