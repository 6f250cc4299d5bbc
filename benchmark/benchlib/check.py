"""The comparison that decides `correct`: what the timed requests
produced, at the timed sizes, against the plain reference.

Every number compared is exact (bytes, counts), so every limit is 0 or
a floor the configuration states (the write quorum). Each is returned
as name -> [value, limit, "max"|"min"]; a run is correct when every
value is within its limit.
"""

from __future__ import annotations

import glob
import http.client
import os

import numpy as np

from benchlib import janitor, loadgen, reference, sigv4, traffic


def write_quorum(k: int, m: int) -> int:
    """Upstream's: k data shards, one more when k == m."""
    return k + 1 if k == m else k


def verdict(numbers: dict) -> bool:
    return all(v <= lim if how == "max" else v >= lim
               for v, lim, how in numbers.values())


def _get(port: int, key: str) -> tuple[int, bytes]:
    path = f"/{traffic.BUCKET}/{key}"
    hdrs = sigv4.sign("GET", path, {}, f"127.0.0.1:{port}",
                      loadgen.EMPTY_SHA, loadgen.ACCESS_KEY,
                      loadgen.SECRET_KEY)
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=loadgen.REQUEST_TIMEOUT_S)
    try:
        conn.request("GET", path, headers=hdrs)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def device_path(verb: str, c0: dict, c1: dict,
                rehearsal: bool = False) -> dict:
    """The run took the device path: launches of the cell's verb ran on
    the device inside the window, none raised, none was declined for
    want of a device."""
    v0, v1 = c0["verbs"][verb], c1["verbs"][verb]
    bad = [d for d in c1["declines"]
           if d.get("reason") in ("no-device", "error")
           and not (rehearsal and d.get("stage") == "boot")]
    return {
        "device_launches": [v1["batches"] - v0["batches"], 1, "min"],
        "dispatch_errors": [sum(v["errors"] for v in c1["verbs"].values()),
                            0, "max"],
        "device_declines": [len(bad), 0, "max"],
    }


def put_cell(seed: int, mix: dict, k: int, m: int, block_size: int,
             port: int, drive_paths: list[str], in_window: list) -> dict:
    """PUT cells. Every answer in the window: 200 + the body's MD5 as
    ETag (the clients checked; `in_window` carries their verdicts). A
    sample of `check_sample` objects drawn from the seed, among those
    the janitor keeps, is held to the reference on every drive: the
    part file's length, and the frames (digest + shard: parity rows,
    bitrot digests, framing, placement) of all blocks for the first
    `check_whole` of them and of `check_blocks` blocks drawn from the
    seed, one in each equal stretch of the object, for the rest — many
    objects, so many launches of every size, at the reference's cost
    of a few. Each is also read back through the endpoint."""
    wrong_answers = sum(1 for r in in_window if r[3] == 200 and not r[4])
    good = [r for r in in_window
            if r[4] and janitor.kept(r[5], mix.get("keep_one_in", 0))]
    picked = traffic.sample(seed, good, mix["check_sample"])
    n, s = k + m, reference.shard_size(block_size, k)
    nb = mix["object_bytes"] // block_size
    frame = reference.DIGEST_BYTES + s
    # which blocks of which object, then ONE pass of the reference
    wanted, datas, bodies = [], [], []
    for o, rec in enumerate(picked):
        body = traffic.body(seed, rec[7], rec[6], mix["object_bytes"])
        bodies.append(body)
        blocks = list(range(nb)) if o < mix.get("check_whole", nb) \
            else traffic.blocks_to_check(seed, rec[5], nb,
                                         mix.get("check_blocks", nb))
        wanted.append(blocks)
        datas.append(reference.split_blocks(body, block_size, k)[blocks])
    expect = reference.frames(np.concatenate(datas), m) if picked else None
    files_wrong = readback_wrong = at = 0
    files_right_min = n if picked else 0
    for rec, body, blocks in zip(picked, bodies, wanted):
        key = rec[5]
        mine = expect[at:at + len(blocks)]                 # (b, n, 32+S)
        at += len(blocks)
        shard_of = reference.shard_of_drive(traffic.BUCKET, key, n)
        right = 0
        for j, drive in enumerate(drive_paths):
            found = glob.glob(os.path.join(
                glob.escape(os.path.join(drive, traffic.BUCKET, key)),
                "*", "part.1"))
            if not found:
                continue            # a write the quorum ack left behind
            with open(found[0], "rb") as f:
                got = f.read()
            same = len(found) == 1 and len(got) == nb * frame and all(
                got[b * frame:(b + 1) * frame]
                == mine[i, shard_of[j]].tobytes()
                for i, b in enumerate(blocks))
            if same:
                right += 1
            else:
                files_wrong += 1
        files_right_min = min(files_right_min, right)
        status, got = _get(port, key)
        if status != 200 or got != body:
            readback_wrong += 1
    return {
        "answers_wrong": [wrong_answers, 0, "max"],
        "objects_checked": [len(picked), min(1, mix["check_sample"]),
                            "min"],
        "blocks_checked": [sum(len(b) for b in wanted), 1, "min"],
        "drive_files_wrong": [files_wrong, 0, "max"],
        "drive_files_right_min": [files_right_min, write_quorum(k, m),
                                  "min"],
        "readback_wrong": [readback_wrong, 0, "max"],
    }


def get_cell(in_window: list, held: bool, healed_in_window: int,
             decode_by_quarter: list[int]) -> dict:
    """GET cell. Every answer in the window was compared by its client
    with the body that set-up PUT (bytes and ETag), so what is compared
    is the decode verb's output as the endpoint serves it. The degraded
    state held: the drive is still gone, no heal completed, and decode
    launches ran in every quarter of the window."""
    wrong = sum(1 for r in in_window if r[3] == 200 and not r[4])
    return {
        "answers_wrong": [wrong, 0, "max"],
        "answers_checked": [sum(1 for r in in_window if r[4]), 1, "min"],
        "drive_still_gone": [int(held), 1, "min"],
        "heals_completed": [healed_in_window, 0, "max"],
        "decode_launches_least_quarter": [min(decode_by_quarter), 1, "min"],
    }
