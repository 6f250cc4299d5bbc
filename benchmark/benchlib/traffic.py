"""The one general traffic generator: a mix file's parameters + the
seed -> bodies, keys and each client's request sequence.

A mix (`benchmark/traffic/<mix>.json`) is data only:

  op               "PUT" or "GET" — what every client sends in a loop
  clients          closed-loop client processes
  clients_from     a note, not read: where the client count comes from
  object_bytes     size of every object
  distinct_bodies  PUT: bodies in each client's pool (SHA-256 and MD5
                   computed before the window); GET: distinct bodies
                   among the populated objects
  populate_objects GET only: objects PUT during set-up
  drives_offline   drives taken away after populating (a pulled drive)
  warm_lost_shards GET only: numbers of missing data shards whose decode
                   programs set-up warms (1 = the pulled drive; 2 = a
                   latency hedge in the read plan left a second one out)
  check_sample     PUT only: objects held to the plain reference after
                   the window (drive files + read-back)
  check_whole      PUT only: how many of them have every block compared
  check_blocks     PUT only: blocks compared in each of the others,
                   drawn from the seed, one in each equal stretch of
                   the object (with the part file's length)
  keep_one_in      PUT only: the janitor (benchlib/janitor.py) removes
                   acknowledged objects from the RAM-backed drives
                   during the window, all but one in this many
  room_MiB_s       PUT only: the PUT rate the drive root is checked to
                   have room for before the run starts

Every seed gives the same SIZES and COUNTS; only body bytes, key
names and the order in which keys are requested change with it.
This module imports no JAX and nothing of the program.
"""

from __future__ import annotations

import hashlib
import json
import os
import zlib

import numpy as np

from benchlib import reference

BUCKET = "bench"


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix["op"] not in ("PUT", "GET"):
        raise ValueError(f"traffic mix {name}: unknown op {mix['op']!r}")
    return mix


def body(seed: int, stream: int, index: int, nbytes: int) -> bytes:
    """Body `index` of `stream` (a client number, or -1 for the
    populated set): incompressible bytes from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                 stream + 1, index])
    return rng.bytes(nbytes)


def put_key(seed: int, client: int, n: int) -> str:
    """The n-th fresh key of a client; the prefix mixes the seed in so
    that placement (crc32 of the key) differs from run to run."""
    tag = hashlib.sha256(f"{seed}/{client}/{n}".encode()).hexdigest()[:10]
    return f"put/{tag}-c{client}-{n:05d}"


def offline_drives(seed: int, mix: dict, drives: int) -> list[int]:
    """Which drives of the set are pulled: drawn from the seed."""
    return [(seed + 7 * i) % drives
            for i in range(mix.get("drives_offline", 0))]


def populated_keys(seed: int, mix: dict, drives: int) -> list[str]:
    """Keys of the GET cell's working set. Which shard an object loses
    to a pulled drive follows from its key (upstream's placement:
    CRC-32 of "bucket/key" rotates the shards over the drives), so keys
    drawn blindly would give every seed another share of objects that
    need the decode verb — the seed would change the work. Object j is
    therefore given a key under which the first pulled drive holds its
    shard j mod drives: every seed has the same number of objects for
    each lost shard index (data and parity in the set's own ratio),
    under other names, on another drive, asked for in another order."""
    pulled = offline_drives(seed, mix, drives)
    keys = []
    for j in range(mix["populate_objects"]):
        salt = 0
        while True:
            tag = hashlib.sha256(f"{seed}/{j}/{salt}".encode()).hexdigest()
            key = f"get/{tag[:10]}-{j:03d}"
            if not pulled or reference.shard_of_drive(
                    BUCKET, key, drives)[pulled[0]] == j % drives:
                break
            salt += 1
        keys.append(key)
    return keys


def get_order(seed: int, client: int, count: int):
    """Endless key indices for one GET client: shuffled passes over the
    whole populated set, so every object (hence every survivor mask)
    is asked for equally often whatever the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 77,
                                 client])
    while True:
        yield from rng.permutation(count).tolist()


def blocks_to_check(seed: int, key: str, blocks: int, n: int) -> list[int]:
    """n block indices of an object of `blocks` blocks: one drawn from
    each of n equal stretches (a stream hands the former its blocks in
    groups; a sample spread over the object meets more launches)."""
    if n >= blocks:
        return list(range(blocks))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 55,
                                 zlib.crc32(key.encode())])
    edges = [round(i * blocks / n) for i in range(n + 1)]
    return [int(rng.integers(lo, hi)) for lo, hi in zip(edges, edges[1:])]


def sample(seed: int, items: list, n: int) -> list:
    """n of items, drawn from the seed, order kept."""
    if len(items) <= n:
        return list(items)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 99])
    pick = sorted(rng.choice(len(items), size=n, replace=False).tolist())
    return [items[i] for i in pick]
