"""Everything BENCHMARK.json names is there to be found, and in the
characters the contract allows."""

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from benchlib import harness, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_lengths():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_every_cell_finds_its_files_and_reports_enough():
    b = bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        found = harness.load_cell(w["name"])
        assert found["config"]["drives"] == 16
        assert found["mix"]["op"] in ("PUT", "GET")
        mine = {m["name"] for m in found["end_to_end"]}
        assert "setup_s" in mine and len(mine) >= 2
        assert found["per_layer"]
        op = found["mix"]["op"].lower()
        for m in found["per_layer"]:
            assert m["moves"] in e2e
            assert callable(harness.load_reader(m["name"]))
            # a metric moves an end-to-end metric its cells report
            assert m["moves"] in mine
        assert mine <= {f"{op}_MiB_s", f"{op}_p50_ms", f"{op}_p90_ms",
                        f"{op}_p99_ms", "setup_s"}
    # every config is used, every config file restates its reduced keys
    used = {w["config"] for w in b["workloads"]}
    for c in b["configs"]:
        assert c["name"] in used
        with open(os.path.join(REPO, c["file"])) as f:
            assert set(c["reduced"]) == set(json.load(f)["reduced"])


def test_get_keys_give_every_seed_the_same_work():
    from collections import Counter
    from benchlib import reference
    mix = traffic.load_mix(BENCH, "get64m-1down-c8")
    for seed in (3, 2**31 + 12345):
        off = traffic.offline_drives(seed, mix, 16)
        keys = traffic.populated_keys(seed, mix, 16)
        lost = Counter(reference.shard_of_drive(traffic.BUCKET, k, 16)[off[0]]
                       for k in keys)
        assert lost == Counter({i: 2 for i in range(16)})
