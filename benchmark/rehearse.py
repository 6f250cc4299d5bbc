#!/usr/bin/env python3
"""benchmark/rehearse.py — the harness end to end at a tiny size on
XLA-CPU, for machines without a chip. Not a measurement.

    python3 benchmark/rehearse.py --workload <cell> [--seed N]
        [--seconds S] [--trace 0|1] [--fault NAME]

The device route is forced onto XLA-CPU the way `chip_smoke.py
--dry-run-cpu` forces it (the routing predicate, never the probe);
blocks are 64 KiB, objects 16 blocks. It prints `DRY RUN`, reports what
was compared and which metrics found something to read — names only,
never a CPU number under a device metric's name, never the contract's
result line — and exits 3 when the rehearsal's own checks pass
(1 when they do not): it can never pass as a run.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
EXIT_REHEARSED = 3
TINY_BLOCK = 1 << 16


def rehearse(workload: str, seed: int = 1, seconds: float = 3.0,
             trace: bool = False, fault: str = "") -> dict:
    """-> {"checks_pass", "compared", "attempted", "failed",
    "metrics_found"}; raises what the harness raises."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchlib import check, harness
    from minio_tpu.object import codec as codec_mod
    from minio_tpu.utils import device
    if device.probe().is_tpu:
        raise harness.NoResult("a chip is attached: run benchmark/run.py")
    codec_mod._device_is_tpu = lambda: True
    codec_mod.DEVICE_MIN_BYTES = 0
    tiny = {"node": {"block_size": TINY_BLOCK},
            "mix": {"object_bytes": 16 * TINY_BLOCK, "populate_objects": 8,
                    "room_MiB_s": 64, "keep_one_in": 2, "check_whole": 8}}
    harness.guard(600)
    result = harness.run_cell(workload, seed, seconds, trace,
                              T_PROCESS_START, rehearsal=tiny, fault=fault)
    numbers = {k: (c["value"], c["limit"], c["is"])
               for k, c in result["compared"].items()}
    return {"checks_pass": check.verdict(numbers),
            "compared": result["compared"],
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics_found": sorted(result["metrics"]),
            "beside": result["beside"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    print("DRY RUN platform=cpu — a rehearsal of the harness, "
          "not a chip result", flush=True)
    found = rehearse(args.workload, args.seed, args.seconds,
                     bool(args.trace), args.fault)
    found["beside"].pop("latency_ms", None)     # a CPU time: not printed
    print("DRY RUN findings " + json.dumps(found), flush=True)
    print("DRY RUN platform=cpu — exit 3 means the rehearsal's checks "
          "passed; it is never a pass", flush=True)
    return EXIT_REHEARSED if found["checks_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
