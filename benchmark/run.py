#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Boots the node in this process (the only one that touches JAX), drives
it from client processes over signed S3 HTTP for `--seconds`, checks
what the timed requests produced against the plain reference, and
prints one JSON object as the last line of stdout: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `beside` and, last, `compared` — every number that
was compared, with its limit. Without a TPU, or outside a checkout of
the program, it exits 2 and prints no result.
"""

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_RESULT = 2
DEADLINE_S = 1150      # the driver allows a compiling run 1200 s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="",
                    help="a control: plant a named fault under the timed "
                         "path (benchlib/faults.py); the run must then "
                         "report correct=false. Never set by the driver.")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON",
                    help="override a parameter of the cell's traffic mix "
                         "(clients=16, keep_one_in=0): the sweeps and "
                         "pairs of PERF.md. Never set by the driver.")
    args = ap.parse_args(argv)
    mix_set = {k: json.loads(v) for k, v in
               (kv.split("=", 1) for kv in args.set)}

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        from benchlib import harness
    except ImportError as e:
        print(f"benchmark: cannot import its own library: {e}",
              file=sys.stderr)
        return EXIT_NO_RESULT

    watchdog = harness.guard(DEADLINE_S)

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS_START,
                                  fault=args.fault, mix_set=mix_set)
    except (harness.NoResult, ImportError) as e:
        print(f"benchmark: no result — {e}", file=sys.stderr)
        return EXIT_NO_RESULT
    watchdog.cancel()
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']}  limit {c['is']} "
              f"{c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # the node is shut down, the drive tree removed and every child
    # waited for; leave without unwinding the runtime's daemon threads
    # (libtpu aborts the process when the interpreter tears them down:
    # "FATAL: exception not rethrown", exit 134 after a complete run)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
