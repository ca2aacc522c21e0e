"""Run one benchmark cell and print its result as the last line of stdout.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
BENCHMARK.json and the files named after them (harness/spec.py).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the trace breakdown.  The numbers
compared with the plain reference are printed last on stderr, each beside
its limit, and under ``checks`` at the end of the result line.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits 2.  ``--rehearse`` lets it run anywhere (the CPU, at the
configured sizes) and exit 3 with its would-be result on stderr only.
The check's readings over many seeds, and its control's, come from
``sweep.py``.
"""

from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402
from harness.measure import NoDevice, run_cell  # noqa: E402


def print_checks(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (must be {c['must_be']} "
              f"{c['limit']!r})", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROC0, require_chip=not args.rehearse)
    except NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    if args.rehearse and result["device"]["platform"] != "tpu":
        print("rehearsal on " + result["device"]["platform"]
              + ", not a device result: " + json.dumps(result),
              file=sys.stderr)
        print_checks(result)
        return 3
    print(json.dumps(result), flush=True)
    print_checks(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
