"""Read the check's numbers of one cell over many seeds in one process.

    python3 benchmark/sweep.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--control program|reference] \
        [--seconds 3] [--rehearse]

Each seed is a whole run of the cell (set-up, window, comparison) as
``run.py`` makes it; programs already compiled in this process or in the
compile cache are reused, so a dozen seeds cost one compile.  With
``--control-seeds`` the same cell runs with a lower-precision control on
those seeds (``control`` in the configuration file): ``program`` switches
on the program's own lower-precision path (``control.make_app``),
``reference`` puts the reference computed in ``control.reference_dtype``
in the program's place.  The default is the program's path where the
configuration has one.  One JSON line per run: the seed, the control, and
every number compared with its limit.  A run that raises gives no reading:
its line has ``correct`` null and the error.  These readings set the
limits in ``configs/<config>.json``.
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
from harness.measure import run_cell  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s.strip()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", choices=("program", "reference"))
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    kind = args.control or (
        "program" if "make_app" in cell.config["control"] else "reference")
    runs = [(s, None) for s in _seeds(args.seeds)]
    runs += [(s, kind) for s in _seeds(args.control_seeds)]
    for seed, control in runs:
        t0 = time.perf_counter()
        try:
            r = run_cell(cell, seed, args.seconds, False, t0,
                         control=control, require_chip=not args.rehearse)
            line = {"seed": seed, "control": control,
                    "correct": r["correct"], "checks": r["checks"],
                    "info": r["info"], "metrics": r["metrics"]}
        except Exception as e:  # no reading: not a pass, not a fail
            line = {"seed": seed, "control": control, "correct": None,
                    "error": f"{type(e).__name__}: {e}"}
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
