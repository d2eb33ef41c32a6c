#!/usr/bin/env python3
"""Run a cell with its control in the timed path's place over many seeds,
in one process (the benchmark's own runs never run the control).

    python chipbench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

Each seed is one whole run of the cell (``run.run_cell``) in which the
control stands in for what the timed path produced, and the cell's own
checks decide ``correct``: for a served model the float8 reference's first
choice at each served position, for the pricing cells the reference with
predicated lanes priced.  The program's own reading of each compared
number is noted beside it.  One JSON line per seed goes to standard
output; the exit code is 1 if any control came out correct.  The limits in
the configuration files are set from these readings (``PERF.md`` gives
them).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import run  # noqa: E402


def control_lines(workload: str, seconds: float, seeds, **kw):
    """One result line per seed, the control in the timed path's place;
    each line carries the run's notes under ``notes``."""
    for seed in seeds:
        notes: dict = {}
        line = run.run_cell(workload, seed, seconds, False, notes=notes,
                            control=True, **kw)
        yield dict(line, seed=seed, notes=notes)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    args = ap.parse_args(argv)
    passed = 0
    try:
        for line in control_lines(args.workload, args.seconds,
                                  [int(s) for s in args.seeds.split(",")]):
            passed += line["correct"]
            print(json.dumps({k: line[k] for k in (
                "seed", "correct", "checks", "notes", "metrics")}),
                flush=True)
    except harness.SetupError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if passed:
        print(f"chipbench: {passed} control run(s) came out correct",
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
