#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's own
size: a run of the harness with the plain reference put in the program's
place, computed in bfloat16 (the precision below the configuration's
float32). Its compared numbers are the control's readings; each has to
exceed its limit on some number.

  python chipbench/control.py --workload <cell> --seconds 6 --seeds 11 12 13

Each seed is a short window at the cell's load (one caller, closed loop)
that ends after as many matchings as a run compares. The benchmark's own
runs never run this. It needs what a run needs: the cell's chips attached.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for path in (ROOT, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import reference, run  # noqa: E402


def reference_route(dtype):
    """A stand-in for the program's entry that returns the reference's
    matching, computed in ``dtype``, as a ``MatchResult``."""
    from repro.core.api import MatchResult

    def solve(problem, options, warm_start=None):
        warm = None if warm_start is None else (
            np.asarray(warm_start.mate_row), np.asarray(warm_start.mate_col))
        m = reference.solve(np.asarray(problem.row), np.asarray(problem.col),
                            np.asarray(problem.val), problem.n, dtype=dtype,
                            warm=warm)
        n = problem.n
        return MatchResult(mate_row=m.mate_row.astype(np.int32),
                           mate_col=m.mate_col.astype(np.int32),
                           weight=np.float32(m.weight),
                           awac_iters=np.int32(m.awac_rounds),
                           perfect=bool((m.mate_row[:n] < n).all()))
    return solve


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    run.route_solve = reference_route(ml_dtypes.bfloat16)
    for seed in args.seeds:
        line, checks = run.run(argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds, trace=0))
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "attempted": line["attempted"], "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
