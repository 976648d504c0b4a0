"""Run one cell of the on-chip benchmark and print its result line.

    python benchmarks/chip/run.py --workload NAME --seed N --seconds S \
        --trace 0|1

The cell is looked up by name in ``BENCHMARK.json``.  The last line of
standard output is the result as one JSON object; an earlier line,
starting ``diag``, records the layout, the set-up phases and egress per
second of the window.  The numbers compared with the reference are the
last lines of standard error.  A run that finds no TPU, or fewer chips
than the cell asks for, exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.use_checkout_cache()
    cell = harness.load_cell(args.workload)
    try:
        out = harness.Run(cell, args.seed, args.seconds,
                          bool(args.trace)).execute()
    except harness.NoChip as exc:
        print(f"no chip: {exc}", file=sys.stderr, flush=True)
        return 3
    result = out["result"]
    print("diag " + json.dumps(out["diag"]), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
