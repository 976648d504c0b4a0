"""Read the comparison's numbers for the program and for its controls and
faults, on the chip, at a cell's own size and load.

    python benchmarks/chip/control.py --workload NAME --seeds 1,2,3 \
        --seconds S [--faults half_batch,answer_altered]

For each seed, one sound run prints the numbers compared and, on the same
rows, those of each control (``faults.CONTROLS``); then one run for each
fault named.  All runs share this process, which never brings a jax
backend up, so each run's device worker opens the chip in turn.  The
benchmark's own runs (``run.py``) plant nothing and run no control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import faults  # noqa: E402
import harness  # noqa: E402


def _line(seed, scenario, checks, extra=None) -> str:
    return json.dumps(dict(
        seed=seed, scenario=scenario,
        correct=all(c["value"] <= c["limit"] for c in checks.values()),
        checks={k: c["value"] for k, c in checks.items()}, **(extra or {})))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    harness.use_checkout_cache()
    planted = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(harness.load_cell(args.workload), seed,
                          args.seconds, False)
        out = run.execute()
        res = out["result"]
        print(_line(seed, "sound", res["checks"], {
            "attempted": res["attempted"], "metrics": res["metrics"]}),
            flush=True)
        for control in faults.CONTROLS:
            print(_line(seed, control, run.compare(control)), flush=True)
        del run
        for fault in planted:
            run = harness.Run(harness.load_cell(args.workload), seed,
                              args.seconds, False, fault=fault)
            res = run.execute()["result"]
            print(_line(seed, fault, res["checks"]), flush=True)
            del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
