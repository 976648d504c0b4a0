"""Sweep an open-loop cell's offered rate on the chip, to find its knee.

    python benchmarks/chip/sweep.py --workload NAME --rates 22000,24000 \
        --repeats 2 --seconds 10 --seed N

For each rate, ``--repeats`` runs of the cell with its mix's ``rate_eps``
replaced and its warm-up cut to ``WARMUP_S``, so that lag which a longer
warm-up would build before the window shows in it; each on its own seed (``--seed`` plus the run's index), all in
this process, which never brings a jax backend up.  One JSON line per
run: the median egress per second of the window, the latency
percentiles, the median latency by second of the window, and whether
that lag grows: the median of the last third of the seconds over the
least second, ``grows`` where it exceeds ``GROWTH``.  A system that keeps
up returns to its floor after a spike; one that does not ends the window
above it, also where its lag was high from the start.  The knee is the
highest rate at which no run's lag grows.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402

GROWTH = 1.5
WARMUP_S = 0.5


def lag_growth(p50_by_s: list) -> float:
    """Median p50 of the window's last third of seconds over the least
    second's."""
    k = max(len(p50_by_s) // 3, 1)
    return statistics.median(p50_by_s[-k:]) / min(p50_by_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    harness.use_checkout_cache()
    k = 0
    for rate in (float(r) for r in args.rates.split(",")):
        for _ in range(args.repeats):
            cell = harness.load_cell(args.workload)
            cell["mix"].update(rate_eps=rate, warmup_s=WARMUP_S)
            seed = args.seed + k
            k += 1
            out = harness.Run(cell, seed, args.seconds, False).execute()
            diag, res = out["diag"], out["result"]
            p50_by_s = [p[0] for p in diag["latency_ms_by_s"]]
            growth = lag_growth(p50_by_s)
            print(json.dumps({
                "rate_eps": rate, "seed": seed, "seconds": args.seconds,
                "correct": res["correct"],
                "egress_eps": statistics.median(diag["egress_per_s"]),
                "latency_ms": diag["latency_ms"],
                "p50_ms_by_s": p50_by_s, "growth": growth,
                "lag": "grows" if growth > GROWTH else "flat",
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
