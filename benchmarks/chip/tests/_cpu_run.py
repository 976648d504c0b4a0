"""Drive runs of one cell on the CPU at a tiny size, without a chip, and
print one JSON line per scenario: the sound run, its controls, and one
run for each fault.  Used by the tests; the parent never brings up a jax
backend, so each run forks a fresh device worker.

    JAX_PLATFORMS=cpu python _cpu_run.py WORKLOAD SCENARIO [SCENARIO ...]

A scenario is ``sound`` (with every control checked on its output) or
the name of a fault in ``faults.FAULTS``.
"""
import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import faults  # noqa: E402
import harness  # noqa: E402

SEED = 2 ** 31 + 12345  # a seed need not fit in 32 signed bits


def run(workload: str, fault):
    cell = harness.load_cell(workload)
    cell["cfg"]["pool_rows"] = 2048
    cell["mix"]["warmup_s"] = 0.3
    r = harness.Run(cell, SEED, 0.5, False, require_chip=False,
                    fault=fault, layout={"host_workers": 1},
                    check_cores=False)
    out = r.execute()
    return r, out


def main() -> None:
    workload, scenarios = sys.argv[1], sys.argv[2:]
    for sc in scenarios:
        r, out = run(workload, None if sc == "sound" else sc)
        res = out["result"]
        line = {"scenario": sc, "correct": res["correct"],
                "checks": res["checks"], "attempted": res["attempted"],
                "metrics": sorted(res["metrics"]), "device": res["device"]}
        if sc == "sound":
            line["controls"] = {
                c: all(v["value"] <= v["limit"]
                       for v in r.compare(c).values())
                for c in faults.CONTROLS}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
