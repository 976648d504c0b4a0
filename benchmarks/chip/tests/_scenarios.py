"""Run ``_cpu_run.py`` once per test module and hand its lines out."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def run_scenarios(workload: str, scenarios: list) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "_cpu_run.py"), workload, *scenarios],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=str(HERE.parents[2]),
    )
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    if proc.returncode != 0 or len(lines) != len(scenarios):
        raise RuntimeError(f"_cpu_run.py failed (rc {proc.returncode}):\n"
                           f"{proc.stderr[-4000:]}")
    return {line["scenario"]: line for line in lines}
