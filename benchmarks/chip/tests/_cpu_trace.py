"""Trace a jax device worker on the CPU, as the probe traces it on the
chip, and print the program's spans in the trace as one JSON line:
``{"spans": {name: [count, total_s]}, "dispatches": n}``.

    JAX_PLATFORMS=cpu python _cpu_trace.py
    JAX_PLATFORMS=cpu python _cpu_trace.py WORKLOAD [WORKLOAD ...]

With workloads named, make one traced run of each cell through the
harness at a tiny size instead, and print for each one JSON line: its
per-layer metrics as ``Run._per_layer`` read them, and what the readers'
context held of the cell (``cfg`` and ``device_op``).  The CPU has no
device plane and no published peaks, so XLA's CPU threads stand in for
the plane and a TPU v5e's peaks for the CPU's.

The parent never brings up a jax backend; the device worker does, and the
probe's fork hook runs the profiler inside it.
"""
import json
import sys
import tempfile
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import probe  # noqa: E402
import spanreduce  # noqa: E402
import tracereduce  # noqa: E402


def pair(v):
    return [(v, 2 * v)]


def drive(session, n0: int, n1: int) -> None:
    session.push(range(n0, n1))
    got = list(session.results(max_items=n1 - n0, timeout=60))
    assert got == [(3 * v + 1, 6 * v + 1) for v in range(n0, n1)], got[:3]


def traced_run(workload: str) -> dict:
    """One traced run of ``workload`` through the harness on the CPU."""
    import harness
    import peaks

    seen = {}
    real_extract, real_reader = tracereduce.extract, harness.reader
    real_peaks = peaks.peaks

    def extract(path):
        return [["/device:TPU:0", "XLA Ops", *e[2:]]
                if e[1].startswith("tf_XLA") else e
                for e in real_extract(path)]

    def reader(name):
        read = real_reader(name)

        def spy(ctx):
            op = ctx["device_op"]
            kernel, params = op.device_kernel
            seen.update(cfg=ctx["cfg"]["name"], device_op={
                "name": op.name, "kind": op.kind,
                "columns": len(op.schema.fields), "kernel": kernel,
                "params": dict(params)})
            return read(ctx)

        return spy

    tracereduce.extract, harness.reader = extract, reader
    peaks.peaks = lambda kind: real_peaks("TPU v5 lite")
    try:
        cell = harness.load_cell(workload)
        cell["cfg"]["pool_rows"] = 2048
        cell["mix"]["warmup_s"] = 0.3
        out = harness.Run(cell, 2 ** 31 + 777, 1.0, True,
                          require_chip=False, layout={"host_workers": 1},
                          check_cores=False).execute()
    finally:
        tracereduce.extract, harness.reader = real_extract, real_reader
        peaks.peaks = real_peaks
    res = out["result"]
    return {"workload": workload, "correct": res["correct"],
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "breakdown": sorted(res["breakdown"]),
            "stage_counters": out["diag"]["stage_counters"] is not None,
            "idle_by_span": out["diag"]["trace_summary"]["idle_by_span"],
            **seen}


def main() -> None:
    if sys.argv[1:]:
        for workload in sys.argv[1:]:
            print(json.dumps(traced_run(workload)), flush=True)
        return
    from repro.columnar import Schema, device_op
    from repro.core import Engine, EngineConfig, OpSpec, ProcessOptions

    run_dir = Path(tempfile.mkdtemp(prefix="cpu_trace_"))
    probe.install(run_dir)
    ops = [OpSpec("pair", "stateless", pair),
           device_op("dev", "affine", Schema.of("i4", "i4"),
                     params={"a": 3, "b": 1}, backend="jax")]
    eng = Engine(EngineConfig(
        backend="process", num_workers=1, batch_size=8, collect_outputs=True,
        process=ProcessOptions(columnar=True, device_batch=64,
                               device_backend="jax")))
    session = eng.open(eng.plan(ops))
    p = probe.Probe(run_dir)
    deadline = time.monotonic() + 120
    while not (session.stats()["devices"] and p.armed()):
        assert time.monotonic() < deadline, "the device worker never came up"
        session.service()
        time.sleep(0.01)
    p.trace_start(session.service)
    drive(session, 0, 600)
    time.sleep(0.05)  # an input wait, closed by the next record
    drive(session, 600, 1000)
    info = p.trace_stop(session.service)
    session.close()
    events = tracereduce.extract(
        tracereduce.newest_xplane(str(run_dir / "trace")))
    print(json.dumps({
        "spans": spanreduce.spans(events),
        "dispatches": sum(d.get("dispatches", 0)
                          for d in session.stats()["devices"]),
        "stopped": info["t"] > 0,
    }), flush=True)


if __name__ == "__main__":
    main()
