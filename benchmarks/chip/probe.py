"""A probe inside the process that holds the chip, started by a fork hook.

The program forks its stage workers; only the device worker brings up a
jax backend, and only that process can read the chip's memory or trace
it.  :func:`install` registers a fork hook in the harness process.  In
every child it starts one daemon thread that sleeps until the child has a
jax backend (only the device worker ever does) and then serves requests
that the harness writes as files into ``run_dir``:

- ``ctl_trace_start``: start the profiler into ``run_dir/trace``, then
  write ``ack_trace_start`` with the probe's clock;
- ``ctl_trace_stop``: stop it, then write ``ack_trace_stop``;
- ``ctl_memory``: write ``memory.json``, the chip's memory counters.

On arming it writes ``probe.json``: when the child was forked, when jax
was imported and when its backend was up, on the host's wall clock.
The program's plan and code are left as they are.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

POLL_IDLE_S = 0.05  # a child without jax checks this often
POLL_ARMED_S = 0.02  # the device worker answers requests this fast


def _write(path: Path, obj) -> None:
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(obj))
    os.replace(tmp, path)


def _backend_up() -> bool:
    if "jax" not in sys.modules:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge.backends_are_initialized())
    except (ImportError, AttributeError):
        return False


def _memory() -> dict:
    import jax

    peaks, in_use = [], []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        in_use.append(int(stats.get("bytes_in_use", 0)))
    return {"peak_bytes": max(peaks), "bytes_in_use": max(in_use),
            "devices": len(peaks)}


def _serve(run_dir: Path, forked_at: float) -> None:
    t_import = None
    while not _backend_up():
        if t_import is None and "jax" in sys.modules:
            t_import = time.time()
        time.sleep(POLL_IDLE_S)
    _write(run_dir / "probe.json", {
        "pid": os.getpid(), "forked_at": forked_at,
        "jax_imported_at": t_import or forked_at, "backend_up_at": time.time(),
    })
    tracing = False
    while True:
        start = run_dir / "ctl_trace_start"
        stop = run_dir / "ctl_trace_stop"
        mem = run_dir / "ctl_memory"
        if not tracing and start.exists():
            import jax

            # the device and the runtime's host events, without a Python
            # function tracer, whose cost would slow the traced window
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(run_dir / "trace"),
                                     profiler_options=opts)
            tracing = True
            _write(run_dir / "ack_trace_start", {"t": time.perf_counter()})
        if tracing and stop.exists():
            import jax

            t = time.perf_counter()
            jax.profiler.stop_trace()
            tracing = False
            stop.unlink()
            start.unlink()
            _write(run_dir / "ack_trace_stop",
                   {"t": t, "written": time.perf_counter()})
        if mem.exists():
            mem.unlink()
            _write(run_dir / "memory.json", _memory())
        time.sleep(POLL_ARMED_S)


#: where the probes of children forked from now on serve; a fork hook
#: cannot be taken back, so one hook serves every run of the process
_target: dict = {"dir": None}


def _child() -> None:
    run_dir = _target["dir"]
    if run_dir is not None:
        threading.Thread(target=_serve, args=(run_dir, time.time()),
                         name="bench-probe", daemon=True).start()


def install(run_dir: Path) -> None:
    """Start a probe thread, serving ``run_dir``, in every child this
    process forks from now on."""
    run_dir.mkdir(parents=True, exist_ok=True)
    if _target["dir"] is None:
        os.register_at_fork(after_in_child=_child)
    _target["dir"] = run_dir


class Probe:
    """The harness's side of the probe: requests and their answers."""

    def __init__(self, run_dir: Path):
        self.dir = run_dir

    def _ask(self, ctl: str, answer: str, timeout: float, service):
        """Write request ``ctl`` and wait for file ``answer``, calling
        ``service`` while waiting."""
        path = self.dir / answer
        if path.exists():
            path.unlink()
        (self.dir / ctl).write_text("1")
        deadline = time.perf_counter() + timeout
        while not path.exists():
            if time.perf_counter() > deadline:
                raise TimeoutError(f"the device worker's probe did not "
                                   f"answer {ctl} in {timeout}s")
            service()
        return json.loads(path.read_text())

    def armed(self) -> dict | None:
        path = self.dir / "probe.json"
        return json.loads(path.read_text()) if path.exists() else None

    def trace_start(self, service) -> float:
        return self._ask("ctl_trace_start", "ack_trace_start", 30.0,
                         service)["t"]

    def trace_stop(self, service) -> dict:
        return self._ask("ctl_trace_stop", "ack_trace_stop", 120.0,
                         service)

    def memory(self, service) -> dict:
        return self._ask("ctl_memory", "memory.json", 30.0, service)
