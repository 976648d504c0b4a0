"""From a profiler trace of the device worker to the device's numbers.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
flat events ``(plane, line, name, start_ns, dur_ns)``; :func:`reduce`
turns those into what the per-layer readers use.  A device plane is one
whose name starts with ``/device:`` and is not the host's; on it the
``XLA Ops`` line holds one event per operation that ran (``XLA Modules``
one per program launched).  Host planes (``/host:``) hold the device
worker's own threads, whose events say what the host was doing while the
device sat idle; among them the program's own ``stream.*`` spans, which
:mod:`spanreduce` reduces over the same window.
"""
from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    return max(found, key=os.path.getmtime)


def extract(path: str) -> list:
    """Every event of the trace as ``[plane, line, name, start_ns,
    dur_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append([plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)])
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _union(intervals):
    """Merge ``(start, end)`` intervals; returns the sorted, disjoint
    union."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _innermost(points, spans):
    """For each point, the name of the shortest span that contains it
    (``None`` where none does).  ``spans`` are ``(start, end, name)``."""
    spans = sorted(spans)
    order = sorted(range(len(points)), key=points.__getitem__)
    heap, out, j = [], [None] * len(points), 0
    for i in order:
        p = points[i]
        while j < len(spans) and spans[j][0] <= p:
            s, e, name = spans[j]
            heapq.heappush(heap, (e - s, e, name))
            j += 1
        # spans that ended before p ended before every later point too
        while heap and heap[0][1] < p:
            heapq.heappop(heap)
        out[i] = heap[0][2] if heap else None
    return out


def reduce(events: list, window_s: float | None = None,
           top: int = 10) -> dict:
    """Device busy time, per-op totals, program launches and the idle gaps
    by what the host was doing, over the traced window.

    The window opens at the trace's first event and lasts ``window_s``
    (the span between the profiler's start and the request to stop it,
    so what the stop itself records is left out), or to the last event
    where ``window_s`` is not given; events are clipped to it.  Busy is
    the union of the device's operation intervals (the ``XLA Ops`` line,
    or every device line where a plane has none), averaged over the
    device planes.  Each idle gap of a device is given to the innermost
    host event open at its midpoint (``idle_gaps``), and to the innermost
    program span open there (``idle_by_span``); ``spans`` counts and
    totals the program's spans."""
    import spanreduce  # it builds on this module's helpers

    if not events:
        raise ValueError("the trace holds no events")
    t0 = min(e[3] for e in events)
    t1 = (t0 + window_s * 1e9 if window_s is not None
          else max(e[3] + e[4] for e in events))
    device = defaultdict(lambda: defaultdict(list))
    host = []
    for plane, line, name, start, dur in events:
        start, end = max(start, t0), min(start + dur, t1)
        if end < start or (end == start and dur > 0):
            continue
        dur = end - start
        if is_device_plane(plane):
            device[plane][line].append((start, start + dur, name))
        elif plane.startswith("/host:"):
            host.append((start, start + dur, name))
    if not device:
        raise ValueError("the trace holds no device plane")
    busy_total, op_time, launches = 0.0, defaultdict(float), 0
    gap_time = defaultdict(float)
    for plane, lines in device.items():
        ops = lines.get(OPS_LINE)
        if ops is None:
            ops = [ev for evs in lines.values() for ev in evs]
        launches += len(lines.get(MODULES_LINE, ()))
        for s, e, name in ops:
            op_time[name] += (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _ in ops])
        busy_total += sum(e - s for s, e in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        names = _innermost([(s + e) / 2 for s, e in gaps], host)
        for (s, e), name in zip(gaps, names):
            gap_time[name or "no_host_event"] += (e - s) * 1e-9
    n_dev = len(device)
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "device_planes": n_dev,
        "op_s": sum(op_time.values()) / n_dev,
        "launches": launches,
        "device_ops": [[k, v] for k, v in ranked[:top]],
        "idle_gaps": [[k, v / n_dev] for k, v in sorted(
            gap_time.items(), key=lambda kv: -kv[1])[:top]],
        "spans": spanreduce.spans(events, window_s),
        "idle_by_span": spanreduce.idle_by_span(events, window_s),
    }
