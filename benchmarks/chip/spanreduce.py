"""The program's own spans and counters, reduced for the per-layer readers.

The device worker records host spans named ``stream.*`` on its host plane
(``repro.core.trace``), on the same clock as the device's ``XLA Ops``.
:func:`spans` counts and totals them over the traced window, and
:func:`idle_by_span` gives each idle gap of the device to the innermost
``stream.*`` span open at the gap's midpoint, with the same window, gap
and midpoint rule as :func:`tracereduce.reduce` gives its ``idle_gaps``.
:func:`counter_deltas` turns two ``Session.stats()`` samples into the
window's deltas of the program's stage, router and supervisor counters.

These take the flat events of :func:`tracereduce.extract` and the raw
``stats()`` dicts, and find nothing (an empty dict, or ``None``) where a
program records no such span or counter.
"""
from __future__ import annotations

from collections import defaultdict

from tracereduce import OPS_LINE, _innermost, _union, is_device_plane

PREFIX = "stream."  # every span the program records
OUTSIDE = "outside_program_span"  # idle time under no program span


def _clipped(events: list, window_s: float | None):
    """The events clipped to the window as ``(plane, line, name, start,
    end)``, and the window's ends; the window is
    :func:`tracereduce.reduce`'s."""
    t0 = min(e[3] for e in events)
    t1 = (t0 + window_s * 1e9 if window_s is not None
          else max(e[3] + e[4] for e in events))
    out = []
    for plane, line, name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e < s or (e == s and dur > 0):
            continue
        out.append((plane, line, name, s, e))
    return out, t0, t1


def spans(events: list, window_s: float | None = None) -> dict:
    """``{name: [count, total_s]}`` of the ``stream.*`` host events in the
    window; an event the window cuts counts once, with the part inside."""
    if not events:
        return {}
    out: dict = {}
    for plane, _line, name, s, e in _clipped(events, window_s)[0]:
        if plane.startswith("/host:") and name.startswith(PREFIX):
            c = out.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
    return out


def idle_by_span(events: list, window_s: float | None = None) -> dict:
    """The device's idle seconds by the innermost ``stream.*`` span open at
    each idle gap's midpoint (:data:`OUTSIDE` where none is), averaged
    over the device planes.  Empty where the trace has no device plane."""
    if not events:
        return {}
    clipped, t0, t1 = _clipped(events, window_s)
    device = defaultdict(lambda: defaultdict(list))
    program = []
    for plane, line, name, s, e in clipped:
        if is_device_plane(plane):
            device[plane][line].append((s, e))
        elif plane.startswith("/host:") and name.startswith(PREFIX):
            program.append((s, e, name))
    gap_time: dict = defaultdict(float)
    for lines in device.values():
        ops = lines.get(OPS_LINE)
        if ops is None:
            ops = [iv for ivs in lines.values() for iv in ivs]
        edges = [t0] + [x for iv in _union(ops) for x in iv] + [t1]
        gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        names = _innermost([(s + e) / 2 for s, e in gaps], program)
        for (s, e), name in zip(gaps, names):
            gap_time[name or OUTSIDE] += (e - s) * 1e-9
    return {k: v / len(device) for k, v in sorted(
        gap_time.items(), key=lambda kv: -kv[1])}


def _sub(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


def counter_deltas(st0: dict, st1: dict, kinds: list) -> dict | None:
    """The counters' deltas between two ``Session.stats()`` samples:
    ``stage_counters`` (per stage, one dict per worker), ``router_counters``
    and ``supervisor_counters``, with each stage's kind in ``kinds``.
    ``None`` where the program keeps no such counters, or where the plan
    moved between the samples (``replans`` or ``restarts``), since the
    workers then are not the same."""
    if "stage_counters" not in st0 or "stage_counters" not in st1:
        return None
    if (st0["replans"], st0["restarts"]) != (st1["replans"], st1["restarts"]):
        return None
    return {
        "kinds": list(kinds),
        "stage_counters": [[_sub(a, b) for a, b in zip(g0, g1)] for g0, g1
                           in zip(st0["stage_counters"],
                                  st1["stage_counters"])],
        "router_counters": [_sub(a, b) for a, b in zip(
            st0["router_counters"], st1["router_counters"])],
        "supervisor_counters": _sub(st0["supervisor_counters"],
                                    st1["supervisor_counters"]),
    }


def workers(counters: dict | None, kind: str) -> list:
    """The per-worker counter deltas of every stage of ``kind``."""
    if not counters:
        return []
    return [w for k, group in zip(counters["kinds"],
                                  counters["stage_counters"])
            if k == kind for w in group]
