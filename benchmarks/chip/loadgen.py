"""Traffic: a mix's parameters in, a schedule of arrivals out.

A mix is a JSON file under ``traffic/``.  ``"mode": "closed"`` floods:
the feeder pushes as fast as backpressure admits.  ``"mode": "poisson"``
is an open loop at ``rate_eps`` events/s whose arrival times come from
the seed; latency counts from each event's scheduled arrival, so a stall
is charged to every event it delays (the coordinated-omission-free
arithmetic of ``repro/serve/loadgen.py``, copied here so that the
yardstick does not move with the program).
"""
from __future__ import annotations

import numpy as np


def arrivals(rate_eps: float, seed: int, n: int) -> np.ndarray:
    """``n`` Poisson arrival offsets in seconds from the start.  Every seed
    draws the same multiset of gaps, in its own order, so seeds differ in
    order and not in the load they offer."""
    gaps = -np.log1p(-((np.arange(n) + 0.5) / n))
    np.random.default_rng(seed).shuffle(gaps)
    return np.cumsum(gaps) / rate_eps
