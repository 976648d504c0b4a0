"""The ``store_sales_item`` query as the program runs it.

``project`` (stateless) keeps 12 columns of the event, ``price_convert``
(a device stage on ``kernel``) maps them from cents to mills, and
``item_revenue`` (partitioned by ``ss_item_sk``) emits, for every event,
``(ev_id, ss_item_sk, count, quantity, revenue, fold)``: the item's
running count of sales, units sold and ``ss_ext_sales_price`` in mills,
and ``fold``, the sum of the 12 columns the device stage returned, in
mills, column ``i`` weighted ``i + 1``, so that every column of every
row reaches the comparison exactly.
"""
from __future__ import annotations

import functools
import operator

from repro.columnar import Schema, device_op
from repro.core import OpSpec


def project(e, get):
    return [get(e)]


def item_key(t, a, b):
    return (t[1] - b) // a


def zero_state():
    return (0, 0, 0)


def fold(t):
    return sum(map(operator.mul, range(1, len(t) + 1), t))


def item_revenue(state, key, t, a, b):
    n, units, revenue = state
    state = (n + 1, units + (t[2] - b) // a, revenue + t[4])
    return state, [((t[0] - b) // a, key) + state + (fold(t),)]


def build(cfg: dict, columns: tuple, kernel: str) -> list:
    """The operator chain, with the device stage on ``kernel``."""
    conv = cfg["price_convert"]
    a, b = conv["a"], conv["b"]
    idx = [columns.index(c) for c in cfg["projection"]]
    return [
        OpSpec("project", "stateless",
               functools.partial(project, get=operator.itemgetter(*idx)),
               cost_us=2.0),
        device_op("price_convert", kernel, Schema.of(*["i4"] * len(idx)),
                  params={"a": a, "b": b}, cost_us=1.0),
        OpSpec("item_revenue", "partitioned",
               functools.partial(item_revenue, a=a, b=b),
               key_fn=functools.partial(item_key, a=a, b=b),
               num_partitions=cfg["partitions"], init_state=zero_state,
               cost_us=4.0),
    ]
