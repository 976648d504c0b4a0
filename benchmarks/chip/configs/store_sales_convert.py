"""The ``store_sales_convert`` query as the program runs it.

``project`` (stateless) passes the 24-column event on, ``price_convert``
(a device stage on ``kernel``) maps every column from cents to mills, and
``check`` (stateless) takes the columns back to cents, tests the money
identities that TPC-DS derives ``store_sales`` by, and emits
``(ev_id, ss_item_sk, ss_net_paid, ss_net_profit, ok, fold)`` with the
two amounts in mills, ``ok`` 1 where every identity holds, and ``fold``
the sum of the 24 columns the device stage returned, in mills, column
``i`` weighted ``i + 1``, so that every column of every row reaches the
comparison exactly.
"""
from __future__ import annotations

import functools
import operator

from repro.columnar import Schema, device_op
from repro.core import OpSpec


def project(e):
    return [e]


def fold(t):
    return sum(map(operator.mul, range(1, len(t) + 1), t))


def check(t, a, b, at):
    c = [(x - b) // a for x in t]
    q = c[at["ss_quantity"]]
    ext_sales = c[at["ss_ext_sales_price"]]
    ext_list = c[at["ss_ext_list_price"]]
    net_paid = c[at["ss_net_paid"]]
    ok = (
        ext_sales == c[at["ss_sales_price"]] * q
        and ext_list == c[at["ss_list_price"]] * q
        and c[at["ss_ext_wholesale_cost"]] == c[at["ss_wholesale_cost"]] * q
        and c[at["ss_ext_discount_amt"]] == ext_list - ext_sales
        and net_paid == ext_sales - c[at["ss_coupon_amt"]]
        and c[at["ss_net_paid_inc_tax"]] == net_paid + c[at["ss_ext_tax"]]
        and c[at["ss_net_profit"]]
        == net_paid - c[at["ss_ext_wholesale_cost"]]
    )
    return [(c[at["ev_id"]], c[at["ss_item_sk"]], t[at["ss_net_paid"]],
             t[at["ss_net_profit"]], int(ok), fold(t))]


def build(cfg: dict, columns: tuple, kernel: str) -> list:
    """The operator chain, with the device stage on ``kernel``."""
    conv = cfg["price_convert"]
    a, b = conv["a"], conv["b"]
    at = {c: i for i, c in enumerate(cfg["projection"])}
    if list(cfg["projection"]) != list(columns):
        raise ValueError("store_sales_convert converts the whole event")
    return [
        OpSpec("project", "stateless", project, cost_us=1.0),
        device_op("price_convert", kernel, Schema.of(*["i4"] * len(at)),
                  params={"a": a, "b": b}, cost_us=1.0),
        OpSpec("check", "stateless",
               functools.partial(check, a=a, b=b, at=at), cost_us=3.0),
    ]
