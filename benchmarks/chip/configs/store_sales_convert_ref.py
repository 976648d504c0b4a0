"""Plain NumPy reference of ``store_sales_convert``; imports nothing of
the program.  Row ``i`` of the result is what the query must egress for
event ``i``, in serial order."""
from __future__ import annotations

import numpy as np


def reference(cfg: dict, columns: tuple, ev: np.ndarray) -> np.ndarray:
    """``ev`` is the stream's events as an ``(n, 24)`` array; returns the
    ``(n, 6)`` int64 egress."""
    a, b = cfg["price_convert"]["a"], cfg["price_convert"]["b"]
    c = {n: ev[:, i].astype(np.int64) for i, n in enumerate(columns)}
    q = c["ss_quantity"]
    ok = (
        (c["ss_ext_sales_price"] == c["ss_sales_price"] * q)
        & (c["ss_ext_list_price"] == c["ss_list_price"] * q)
        & (c["ss_ext_wholesale_cost"] == c["ss_wholesale_cost"] * q)
        & (c["ss_ext_discount_amt"]
           == c["ss_ext_list_price"] - c["ss_ext_sales_price"])
        & (c["ss_net_paid"] == c["ss_ext_sales_price"] - c["ss_coupon_amt"])
        & (c["ss_net_paid_inc_tax"] == c["ss_net_paid"] + c["ss_ext_tax"])
        & (c["ss_net_profit"]
           == c["ss_net_paid"] - c["ss_ext_wholesale_cost"])
    )
    return np.stack([
        c["ev_id"], c["ss_item_sk"], c["ss_net_paid"] * a + b,
        c["ss_net_profit"] * a + b, ok.astype(np.int64),
        (ev.astype(np.int64) * a + b)
        @ np.arange(1, ev.shape[1] + 1, dtype=np.int64),
    ], axis=1)
