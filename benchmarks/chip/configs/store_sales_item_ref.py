"""Plain NumPy reference of ``store_sales_item``; imports nothing of the
program.  Row ``i`` of the result is what the query must egress for event
``i``, in serial order."""
from __future__ import annotations

import numpy as np


def running_sums(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """For each row, the sum of ``values`` over the rows up to and
    including it that share its key."""
    order = np.argsort(keys, kind="stable")
    v = values[order].astype(np.int64)
    total = np.cumsum(v)
    k = keys[order]
    first = np.ones(len(k), bool)
    first[1:] = k[1:] != k[:-1]
    start = np.flatnonzero(first)
    before = np.repeat(total[start] - v[start], np.diff(np.append(start, len(k))))
    out = np.empty(len(k), np.int64)
    out[order] = total - before
    return out


def fold(proj: np.ndarray, a: int, b: int) -> np.ndarray:
    """Per row, the sum of the projected columns in mills, column ``i``
    weighted ``i + 1``."""
    mills = proj.astype(np.int64) * a + b
    return mills @ np.arange(1, proj.shape[1] + 1, dtype=np.int64)


def reference(cfg: dict, columns: tuple, ev: np.ndarray) -> np.ndarray:
    """``ev`` is the stream's events as an ``(n, 24)`` array; returns the
    ``(n, 6)`` int64 egress."""
    a, b = cfg["price_convert"]["a"], cfg["price_convert"]["b"]
    col = {c: ev[:, columns.index(c)].astype(np.int64) for c in
           ("ev_id", "ss_item_sk", "ss_quantity", "ss_ext_sales_price")}
    item = col["ss_item_sk"]
    ones = np.ones(len(item), np.int64)
    return np.stack([
        col["ev_id"], item, running_sums(item, ones),
        running_sums(item, col["ss_quantity"]),
        running_sums(item, col["ss_ext_sales_price"] * a + b),
        fold(ev[:, [columns.index(c) for c in cfg["projection"]]], a, b),
    ], axis=1)
