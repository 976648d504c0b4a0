"""TPC-DS ``store_sales`` rows, generated in NumPy from a seed.

Columns follow the TPC-DS specification's ``store_sales`` table (23
columns) in its order, with a 24th, ``ev_id``, the event's index in the
run.  Money columns are ``decimal(7,2)`` values held as int32 cents and
derived the way the specification derives them: a wholesale cost, a list
price by markup, a sales price by discount, ``ext_*`` as price x
quantity, then tax, coupon, net paid and net profit.

``ss_item_sk`` is drawn Zipf over the item domain.  The map from
popularity rank to item key is fixed by the configuration, not by the
seed, so every seed puts the same items on the same partitions and only
the order and the values of events change from seed to seed.
"""
from __future__ import annotations

import numpy as np

COLUMNS = (
    "ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk", "ss_customer_sk",
    "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk",
    "ss_promo_sk", "ss_ticket_number", "ss_quantity", "ss_wholesale_cost",
    "ss_list_price", "ss_sales_price", "ss_ext_discount_amt",
    "ss_ext_sales_price", "ss_ext_wholesale_cost", "ss_ext_list_price",
    "ss_ext_tax", "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
    "ss_net_profit", "ev_id",
)
COL = {name: i for i, name in enumerate(COLUMNS)}
EV_ID = COL["ev_id"]


def zipf_ranks(rng: np.random.Generator, n: int, domain: int,
               s: float) -> np.ndarray:
    """``n`` popularity ranks in ``[0, domain)``, rank ``r`` drawn with
    probability proportional to ``(r + 1) ** -s``."""
    weights = np.arange(1, domain + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(n), side="right")
    return np.minimum(ranks, domain - 1)


def store_sales(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` rows of ``store_sales`` as an ``(n, 24)`` int32 array.

    ``cfg`` is a configuration file's content: ``domains`` gives each
    key's inclusive ``[lo, hi]``, ``item_zipf`` the skew of
    ``ss_item_sk`` and ``item_rank_seed`` the fixed rank-to-key map,
    ``money`` the ranges of the drawn percentages.  ``ev_id`` is left 0:
    the feeder stamps it."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n, len(COLUMNS)), np.int64)
    dom = cfg["domains"]
    for name, (lo, hi) in dom.items():
        if name == "ss_item_sk":
            continue
        out[:, COL[name]] = rng.integers(lo, hi + 1, size=n)
    lo, hi = dom["ss_item_sk"]
    items = hi - lo + 1
    rank_to_key = lo + np.random.default_rng(
        cfg["item_rank_seed"]).permutation(items)
    out[:, COL["ss_item_sk"]] = rank_to_key[
        zipf_ranks(rng, n, items, cfg["item_zipf"])]

    m = cfg["money"]
    q = rng.integers(m["quantity"][0], m["quantity"][1] + 1, size=n)
    wholesale = rng.integers(m["wholesale_cents"][0],
                             m["wholesale_cents"][1] + 1, size=n)
    markup = rng.integers(m["markup_pct"][0], m["markup_pct"][1] + 1, size=n)
    discount = rng.integers(m["discount_pct"][0], m["discount_pct"][1] + 1,
                            size=n)
    tax = rng.integers(m["tax_pct"][0], m["tax_pct"][1] + 1, size=n)
    coupon = rng.integers(m["coupon_pct"][0], m["coupon_pct"][1] + 1, size=n)
    coupon[rng.random(n) >= m["coupon_share"]] = 0
    list_price = wholesale * (100 + markup) // 100
    sales_price = list_price * (100 - discount) // 100
    ext_sales = sales_price * q
    ext_list = list_price * q
    ext_wholesale = wholesale * q
    ext_tax = ext_sales * tax // 100
    coupon_amt = ext_sales * coupon // 100
    net_paid = ext_sales - coupon_amt
    for name, col in (
        ("ss_quantity", q), ("ss_wholesale_cost", wholesale),
        ("ss_list_price", list_price), ("ss_sales_price", sales_price),
        ("ss_ext_discount_amt", ext_list - ext_sales),
        ("ss_ext_sales_price", ext_sales),
        ("ss_ext_wholesale_cost", ext_wholesale),
        ("ss_ext_list_price", ext_list), ("ss_ext_tax", ext_tax),
        ("ss_coupon_amt", coupon_amt), ("ss_net_paid", net_paid),
        ("ss_net_paid_inc_tax", net_paid + ext_tax),
        ("ss_net_profit", net_paid - ext_wholesale),
    ):
        out[:, COL[name]] = col
    if np.abs(out).max() >= 2 ** 31:
        raise ValueError("a store_sales value does not fit int32")
    return out.astype(np.int32)


def events(pool: np.ndarray, start: int, stop: int) -> np.ndarray:
    """Events ``start .. stop - 1`` of the stream: the pool's rows in turn,
    cycling, each stamped with its ``ev_id``."""
    idx = np.arange(start, stop, dtype=np.int64)
    rows = pool[idx % len(pool)]
    rows[:, EV_ID] = idx
    return rows
