"""Plain reference for Q21 (Suppliers Who Kept Orders Waiting), in numpy;
it imports nothing of the program.

It follows the SQL: for each late line l1 of an F order whose supplier is
in NATION, `EXISTS l2` asks whether the order has a supplier other than
l1's, and `NOT EXISTS l3` whether it has a late supplier other than l1's.
Both read the order's distinct (l_orderkey, l_suppkey) pairs: all of them,
and the late ones.

The answer is all integers, so no lower precision can fail the comparison.
`control` is the query without its `NOT EXISTS` clause: the answer a group
filter that lost its second condition would give.  A run puts it in the
program's place (`run.py --control 1`) to show that the comparison fails it.
"""

from __future__ import annotations

import numpy as np


def _distinct_suppliers(orderkey, suppkey, orders, base: int):
    """Distinct suppliers of each order in `orders` (sorted keys); `base`
    exceeds every supplier key, so each pair has one code."""
    pairs = np.unique(orderkey * base + suppkey)
    keys, counts = np.unique(pairs // base, return_counts=True)
    out = np.zeros(len(orders), np.int64)
    out[np.searchsorted(orders, keys)] = counts
    return out


def _numwait(cfg: dict, data: dict, not_exists: bool) -> dict:
    li, od, su, na = (data["lineitem"], data["orders"], data["supplier"],
                      data["nation"])
    ok, sk = li["l_orderkey"], li["l_suppkey"]
    late = li["l_receiptdate"] > li["l_commitdate"]
    orders, base = od["o_orderkey"], cfg["supplier_rows"] + 1
    suppliers = _distinct_suppliers(ok, sk, orders, base)
    late_suppliers = _distinct_suppliers(ok[late], sk[late], orders, base)
    o = np.searchsorted(orders, ok)
    s = np.searchsorted(su["s_suppkey"], sk)
    n = np.searchsorted(na["n_nationkey"], su["s_nationkey"][s])
    # o_orderstatus F is code 0, n_name the nation's position (`assumed`)
    keep = late & (od["o_orderstatus"][o] == 0) \
        & (na["n_name"][n] == cfg["nations"].index(cfg["nation"]))
    keep &= suppliers[o] > 1                       # EXISTS l2
    if not_exists:
        keep &= late_suppliers[o] == 1             # NOT EXISTS l3
    names, counts = np.unique(su["s_name"][s][keep], return_counts=True)
    top = np.lexsort((names, -counts))[:cfg["limit"]]
    return {"s_name": names[top].astype(np.int32),
            "numwait": counts[top].astype(np.int32),
            "neg_numwait": (-counts[top]).astype(np.int32)}


def reference(cfg: dict, data: dict) -> dict:
    return _numwait(cfg, data, not_exists=True)


def control(cfg: dict, data: dict) -> dict:
    return _numwait(cfg, data, not_exists=False)
