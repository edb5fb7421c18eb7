"""Plain reference for Q7 (Volume Shipping), in numpy float64; it imports
nothing of the program.

`control` is the same query computed a step below the precision the
configuration states — each group's revenue summed in float32.  A run puts
it in the program's place (`run.py --control 1`) to show that the
comparison fails it.
"""

from __future__ import annotations

import numpy as np


def _lookup(keys, table_keys):
    """Row of each key in a key-sorted table (every key is present)."""
    return np.searchsorted(table_keys, keys)


def _shipping(cfg: dict, data: dict):
    """The joined, filtered rows Q7 groups: (supp_nation, cust_nation,
    year, volume) as arrays."""
    li, od, cu, su = (data["lineitem"], data["orders"], data["customer"],
                      data["supplier"])
    n1, n2 = data["nation1"], data["nation2"]
    lo, hi = cfg["ship_window"]
    ship = li["l_shipdate"]
    keep = (ship >= lo) & (ship <= hi)
    o = _lookup(li["l_orderkey"][keep], od["o_orderkey"])
    c = _lookup(od["o_custkey"][o], cu["c_custkey"])
    s = _lookup(li["l_suppkey"][keep], su["s_suppkey"])
    sn = n1["n1_name"][_lookup(su["s_nationkey"][s], n1["n1_nationkey"])]
    cn = n2["n2_name"][_lookup(cu["c_nationkey"][c], n2["n2_nationkey"])]
    a, b = (cfg["nations"].index(n) for n in cfg["nation_pair"])
    pair = ((sn == a) & (cn == b)) | ((sn == b) & (cn == a))
    d = ship[keep][pair]
    year = cfg["first_year"] + np.searchsorted(
        np.asarray(cfg["year_starts"][1:]), d, side="right")
    volume = li["l_extendedprice"][keep][pair] \
        * (1.0 - li["l_discount"][keep][pair])
    return sn[pair], cn[pair], year.astype(np.int32), volume


def _grouped(sn, cn, year, volume, dtype) -> dict:
    keys, inv = np.unique(np.stack([sn, cn, year], 1), axis=0,
                          return_inverse=True)
    inv = inv.reshape(-1)
    revenue = np.zeros(len(keys), dtype)
    np.add.at(revenue, inv, volume.astype(dtype))
    return {"n1_name": keys[:, 0].astype(np.int32),
            "n2_name": keys[:, 1].astype(np.int32),
            "l_year": keys[:, 2].astype(np.int32),
            "revenue": revenue.astype(np.float64)}


def reference(cfg: dict, data: dict) -> dict:
    return _grouped(*_shipping(cfg, data), np.float64)


def control(cfg: dict, data: dict) -> dict:
    return _grouped(*_shipping(cfg, data), np.float32)
