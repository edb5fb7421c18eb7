"""TPC-H Q7 at SF1: the query as a user submits it, and its seeded data.

The flow is Q7 (Volume Shipping) over the full schemas of its six
relations, written as the SQL reads: the five key equalities in the order
of the FROM list (supplier, lineitem, orders, customer, nation n1, nation
n2), then the WHERE clause's two filters, then the select list's year and
volume, then the grouped sum.  Choosing the join order is the planner's
job.  nation is bound twice, as `nation1` and `nation2`, with its columns
prefixed `n1_` and `n2_` (the SQL's aliases).

lineitem and supplier are the tables `tpch-q15-sf1.py` draws, from the same
draws in the same order; orders, customer and nation are drawn after them
(see the configuration's `assumed`).  Every seed gives the same sizes but
orders', which follows lineitem's.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _q15():
    """The Q15 configuration's module, whose draws make lineitem and
    supplier."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_configs_tpch_q15_sf1_for_q7",
        os.path.join(_HERE, "tpch-q15-sf1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def nation_columns(cfg: dict, alias: str) -> dict:
    """nation's columns under one alias: `n_name` becomes `n1_name`."""
    prefix = cfg["nation_aliases"][alias]
    return {prefix + c[2:]: t for c, t in cfg["nation_columns"].items()}


def pair_codes(cfg: dict) -> tuple:
    """The dictionary codes of NATION1 and NATION2."""
    return tuple(cfg["nations"].index(n) for n in cfg["nation_pair"])


def flow(cfg: dict):
    """The Q7 flow over `cfg`'s tables."""
    from repro.core import flow as F
    from repro.core.operators import Hints
    from repro.core.record import Schema

    def src(name, cols, rows, key):
        return F.source(name, Schema.of(**{
            c: np.dtype(t) for c, t in cols.items()}),
            num_records=rows, sorted_on=(key,))

    su = src("supplier", cfg["supplier_columns"], cfg["supplier_rows"],
             "s_suppkey")
    li = src("lineitem", cfg["lineitem_columns"], cfg["lineitem_rows"],
             "l_orderkey")
    od = src("orders", cfg["orders_columns"], cfg["orders_rows"],
             "o_orderkey")
    cu = src("customer", cfg["customer_columns"], cfg["customer_rows"],
             "c_custkey")
    n1 = src("nation1", nation_columns(cfg, "nation1"), cfg["nation_rows"],
             "n1_nationkey")
    n2 = src("nation2", nation_columns(cfg, "nation2"), cfg["nation_rows"],
             "n2_nationkey")
    lo, hi = cfg["ship_window"]
    a, b = pair_codes(cfg)
    starts = cfg["year_starts"][1:]
    year0 = cfg["first_year"]

    def ship_filter(ir, out):
        out.emit(ir.copy(), where=(ir.get("l_shipdate") >= lo)
                 & (ir.get("l_shipdate") <= hi))

    def nation_pair(ir, out):
        s, c = ir.get("n1_name"), ir.get("n2_name")
        out.emit(ir.copy(), where=((s == a) & (c == b)) | ((s == b) & (c == a)))

    def volume(ir, out):
        d = ir.get("l_shipdate")
        year = year0 + sum((d >= s).astype(np.int32) for s in starts)
        out.emit(ir.copy().set("l_year", year.astype(np.int32)).set(
            "volume", ir.get("l_extendedprice") * (1.0 - ir.get("l_discount"))))

    def revenue(g, out):
        out.emit(g.keys().set("revenue", g.sum(g.get("volume"))))

    pk = Hints(pk_side="right")
    j = F.match(li, su, ["l_suppkey"], ["s_suppkey"], name="JoinSupplier",
                hints=pk)
    j = F.match(j, od, ["l_orderkey"], ["o_orderkey"], name="JoinOrders",
                hints=pk)
    j = F.match(j, cu, ["o_custkey"], ["c_custkey"], name="JoinCustomer",
                hints=pk)
    j = F.match(j, n1, ["s_nationkey"], ["n1_nationkey"],
                name="JoinSuppNation", hints=pk)
    j = F.match(j, n2, ["c_nationkey"], ["n2_nationkey"],
                name="JoinCustNation", hints=pk)
    f = F.map_(j, ship_filter, name="FilterShipdate",
               hints=Hints(selectivity=cfg["selectivity"]))
    f = F.map_(f, nation_pair, name="FilterNationPair",
               hints=Hints(selectivity=cfg["pair_selectivity"]))
    v = F.map_(f, volume, name="Volume")
    return F.reduce_(v, ["n1_name", "n2_name", "l_year"], revenue,
                     name="AggRevenue",
                     hints=Hints(distinct_keys=cfg["groups"]))


def generate(cfg: dict, seed: int) -> dict:
    """The six tables for one seed: lineitem and supplier as
    `tpch-q15-sf1.py` draws them, then orders (one per distinct
    `l_orderkey`, in key order), `customer_rows` customers in key order,
    and the spec's nations under both aliases."""
    data = _q15().generate(cfg, seed)
    n = cfg["lineitem_rows"]
    d0, d1 = cfg["order_days"]
    # the first two of the Q15 draws again: lines per order, order dates
    rng = np.random.default_rng(seed)
    per_order = rng.integers(1, 8, n // 2 + 8)
    n_orders = int(np.searchsorted(np.cumsum(per_order), n)) + 1
    order = np.arange(n_orders)
    orderdate = rng.integers(d0, d1 + 1, n_orders)
    rng = np.random.default_rng([seed, 7])
    n_cu = cfg["customer_rows"]
    live = np.arange(1, n_cu + 1)
    live = live[live % 3 != 0]
    cu_key = np.arange(1, n_cu + 1)
    cu_nation = rng.integers(0, 25, n_cu)
    data["orders"] = {
        "o_orderkey": ((order // 8) * 32 + order % 8 + 1).astype(np.int64),
        "o_custkey": live[rng.integers(0, len(live), n_orders)]
        .astype(np.int64),
        "o_orderstatus": rng.integers(0, 3, n_orders).astype(np.int8),
        "o_totalprice": rng.integers(85000, 55500000, n_orders) / 100,
        "o_orderdate": orderdate.astype(np.int32),
        "o_orderpriority": rng.integers(0, 5, n_orders).astype(np.int8),
        "o_clerk": rng.integers(1, 1001, n_orders).astype(np.int32),
        "o_shippriority": np.zeros(n_orders, np.int32),
        "o_comment": rng.integers(0, 2**31 - 1, n_orders).astype(np.int32)}
    data["customer"] = {
        "c_custkey": cu_key.astype(np.int64),
        "c_name": cu_key.astype(np.int32),
        "c_address": rng.integers(0, 2**31 - 1, n_cu).astype(np.int32),
        "c_nationkey": cu_nation.astype(np.int32),
        "c_phone": ((cu_nation + 10) * 10**10
                    + rng.integers(100, 1000, n_cu) * 10**7
                    + rng.integers(100, 1000, n_cu) * 10**4
                    + rng.integers(1000, 10000, n_cu)).astype(np.int64),
        "c_acctbal": rng.integers(-99999, 1000000, n_cu) / 100,
        "c_mktsegment": rng.integers(0, 5, n_cu).astype(np.int8),
        "c_comment": rng.integers(0, 2**31 - 1, n_cu).astype(np.int32)}
    n_na = cfg["nation_rows"]
    nation = {
        "n_nationkey": np.arange(n_na, dtype=np.int32),
        "n_name": np.arange(n_na, dtype=np.int32),
        "n_regionkey": np.asarray(cfg["nation_regions"], np.int32),
        "n_comment": rng.integers(0, 2**31 - 1, n_na).astype(np.int32)}
    for alias, prefix in cfg["nation_aliases"].items():
        data[alias] = {prefix + c[2:]: v.copy() for c, v in nation.items()}
    return data


def rows_consumed(cfg: dict) -> int:
    """The rows one query consumes, for `rows_per_s`: lineitem's."""
    return cfg["lineitem_rows"]


def logical_bytes(cfg: dict, rows_out: int) -> int:
    """Bytes the query must move at the least: every source column it reads
    (`reads`), once, plus its output rows — from the schema and the row
    counts, never from the program's buffers or padding."""
    def width(cols, names):
        return sum(np.dtype(cols[c]).itemsize for c in names)

    r = cfg["reads"]
    total = cfg["lineitem_rows"] * width(cfg["lineitem_columns"],
                                         r["lineitem"])
    for table in ("orders", "customer", "supplier"):
        total += cfg[f"{table}_rows"] * width(cfg[f"{table}_columns"],
                                              r[table])
    for alias in cfg["nation_aliases"]:
        total += cfg["nation_rows"] * width(cfg["nation_columns"], r[alias])
    return total + rows_out * width(cfg["output_columns"],
                                    cfg["output_columns"])
