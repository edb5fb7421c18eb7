"""TPC-H Q15 at SF1: the query as a user submits it, and its seeded data.

The flow is Q15 (Top Supplier) over the full lineitem and supplier
schemas: filter lineitem to the 90-day ship window, sum
`extendedprice * (1 - discount)` per supplier (the revenue view), keep the
suppliers whose revenue is the maximum (a WITH-TIES top-1 on the negated
revenue), join supplier on its key and project Q15's output columns.  The
generator draws both tables from the seed with dbgen's distributions as
plain numpy columns; every seed gives the same sizes.
"""

from __future__ import annotations

import numpy as np


def flow(cfg: dict):
    """The Q15 flow over `cfg`'s tables."""
    from repro.core import flow as F
    from repro.core.operators import Hints
    from repro.core.record import Schema

    n, n_su = cfg["lineitem_rows"], cfg["supplier_rows"]
    lo, hi = cfg["ship_window"]
    li = F.source("lineitem", Schema.of(**{
        c: np.dtype(t) for c, t in cfg["lineitem_columns"].items()}),
        num_records=n, sorted_on=("l_orderkey",))
    su = F.source("supplier", Schema.of(**{
        c: np.dtype(t) for c, t in cfg["supplier_columns"].items()}),
        num_records=n_su, sorted_on=("s_suppkey",))

    def ship_filter(ir, out):
        out.emit(ir.copy(), where=(ir.get("l_shipdate") >= lo)
                 & (ir.get("l_shipdate") < hi))

    def revenue(g, out):
        out.emit(g.keys().set("total_revenue", g.sum(
            g.get("l_extendedprice") * (1.0 - g.get("l_discount")))))

    def negate(ir, out):
        out.emit(ir.copy().set("neg_revenue", -ir.get("total_revenue")))

    def project(top, s, out):
        b = top.concat(s)
        for c in ("l_suppkey", "neg_revenue", "s_nationkey", "s_acctbal",
                  "s_comment"):
            b = b.drop(c)
        out.emit(b)

    f = F.map_(li, ship_filter, name="FilterShipdate",
               hints=Hints(selectivity=cfg["selectivity"]))
    r = F.reduce_(f, ["l_suppkey"], revenue, name="Revenue",
                  hints=Hints(distinct_keys=n_su))
    top = F.limit_(F.map_(r, negate, name="NegRevenue"), 1,
                   ["neg_revenue"], name="MaxRevenue")
    return F.match(top, su, ["l_suppkey"], ["s_suppkey"], project,
                   name="JoinSupplier", hints=Hints(pk_side="right"))


def generate(cfg: dict, seed: int) -> dict:
    """The two tables for one seed, as dbgen draws them (see the
    configuration's `assumed`): `lineitem_rows` lines in orderkey order and
    `supplier_rows` suppliers in key order."""
    n, n_su, n_part = (cfg["lineitem_rows"], cfg["supplier_rows"],
                       cfg["part_rows"])
    d0, d1 = cfg["order_days"]
    today = cfg["current_day"]
    rng = np.random.default_rng(seed)
    # orders of 1-7 lines, cut at the n-th line
    per_order = rng.integers(1, 8, n // 2 + 8)
    n_orders = int(np.searchsorted(np.cumsum(per_order), n)) + 1
    per_order = per_order[:n_orders]
    order = np.repeat(np.arange(n_orders), per_order)[:n]
    first = np.cumsum(per_order) - per_order
    orderkey = (order // 8) * 32 + order % 8 + 1
    linenumber = np.arange(n) - first[order] + 1
    orderdate = rng.integers(d0, d1 + 1, n_orders)[order]
    partkey = rng.integers(1, n_part + 1, n)
    corner = rng.integers(0, 4, n)
    suppkey = (partkey + corner * (n_su // 4 + (partkey - 1) // n_su)) \
        % n_su + 1
    quantity = rng.integers(1, 51, n).astype(np.float64)
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100
    ship = orderdate + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    # A=0, N=1, R=2; F=0, O=1
    returnflag = np.where(receipt <= today, 2 * rng.integers(0, 2, n), 1)
    su_key = np.arange(1, n_su + 1)
    nation = rng.integers(0, 25, n_su)
    return {
        "lineitem": {
            "l_orderkey": orderkey.astype(np.int64),
            "l_partkey": partkey.astype(np.int64),
            "l_suppkey": suppkey.astype(np.int64),
            "l_linenumber": linenumber.astype(np.int32),
            "l_quantity": quantity,
            "l_extendedprice": (quantity * retail).round(2),
            "l_discount": rng.integers(0, 11, n) / 100,
            "l_tax": rng.integers(0, 9, n) / 100,
            "l_returnflag": returnflag.astype(np.int8),
            "l_linestatus": (ship > today).astype(np.int8),
            "l_shipdate": ship.astype(np.int32),
            "l_commitdate": (orderdate + rng.integers(30, 91, n))
            .astype(np.int32),
            "l_receiptdate": receipt.astype(np.int32),
            "l_shipinstruct": rng.integers(0, 4, n).astype(np.int8),
            "l_shipmode": rng.integers(0, 7, n).astype(np.int8),
            "l_comment": rng.integers(0, 2**31 - 1, n).astype(np.int32)},
        "supplier": {
            "s_suppkey": su_key.astype(np.int64),
            "s_name": su_key.astype(np.int32),
            "s_address": rng.integers(0, 2**31 - 1, n_su).astype(np.int32),
            "s_nationkey": nation.astype(np.int32),
            "s_phone": ((nation + 10) * 10**10
                        + rng.integers(100, 1000, n_su) * 10**7
                        + rng.integers(100, 1000, n_su) * 10**4
                        + rng.integers(1000, 10000, n_su)).astype(np.int64),
            "s_acctbal": rng.integers(-99999, 1000000, n_su) / 100,
            "s_comment": rng.integers(0, 2**31 - 1, n_su).astype(np.int32)},
    }


def rows_consumed(cfg: dict) -> int:
    """The rows one query consumes, for `rows_per_s`: lineitem's."""
    return cfg["lineitem_rows"]


def logical_bytes(cfg: dict, rows_out: int) -> int:
    """Bytes the query must move at the least: every source column it reads
    (`reads`), once, plus its output rows — from the schema and the row
    counts, never from the program's buffers or padding."""
    def width(cols, names):
        return sum(np.dtype(cols[c]).itemsize for c in names)

    li = width(cfg["lineitem_columns"], cfg["reads"]["lineitem"])
    su = width(cfg["supplier_columns"], cfg["reads"]["supplier"])
    out = width(cfg["output_columns"], cfg["output_columns"])
    return cfg["lineitem_rows"] * li + cfg["supplier_rows"] * su \
        + rows_out * out
