"""TPC-H Q21 at SF1: the query as a user submits it, and its seeded data.

The flow is Q21 (Suppliers Who Kept Orders Waiting) over the full schemas
of its four relations.  The correlated `EXISTS (l2 ...)` and `NOT EXISTS
(l3 ...)` subqueries become one black-box Reduce over lineitem grouped by
`l_orderkey`, `OrderWaits`: it passes an order's lines through when the
order has two or more distinct suppliers and exactly one distinct late
supplier (a group filter, written with group min and max).  Then, as the
SQL reads, the key equalities in the order of the FROM list (supplier,
orders, nation), the WHERE clause's filters on the outer line `l1` (late),
the order (status F) and the nation (SAUDI ARABIA), the count by `s_name`
and the first 100 by (numwait descending, s_name).  Where the operators go
is the planner's choice.

lineitem and supplier are the tables `tpch-q15-sf1.py` draws and orders the
table `tpch-q7-sf1.py` draws, from the same draws in the same order, but
`o_orderstatus`, which follows dbgen's rule from the order's lines; nation
is bound once (see the configuration's `assumed`).
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

# dictionary codes of o_orderstatus (l_linestatus uses F=0, O=1 alike)
STATUS_F, STATUS_O, STATUS_P = 0, 1, 2
# a supplier key above every real one: the late-supplier minimum's fill
NO_SUPPLIER = 2**62


def _q7():
    """The Q7 configuration's module, whose draws make lineitem, supplier
    and orders."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_configs_tpch_q7_sf1_for_q21",
        os.path.join(_HERE, "tpch-q7-sf1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def nation_code(cfg: dict) -> int:
    """The dictionary code of NATION."""
    return cfg["nations"].index(cfg["nation"])


def flow(cfg: dict):
    """The Q21 flow over `cfg`'s tables."""
    from repro.core import flow as F
    from repro.core.operators import Hints
    from repro.core.record import Schema

    def src(name, rows, key):
        return F.source(name, Schema.of(**{
            c: np.dtype(t) for c, t in cfg[f"{name}_columns"].items()}),
            num_records=cfg[rows], sorted_on=(key,))

    li = src("lineitem", "lineitem_rows", "l_orderkey")
    su = src("supplier", "supplier_rows", "s_suppkey")
    od = src("orders", "orders_rows", "o_orderkey")
    na = src("nation", "nation_rows", "n_nationkey")
    nation = nation_code(cfg)
    h = cfg["hints"]

    def order_waits(g, out):
        # EXISTS l2 (another supplier on the order): two distinct suppliers;
        # NOT EXISTS l3 (another late supplier): one distinct late supplier
        s = g.get("l_suppkey")
        late = (g.get("l_receiptdate") > g.get("l_commitdate")).astype(s.dtype)
        first_late = g.min(s * late + (1 - late) * NO_SUPPLIER)
        last_late = g.max(s * late - (1 - late))
        out.emit_records(where=(g.min(s) != g.max(s))
                         & (first_late == last_late))

    def late(ir, out):
        out.emit(ir.copy(),
                 where=ir.get("l_receiptdate") > ir.get("l_commitdate"))

    def status_f(ir, out):
        out.emit(ir.copy(), where=ir.get("o_orderstatus") == STATUS_F)

    def in_nation(ir, out):
        out.emit(ir.copy(), where=ir.get("n_name") == nation)

    def numwait(g, out):
        out.emit(g.keys().set("numwait", g.count().astype(np.int32)))

    def negate(ir, out):
        out.emit(ir.copy().set("neg_numwait", -ir.get("numwait")))

    pk = Hints(pk_side="right")
    w = F.reduce_(li, ["l_orderkey"], order_waits, name="OrderWaits",
                  hints=Hints(group_selectivity=h["order_waits"],
                              distinct_keys=cfg["orders_rows"]))
    j = F.match(w, su, ["l_suppkey"], ["s_suppkey"], name="JoinSupplier",
                hints=pk)
    j = F.match(j, od, ["l_orderkey"], ["o_orderkey"], name="JoinOrders",
                hints=pk)
    j = F.match(j, na, ["s_nationkey"], ["n_nationkey"], name="JoinNation",
                hints=pk)
    f = F.map_(j, late, name="FilterLate",
               hints=Hints(selectivity=h["late"]))
    f = F.map_(f, status_f, name="FilterStatusF",
               hints=Hints(selectivity=h["status_f"]))
    f = F.map_(f, in_nation, name="FilterSaudi",
               hints=Hints(selectivity=h["nation"]))
    c = F.reduce_(f, ["s_name"], numwait, name="NumWait",
                  hints=Hints(distinct_keys=h["suppliers_waiting"]))
    n = F.map_(c, negate, name="NegNumWait")
    return F.limit_(n, cfg["limit"], ["neg_numwait", "s_name"],
                    name="Top100")


def order_status(orderkey, linestatus) -> np.ndarray:
    """dbgen's o_orderstatus from each order's lines (lineitem in orderkey
    order): F when every line is F, O when every line is O, else P."""
    starts = np.flatnonzero(np.r_[True, orderkey[1:] != orderkey[:-1]])
    lo = np.minimum.reduceat(linestatus, starts)
    hi = np.maximum.reduceat(linestatus, starts)
    return np.where(hi == STATUS_F, STATUS_F,
                    np.where(lo == STATUS_O, STATUS_O, STATUS_P)) \
        .astype(np.int8)


def generate(cfg: dict, seed: int) -> dict:
    """The four tables for one seed: lineitem, supplier and orders as
    `tpch-q7-sf1.py` draws them (its customer draws, which come first,
    included), `o_orderstatus` set by `order_status`, and the spec's nations
    bound once as `nation`."""
    data = _q7().generate(dict(cfg, nation_aliases={"nation": "n_"}), seed)
    del data["customer"]
    li = data["lineitem"]
    data["orders"]["o_orderstatus"] = order_status(li["l_orderkey"],
                                                   li["l_linestatus"])
    return data


def rows_consumed(cfg: dict) -> int:
    """The rows one query consumes, for `rows_per_s`: lineitem's."""
    return cfg["lineitem_rows"]


def logical_bytes(cfg: dict, rows_out: int) -> int:
    """Bytes the query must move at the least: every source column it reads
    (`reads`), once, plus its output rows — from the schema and the row
    counts, never from the program's buffers or padding."""
    total = rows_out * sum(np.dtype(t).itemsize
                           for t in cfg["output_columns"].values())
    for table, names in cfg["reads"].items():
        cols = cfg[f"{table}_columns"]
        total += cfg[f"{table}_rows"] * sum(np.dtype(cols[c]).itemsize
                                            for c in names)
    return total
