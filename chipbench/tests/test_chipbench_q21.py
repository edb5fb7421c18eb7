"""The Q21 cell at a tiny size on the CPU: its run through
`paths/pipeline.py` is correct against the plain reference, an altered
answer and the control without `NOT EXISTS` are not, the reference is the
eager executor's answer, its tables are Q15's and Q7's where they overlap,
the program's reduce counters read, and every plan the rewrites reach keeps
the group filter below the operators KGP holds above it."""

from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench.tests._util import H, altered, correct, drive

CELL = {"name": "q21-sf1-pipeline", "config": "tpch-q21-sf1",
        "traffic": "back-to-back", "chips": 1}
TINY_ROWS = 60_000      # lineitem rows of a tiny Q21: about 4 SAUDI suppliers
TINY_LIMIT = 5          # so the tiny answer meets the top-k


def tiny_config(name: str = "tpch-q21-sf1") -> dict:
    """`name`'s configuration at TINY_ROWS lineitem rows, the other tables
    cut in SF1's proportions (the Q7 test's cut), Q21's limit at
    TINY_LIMIT."""
    cfg = H.load_json("configs", f"{name}.json")
    n_su = TINY_ROWS // 600
    cfg.update(lineitem_rows=TINY_ROWS, supplier_rows=n_su,
               part_rows=20 * n_su)
    if "orders_rows" in cfg:
        cfg.update(orders_rows=TINY_ROWS // 4, customer_rows=TINY_ROWS // 40)
    if "limit" in cfg:
        cfg.update(limit=TINY_LIMIT)
    return cfg


def tiny_q21_run(seed: int = 2**31 + 5, control: bool = False) -> H.Run:
    import jax

    return H.Run(CELL, seed, 1.0, False, time.time(), jax.devices()[:1],
                 config=tiny_config(), control=control)


def flows():
    return H.load_module("configs", "tpch-q21-sf1.py")


def test_q21_pipeline_cell_rehearsal():
    r = tiny_q21_run()
    out = drive(r)
    assert correct(out), out.checks
    assert r.window_compiles == 0
    assert out.attempted == out.counters["queries"] > 0
    assert out.counters["rows_out"] > 0
    assert "OrderWaits" in out.counters["plan"]


def test_q21_answer_altered(monkeypatch):
    from repro.core.pipeline import CompiledPlan

    run_device = CompiledPlan.run_device
    monkeypatch.setattr(CompiledPlan, "run_device",
                        lambda self, *a, **k: altered(
                            run_device(self, *a, **k)))
    out = drive(tiny_q21_run())
    assert not correct(out)
    assert out.checks["rows_mismatched"][0] > 0


@pytest.mark.parametrize("seed", [2**31 + 3, 11])
def test_q21_control_fails_the_limit(seed):
    """The control (Q21 without NOT EXISTS) in the program's place comes
    out not correct, by mismatched rows: the answer has no float column."""
    out = drive(tiny_q21_run(seed, control=True))
    assert out.counters["control"]
    assert not correct(out)
    assert out.checks["rows_mismatched"][0] > 0
    assert out.checks["rel_err"][0] == 0.0


@pytest.mark.parametrize("seed", [2**32 + 17, 5, 2**31 - 1])
def test_q21_reference_is_the_eager_executor(seed):
    """The SQL-shaped reference gives the answer the eager executor
    (`core/executor.py`) computes for the flow as written."""
    from chipbench import compare as C
    from repro.core import executor
    from repro.core.record import batch_from_dict

    cfg = tiny_config()
    data = flows().generate(cfg, seed)
    got = executor.execute(flows().flow(cfg), {
        n: batch_from_dict(c) for n, c in data.items()})
    ref = H.load_module("configs", "tpch-q21-sf1.ref.py").reference(cfg, data)
    assert len(ref["s_name"]) > 0
    assert C.compare(C.host_columns(got), ref) == (0, 0.0)


@pytest.mark.parametrize("seed", [2**33 + 5, 4])
def test_q21_tables_are_q15s_and_q7s(seed):
    """lineitem and supplier are Q15's draws, orders Q7's but for
    `o_orderstatus`, which follows dbgen's rule from the order's lines."""
    q21 = flows().generate(tiny_config(), seed)
    q15 = H.load_module("configs", "tpch-q15-sf1.py").generate(
        tiny_config("tpch-q15-sf1"), seed)
    q7 = H.load_module("configs", "tpch-q7-sf1.py").generate(
        tiny_config("tpch-q7-sf1"), seed)
    assert sorted(q21) == ["lineitem", "nation", "orders", "supplier"]
    for t in ("lineitem", "supplier"):
        assert list(q21[t]) == list(q15[t])
        for c in q15[t]:
            assert np.array_equal(q21[t][c], q15[t][c]), (t, c)
    assert list(q21["orders"]) == list(q7["orders"])
    for c in q7["orders"]:
        if c != "o_orderstatus":
            assert np.array_equal(q21["orders"][c], q7["orders"][c]), c
    for c in q7["nation1"]:
        assert np.array_equal(q21["nation"]["n_" + c[3:]], q7["nation1"][c])
    li, od = q21["lineitem"], q21["orders"]
    o = np.searchsorted(od["o_orderkey"], li["l_orderkey"])
    status = od["o_orderstatus"]
    for code, ok in ((0, li["l_linestatus"] == 0), (1, li["l_linestatus"] == 1)):
        every = np.ones(len(status), bool)
        np.logical_and.at(every, o, ok)
        assert np.array_equal(status == code, every), code
    assert set(np.unique(status)) == {0, 1, 2}
    cfg = tiny_config()
    for c, dtype in cfg["orders_columns"].items():
        assert od[c].dtype == np.dtype(dtype), c


def test_q21_logical_bytes_at_sf1():
    cfg = H.load_json("configs", "tpch-q21-sf1.json")
    # lineitem's orderkey and suppkey (8 bytes), commit and receipt dates
    # (4); orders' key (8) and status (1); supplier's key (8), name and
    # nationkey (4); nation's key and name (4)
    assert flows().logical_bytes(cfg, 0) == (
        6_001_215 * 24 + 1_500_000 * 9 + 10_000 * 16 + 25 * 8) == 157_689_360
    # each output row: s_name, numwait and neg_numwait
    assert flows().logical_bytes(cfg, 100) == 157_689_360 + 100 * 12
    assert flows().rows_consumed(cfg) == 6_001_215


def _traced_counts(cfg_name: str, cfg: dict) -> dict:
    """The program's counters while `cfg`'s flow is planned, compiled and
    run once through the pipeline (its first run traces)."""
    from repro import obs
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache
    from repro.core.record import batch_from_dict

    mod = H.load_module("configs", f"{cfg_name}.py")
    data = mod.generate(cfg, 9)
    obs.reset()
    obs.enable()
    try:
        cp = optimize(mod.flow(cfg)).compile(cache=ExecutableCache())
        cp.run({n: batch_from_dict(c) for n, c in data.items()})
    finally:
        obs.disable()
        counts = obs.snapshot()["counts"]
        obs.reset()
    return counts


def test_q21_counts_its_group_filter_over_ordered_lines():
    """OrderWaits segments lineitem in its orderkey order (no sort), masks
    whole groups once, and runs its four min/max as segmented scans."""
    counts = _traced_counts("tpch-q21-sf1", tiny_config())
    assert counts["reduce.sorted"] >= 1
    assert counts["reduce.group_filter"] == 1
    assert counts["segment.scan"] >= 4


def test_q15_counts_its_aggregate_sort():
    """Q15's revenue by supplier sorts lineitem's orderkey order away and
    filters no group."""
    from chipbench.tests._util import TINY_ROWS as Q15_ROWS

    cfg = H.load_json("configs", "tpch-q15-sf1.json")
    n_su = Q15_ROWS // 600
    cfg.update(lineitem_rows=Q15_ROWS, supplier_rows=n_su, part_rows=20 * n_su)
    counts = _traced_counts("tpch-q15-sf1", cfg)
    assert counts["reduce.sort"] >= 1
    assert counts.get("reduce.group_filter", 0) == 0


def test_reduce_sorts_reader():
    """The reader counts the sorting Reduce stages of the cell's program:
    NumWait's partial aggregate sorts, OrderWaits does not."""
    import jax

    from repro import obs

    r = H.Run(H.cell(H.benchmark(), "q21-sf1-pipeline"), 5, 1.0, False,
              time.time(), jax.devices()[:1], config=tiny_config())
    read = H.load_module("metrics", "pipeline.reduce_sorts.py").read
    assert read(H.ReadContext(r, None, {})) == 1
    assert obs.snapshot() == {"spans": {}, "counts": {}}


def test_reduce_sorts_reader_reads_nothing_without_counters(monkeypatch):
    """On a program that does not count its Reduces the reader reads
    nothing, and does not raise."""
    import jax

    from repro import obs
    from repro.core import masked

    monkeypatch.setattr(obs, "count", lambda name, n: None)
    monkeypatch.setattr(masked, "count", lambda name, n: None)
    r = H.Run(CELL, 5, 1.0, False, time.time(), jax.devices()[:1],
              config=tiny_config())
    read = H.load_module("metrics", "pipeline.reduce_sorts.py").read
    assert read(H.ReadContext(r, None, {})) is None


# ---------------------------------------------------------------------------
# Plan soundness: where the rewrites may put the group filter
# ---------------------------------------------------------------------------
# What the guards hold above OrderWaits: its group filter reads l_suppkey
# and the two dates, outside its key, so a filter that drops single lines
# (FilterLate) fails KGP and may not go below it; the supplier and nation
# joins match on keys outside l_orderkey, which invariant grouping refuses.
# JoinOrders matches on the key itself, orders its PK side, and may go
# below.
ABOVE_ORDER_WAITS = ("FilterLate", "JoinSupplier", "JoinNation")


def _below(flow, name: str) -> set:
    """Names of the operators in the sub-flow under the operator `name`."""
    node = next(n for n in flow.iter_nodes() if n.name == name)
    return {n.name for n in node.iter_nodes() if n is not node}


def test_q21_closure_keeps_kgp():
    from repro.core import enumeration

    plans = list(enumeration.closure(flows().flow(tiny_config())))
    assert len(plans) == 4544
    below = [_below(p, "OrderWaits") for p in plans]
    for b in below:
        assert not b & set(ABOVE_ORDER_WAITS), b
    assert sum("JoinOrders" in b for b in below) == 1344


def test_q21_join_orders_below_the_group_filter_runs():
    """A plan with JoinOrders under OrderWaits, as invariant grouping
    allows, runs through the pipeline and gives the reference's answer,
    as the plan the planner picks (JoinOrders above) does."""
    from chipbench import compare as C
    from repro.core import enumeration
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache, compile_plan
    from repro.core.record import batch_from_dict

    cfg = tiny_config()
    data = flows().generate(cfg, 2**31 + 9)
    ref = H.load_module("configs", "tpch-q21-sf1.ref.py").reference(cfg, data)
    bound = {n: batch_from_dict(c) for n, c in data.items()}
    below = next(p for p in enumeration.closure(flows().flow(cfg))
                 if "JoinOrders" in _below(p, "OrderWaits"))
    res = optimize(flows().flow(cfg))
    assert "JoinOrders" not in _below(res.best.flow, "OrderWaits")
    for cp in (compile_plan(below, cache=ExecutableCache()),
               res.compile(cache=ExecutableCache())):
        got = C.host_columns(cp.run(bound))
        assert C.compare(got, ref) == (0, 0.0)


# The plan the planner picks for Q21 at SF1's row counts and hints:
# OrderWaits stays directly over lineitem, where its input order elides the
# sort, and only FilterLate (one line at a time) follows it before the
# joins; the status and nation filters go down to the orders and supplier
# sides of their joins, and NumWait splits into a partial and a merge.  A
# change that moves it updates this row and says why.
Q21_SF1_PLAN = (
    "nation->supplier->JoinNation->FilterSaudi->orders->FilterStatusF->"
    "lineitem->OrderWaits->FilterLate->JoinOrders->JoinSupplier->"
    "NumWait.pre->NumWait.merge->NegNumWait->Top100",
    [("reduce", "OrderWaits"), ("chain", "FilterLate"),
     ("chain", "FilterStatusF"), ("match", "JoinOrders"),
     ("match", "JoinNation"), ("chain", "FilterSaudi"),
     ("match", "JoinSupplier"), ("reduce", "NumWait.pre"),
     ("reduce", "NumWait.merge"), ("chain", "NegNumWait"),
     ("limit", "Top100")])


def test_q21_plan_choice_at_sf1():
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache

    res = optimize(flows().flow(H.load_json("configs", "tpch-q21-sf1.json")))
    order, stages = Q21_SF1_PLAN
    assert res.best.order() == order
    cp = res.compile(cache=ExecutableCache())
    assert [(st.kind, st.ops[-1].name) for st in cp.stages] == stages
