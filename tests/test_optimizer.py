"""Interleaved branch-and-bound optimizer vs. the two-phase reference.

The acceptance bar for the interleaved search: on every evaluation flow it
must return the SAME best plan — identical operator order and total cost
(within 1e-9) — as exhaustively pricing every enumerated flow.  Pruning may
only skip flows that provably cannot win.
"""

import numpy as np
import pytest

import repro.core.optimizer as optimizer_mod
from repro.configs import flows
from repro.core import flow as F
from repro.core.enumeration import PlanSpaceExceeded, enumerate_plans
from repro.core.operators import Hints, commute_id, struct_id
from repro.core.optimizer import optimize, optimize_two_phase
from repro.core.physical import Ctx
from repro.core.record import Schema


def _assert_same_best(root, **kw):
    a = optimize(root, Ctx(dop=32), **kw)
    b = optimize_two_phase(root, Ctx(dop=32), **kw)
    assert a.best.flow.op_names() == b.best.flow.op_names(), \
        (a.best.order(), b.best.order())
    assert abs(a.best.cost - b.best.cost) <= 1e-9
    return a, b


@pytest.mark.parametrize("name", list(flows.FLOWS))
@pytest.mark.parametrize("include_commutes", [True, False])
def test_same_best_plan_as_two_phase(name, include_commutes):
    root, _ = flows.FLOWS[name]()
    a, b = _assert_same_best(root, include_commutes=include_commutes)
    # the searches cover the same logical plan space
    assert a.num_enumerated == b.num_enumerated


def test_pruning_skips_but_never_misses():
    root, _ = flows.FLOWS["q7"]()
    a = optimize(root, Ctx(dop=32))
    assert a.num_pruned > 0                      # the bound actually bites
    assert len(a.ranked) + a.num_pruned == a.num_enumerated
    assert a.ranked[0].cost == min(r.cost for r in a.ranked)


def test_join_tree_same_best_plan():
    for builder, n in ((flows.star_join, 5), (flows.chain_join, 6)):
        _assert_same_best(builder(n), include_commutes=False,
                          max_plans=100_000)
        _assert_same_best(builder(n), include_commutes=True,
                          max_plans=100_000)


def test_unary_group_search_matches_closure():
    """Force the group-lattice fast path on small unary flows and compare
    against the materializing reference, including order-sensitive stats
    (filters + reduces with and without distinct-key hints)."""
    old = optimizer_mod.GROUP_SEARCH_THRESHOLD
    optimizer_mod.GROUP_SEARCH_THRESHOLD = 0
    try:
        root, _ = flows.textmining()
        _assert_same_best(root)

        rng = np.random.default_rng(7)
        fields = ["A", "B", "C", "D"]
        for trial in range(15):
            sch = Schema.of(**{f: np.int64 for f in fields})
            node = F.source("I", sch,
                            num_records=int(rng.integers(1000, 1_000_000)))
            for i in range(int(rng.integers(3, 6))):
                tgt = fields[int(rng.integers(0, 4))]
                if rng.random() < 0.7:
                    def udf(ir, out, tgt=tgt):
                        out.emit(ir.copy().set(tgt, ir.get(tgt) + 1))

                    udf.__name__ = f"m{trial}_{i}"
                    node = F.map_(node, udf, name=f"M{i}", hints=Hints(
                        selectivity=float(rng.uniform(0.1, 1.0))))
                else:
                    def udf(g, out, tgt=tgt):
                        out.emit_records(where=g.any(g.get(tgt) > 0))

                    udf.__name__ = f"r{trial}_{i}"
                    node = F.reduce_(node, [fields[int(rng.integers(0, 4))]],
                                     udf, name=f"R{i}", hints=Hints(
                        group_selectivity=float(rng.uniform(0.2, 0.9))))
            _assert_same_best(node)
    finally:
        optimizer_mod.GROUP_SEARCH_THRESHOLD = old


def test_group_search_handles_factorial_spaces():
    """map-chain-9 has 9! = 362880 orderings; the group search must price it
    through the subset lattice without materializing them."""
    chain = flows.map_chain(9)
    res = optimize(chain, Ctx(dop=8))
    assert res.num_enumerated == 362_880
    # identical maps: every order costs the same, the original order wins
    assert res.best.flow.op_names() == chain.op_names()


def test_plan_space_exceeded_carries_partial_count():
    chain = flows.map_chain(6)  # 720 orderings
    with pytest.raises(PlanSpaceExceeded) as ei:
        enumerate_plans(chain, max_plans=100)
    assert ei.value.limit == 100
    assert ei.value.count == 100
    assert "100" in str(ei.value)
    # the optimizer's closure path propagates it too
    with pytest.raises(PlanSpaceExceeded):
        optimize(chain, Ctx(dop=8), max_plans=100)
    # and PlanSpaceExceeded still is a RuntimeError for legacy callers
    assert issubclass(PlanSpaceExceeded, RuntimeError)


def _brute_force_closure(flow, cap=5000) -> set:
    """Reference enumeration: raw local_rewrites applied at every position,
    no hash-consing, no commute-class quotient."""
    from repro.core.reorder import local_rewrites

    def rewrites_everywhere(tree):
        yield from local_rewrites(tree)
        for i, child in enumerate(tree.children):
            for sub in rewrites_everywhere(child):
                kids = list(tree.children)
                kids[i] = sub
                try:
                    yield tree.with_children(*kids)
                except (ValueError, KeyError):
                    continue

    seen = {flow.canonical()}
    work = [flow]
    while work:
        cur = work.pop()
        for t in rewrites_everywhere(cur):
            c = t.canonical()
            if c not in seen:
                assert len(seen) < cap
                seen.add(c)
                work.append(t)
    return seen


@pytest.mark.parametrize("builder,n", [
    (flows.chain_join, 4), (flows.chain_join, 5), (flows.star_join, 4)])
def test_closure_matches_brute_force_joins(builder, n):
    flow = builder(n)
    fast = {p.canonical() for p in enumerate_plans(flow, max_plans=100_000)}
    assert fast == _brute_force_closure(flow)


def test_closure_matches_brute_force_cross():
    """Regression: both conjugate rotations of a Cross (where, unlike Match,
    key locality pins nothing) must be generated — a side=1 key mix-up in
    the rewrite engine once suppressed half the cross plan space."""
    import numpy as np

    from repro.core.record import Schema

    rels = [F.source(f"R{i}", Schema.of(**{f"x{i}": np.int64}),
                     num_records=10 * (i + 1)) for i in range(3)]
    flow = F.cross(F.cross(rels[0], rels[1], name="CA"), rels[2], name="CB")
    fast = {p.canonical() for p in enumerate_plans(flow, max_plans=100_000)}
    ref = _brute_force_closure(flow)
    assert fast == ref
    # left-deep start as well as right-deep
    flow2 = F.cross(rels[0], F.cross(rels[1], rels[2], name="CA2"),
                    name="CB2")
    fast2 = {p.canonical() for p in enumerate_plans(flow2, max_plans=100_000)}
    assert fast2 == _brute_force_closure(flow2)


def test_structural_ids_follow_canonical():
    """Hash-consed ids agree with the canonical string exactly."""
    root, _ = flows.FLOWS["q7"]()
    plans = enumerate_plans(root, include_commutes=True)
    by_sid = {}
    by_can = {}
    for p in plans:
        by_sid.setdefault(struct_id(p), set()).add(p.canonical())
        by_can.setdefault(p.canonical(), set()).add(struct_id(p))
    assert all(len(v) == 1 for v in by_sid.values())
    assert all(len(v) == 1 for v in by_can.values())
    # commute ids collapse argument order: q7 has 41 distinct pure
    # reorderings; aggregation splitting strictly enlarges the space
    # (AggRevenue is decomposable) without disturbing the reordering core
    reorder_only = enumerate_plans(root, include_commutes=True,
                                   split_reduces=False)
    assert len({commute_id(p) for p in reorder_only}) == 41
    split_cids = {commute_id(p) for p in plans}
    assert {commute_id(p) for p in reorder_only} < split_cids
    assert any(".pre" in p.canonical() for p in plans)


# ---------------------------------------------------------------------------
# The memoized group search on flows with joins (DESIGN.md §4.2)
# ---------------------------------------------------------------------------
def _tpch_q7_flow():
    """TPC-H Q7's six-relation flow from the benchmark's configuration, at
    a tiny scale (the space does not depend on the row counts)."""
    import importlib.util
    import json
    import os

    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs")
    with open(os.path.join(here, "tpch-q7-sf1.json")) as f:
        cfg = json.load(f)
    cfg.update(lineitem_rows=24_000, supplier_rows=40, part_rows=800,
               orders_rows=6_000, customer_rows=600)
    spec = importlib.util.spec_from_file_location(
        "tpch_q7_sf1_flow", os.path.join(here, "tpch-q7-sf1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.flow(cfg)


def _joined_flowgen_seeds(n: int) -> list:
    """The first `n` seeds whose `tests/flowgen.py` flow has a binary
    operator."""
    from flowgen import random_flow
    from repro.core.operators import CoGroupOp, CrossOp, MatchOp

    out, seed = [], 0
    while len(out) < n:
        root, _ = random_flow(seed)
        if any(isinstance(x, (MatchOp, CrossOp, CoGroupOp))
               for x in root.iter_nodes()):
            out.append(seed)
        seed += 1
    return out


_GROUP_CASES = (
    [(f"{name}-commutes-{c}", name, c)
     for name in ("q7", "q15", "clickstream") for c in (True, False)]
    + [(f"{b}-5-commutes-{c}", b, c)
       for b in ("chain_join", "star_join") for c in (True, False)]
    + [(f"flowgen-{s}-commutes-{c}", s, c)
       for s in _joined_flowgen_seeds(24) for c in (True, False)]
    + [("tpch-q7-commutes-False", "tpch-q7", False)])


@pytest.mark.parametrize("case,flow_id,include_commutes", _GROUP_CASES,
                         ids=[c[0] for c in _GROUP_CASES])
def test_group_search_matches_two_phase(case, flow_id, include_commutes,
                                        monkeypatch):
    """Forced onto every flow, the group search finds the exhaustive
    reference's best plan — the same cost, and on cost ties the same flow,
    the one the closure yields first — and sizes the same space."""
    from flowgen import random_flow

    monkeypatch.setattr(optimizer_mod, "GROUP_SEARCH_THRESHOLD", 0)
    if flow_id in flows.FLOWS:
        root, _ = flows.FLOWS[flow_id]()
    elif flow_id == "tpch-q7":
        root = _tpch_q7_flow()
    elif isinstance(flow_id, int):
        root, _ = random_flow(flow_id)
    else:
        root = getattr(flows, flow_id)(5)
    a, b = _assert_same_best(root, include_commutes=include_commutes,
                             max_plans=300_000)
    assert a.num_enumerated == b.num_enumerated


def test_tpch_q7_group_search_sizes_the_closure():
    """Q7's closure holds 6,908 commute classes and 221,056 flows; the
    default search plans it without materializing them."""
    root = _tpch_q7_flow()
    res = optimize(root)
    assert res.num_enumerated == 221_056
    assert len(res.ranked) < 100
    assert optimize(root, include_commutes=False).num_enumerated == 6_908
    assert "JoinSuppNation" in res.best.order()


def test_group_search_counters_count_only_when_enabled():
    from repro import obs

    root = _tpch_q7_flow()
    obs.reset()
    optimize(root)
    assert obs.snapshot()["counts"] == {}
    obs.enable()
    try:
        optimize(root)
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    assert counts["optimize.groups"] > 6          # more than the relations
    assert counts["optimize.priced"] >= counts["optimize.groups"]
