"""`physical._prune` / `Props.dominates` behaviour: dominated-plan
elimination, equal-cost ties, and a seeded property test that the sweep
never drops the overall-cheapest plan."""

import numpy as np

from repro.core.physical import CostVec, PhysPlan, Props, _prune


def _plan(cost: float, partitions=(), sort=()) -> PhysPlan:
    props = Props(partitions=frozenset(frozenset(g) for g in partitions),
                  sort=tuple(sort))
    return PhysPlan(node=None, props=props, node_cost=CostVec(net=cost))


def test_dominated_plan_eliminated():
    cheap_strong = _plan(1.0, partitions=[("k",)], sort=("k",))
    costly_weak = _plan(2.0)                      # no props, more expensive
    out = _prune([cheap_strong, costly_weak])
    assert list(out.values()) == [cheap_strong]


def test_costlier_plan_with_extra_props_survives():
    cheap_weak = _plan(1.0)
    costly_strong = _plan(2.0, partitions=[("k",)])
    out = _prune([cheap_weak, costly_strong])
    assert set(out.values()) == {cheap_weak, costly_strong}


def test_same_props_keeps_cheapest():
    a = _plan(2.0, partitions=[("k",)])
    b = _plan(1.0, partitions=[("k",)])
    out = _prune([a, b])
    assert list(out.values()) == [b]


def test_equal_cost_tie_dominance():
    # equal cost, one strictly better props vector: the weaker entry goes
    strong = _plan(1.0, partitions=[("k",)], sort=("k",))
    weak = _plan(1.0, partitions=[("k",)])
    out = _prune([weak, strong])
    assert list(out.values()) == [strong]
    out = _prune([strong, weak])                  # order-insensitive
    assert list(out.values()) == [strong]


def test_equal_cost_incomparable_props_both_survive():
    a = _plan(1.0, partitions=[("k",)])
    b = _plan(1.0, sort=("j",))
    out = _prune([a, b])
    assert set(out.values()) == {a, b}


def test_dominates_semantics():
    p = Props(partitions=frozenset({frozenset({"a"})}), sort=("a", "b"))
    q = Props(partitions=frozenset(), sort=("a",))
    assert p.dominates(q)          # superset partitions, sort prefix
    assert not q.dominates(p)
    assert p.dominates(p)          # reflexive
    r = Props(partitions=frozenset({frozenset({"c"})}), sort=())
    assert not p.dominates(r) and not r.dominates(p)   # incomparable


def test_prune_never_drops_overall_cheapest():
    """Property test (seeded, no hypothesis dependency): for random candidate
    sets, the cheapest input plan always survives, every surviving plan is
    non-dominated, and every dropped plan has a cheaper-or-equal dominator
    among the survivors."""
    rng = np.random.default_rng(42)
    attrs = ["a", "b", "c"]
    for _ in range(300):
        cands = []
        for _ in range(int(rng.integers(1, 14))):
            parts = [tuple(np.array(attrs)[rng.random(3) < 0.5]) or ("a",)
                     for _ in range(int(rng.integers(0, 3)))]
            sort = tuple(np.array(attrs)[:int(rng.integers(0, 4))])
            cands.append(_plan(float(rng.integers(1, 6)),
                               partitions=[p for p in parts if p],
                               sort=sort))
        out = _prune(cands)
        survivors = list(out.values())
        best_in = min(c.cost for c in cands)
        assert min(s.cost for s in survivors) == best_in
        for s in survivors:
            assert not any(
                o.props.dominates(s.props) and o.props != s.props
                and o.cost <= s.cost
                for o in survivors)
        for c in cands:
            if all(s is not c for s in survivors):
                assert any(
                    s.props.dominates(c.props)
                    and s.cost <= c.cost
                    for s in survivors), "dropped plan has no dominator"
