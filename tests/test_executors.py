"""Eager / masked-jit / distributed executor equivalence on the paper flows."""

import numpy as np
import pytest

from repro.configs import flows
from repro.core import executor
from repro.core.masked import run_flow_jit
from repro.core.optimizer import optimize
from repro.core.physical import Ctx

N = 6000


@pytest.fixture(scope="module")
def flow_data():
    out = {}
    for name, builder in flows.FLOWS.items():
        root, bindings = builder()
        b = bindings(N, seed=7)
        out[name] = (root, b, executor.execute(root, b))
    return out


@pytest.mark.parametrize("name", list(flows.FLOWS))
def test_all_plans_equivalent_eager(name, flow_data):
    root, b, ref = flow_data[name]
    res = optimize(root, Ctx(dop=8), include_commutes=False)
    for rp in res.ranked:
        assert executor.execute(rp.flow, b).equivalent(ref, atol=1e-4), \
            rp.order()


@pytest.mark.parametrize("name", ["q15", "clickstream"])
def test_masked_jit_equivalent(name, flow_data):
    root, b, ref = flow_data[name]
    got = run_flow_jit(root, b)
    assert got.equivalent(ref, atol=1e-4)


@pytest.mark.parametrize("name", ["q15", "clickstream"])
def test_distributed_equivalent(name, flow_data):
    from repro.core.distributed import execute_distributed

    root, b, ref = flow_data[name]
    res = optimize(root, Ctx(dop=max(1, len(_devices()))),
                   include_commutes=False)
    for rp in res.ranked[:2]:
        got = execute_distributed(rp.plan, b)
        assert got.equivalent(ref, atol=1e-4), rp.order()


def _devices():
    import jax

    return jax.devices()


def test_optimizer_beats_worst_plan():
    root, bindings = flows.q7()
    res = optimize(root, Ctx(dop=32), include_commutes=False)
    assert res.ranked[0].cost < res.ranked[-1].cost
    assert res.num_plans > 10  # bushy join orders reachable


def test_physical_strategy_flip_q15():
    """Paper Sec. 7.3: the Reduce<->Match rewrite flips the join's physical
    strategy — partition-based when the lineitem side is pre-aggregated,
    broadcast of the small supplier side when it is not."""
    root, _ = flows.q15()
    # prune=False: this test inspects the full ranked spectrum, which
    # branch-and-bound deliberately leaves unpriced
    res = optimize(root, Ctx(dop=32), include_commutes=False, prune=False)

    def match_plan(p):
        if p.node.name == "JoinSupplier":
            return p
        for i in p.inputs:
            m = match_plan(i)
            if m is not None:
                return m

    ships = {rp.order(): match_plan(rp.plan).ship for rp in res.ranked}
    assert len(set(ships.values())) >= 2          # strategies flip
    assert any("broadcast" in s for s in ships.values())
    # the aggregated-side plan keeps partition/forward shipping
    agg_first = next(s for o, s in ships.items()
                     if o.index("AggRevenue") < o.index("JoinSupplier"))
    assert "broadcast" not in agg_first
