"""Whole-stage megakernel lowering (DESIGN.md §10): routing, identity,
caching, observation.

The megakernel span executor must be INVISIBLE semantically: on the all-
int64 flowgen corpus every fused execution is bit-identical (row multiset,
no tolerance) to the composed per-stage walk and the eager reference —
across adversarial cost hints (which shift the planned capacities the
route planner sees) and drifting batch distributions (which exercise
truncation re-runs).  Beyond identity, these tests pin the contract's
edges: fallback routing (Cross/CoGroup/shared subtrees/non-blockable
capacities stay solo), executable-cache key separation (fused and composed
traces never share an executable), obs side-channel parity (the adaptive
layer sees identical boundary counts either route), and the truncation
force-swap staying on the megakernel route.
"""

from __future__ import annotations

import numpy as np
import pytest

import flowgen
from repro.configs import flows
from repro.core import executor, flow as F
from repro.core import masked as M
from repro.core import pipeline as PL
from repro.core.cost import seed_source_stats
from repro.core.operators import Hints
from repro.core.pipeline import (AdaptiveConfig, ExecutableCache,
                                 compile_plan)
from repro.core.record import Schema, batch_from_dict
from repro.kernels import megakernel as MK


def _mega_entries(routes):
    return [e for e in (routes or ()) if e[0] == "mega"]


def _routes_for(root, bindings, **kw):
    cp = compile_plan(root, cache=ExecutableCache(), **kw)
    cp.run(bindings)
    return cp._last_routes


# ---------------------------------------------------------------------------
# Bit-identity: configured flows + the flowgen differential corpus
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(flows.FLOWS))
def test_configured_flows_bit_identical(name):
    root, mk = flows.FLOWS[name]()
    b = mk(2048, seed=11)
    on = compile_plan(root, cache=ExecutableCache(), use_megakernel=True)
    off = compile_plan(root, cache=ExecutableCache(), use_megakernel=False)
    assert flowgen.canonical_rows(on.run(b)) \
        == flowgen.canonical_rows(off.run(b))
    if name != "textmining":  # single-stage lowering: nothing to fuse
        assert _mega_entries(on._last_routes)
    assert not _mega_entries(off._last_routes)


@pytest.mark.parametrize("seed", range(8))
def test_flowgen_corpus_bit_identical(seed):
    """Random flows: megakernel on/off, plain and adversarial hints, must
    all reproduce the eager reference bit-exactly."""
    root, mk = flowgen.random_flow(seed)
    for variant in (root, flowgen.adversarial_hints(root, seed)):
        b = mk(seed + 1)
        ref = flowgen.canonical_rows(executor.execute(variant, b))
        for mega in (True, False):
            cp = compile_plan(variant, cache=ExecutableCache(),
                              use_megakernel=mega)
            assert flowgen.canonical_rows(cp.run(b)) == ref, (
                f"seed={seed} mega={mega}\n" + variant.pretty())


@pytest.mark.parametrize("seed", (1, 4))
def test_flowgen_adaptive_drift_bit_identical(seed):
    """The full adaptive serve — drift, calibration swaps, truncation
    re-runs — stays bit-identical with the megakernel route enabled."""
    root, mk = flowgen.random_flow(seed)
    flowgen.assert_adaptive_identical(root, mk, seed, use_megakernel=True)


# ---------------------------------------------------------------------------
# Fallback routing
# ---------------------------------------------------------------------------
def _src(name, rows=64, **fields):
    return F.source(name, Schema.of(**fields), num_records=rows)


def _keep_all(ir, out):
    out.emit(ir.copy(), where=ir.get("v") >= -10**9)


def _agg(g, out):
    out.emit(g.keys().set("s", g.sum("v")))


def test_single_stage_flow_has_no_route():
    root, mk = flows.FLOWS["textmining"]()
    assert _routes_for(root, mk(1024, seed=0)) is None


def test_cross_stays_solo():
    left = F.map_(_src("L", k=np.int64, v=np.int64), _keep_all, name="Keep")
    right = _src("R", rows=1, a=np.int64, b=np.int64)
    root = F.cross(left, right)
    b = {"L": batch_from_dict({"k": np.arange(64, dtype=np.int64),
                               "v": np.arange(64, dtype=np.int64)}),
         "R": batch_from_dict({"a": np.zeros(1, np.int64),
                               "b": np.ones(1, np.int64)})}
    routes = _routes_for(root, b, use_megakernel=True)
    for e in _mega_entries(routes):
        # the cross stage itself must never be fused
        cp_stages = PL.lower(root)
        assert all(cp_stages[i].kind != "cross"
                   for i in range(e[1], e[2]))


def test_non_pk_match_and_cogroup_are_not_fusable():
    lsrc = _src("L", k=np.int64, v=np.int64)
    rsrc = _src("R", k2=np.int64, w=np.int64)
    general = F.match(lsrc, rsrc, ["k"], ["k2"])  # no pk_side hint
    for st in PL.lower(general):
        if st.kind == "match":
            assert not MK._stage_fusable(st)

    def cg(gl, gr, out):
        out.emit(gl.keys().set("s", gl.sum("v") + gr.sum("w")))

    cog = F.cogroup(lsrc, rsrc, ["k"], ["k2"], cg)
    for st in PL.lower(cog):
        if st.kind == "cogroup":
            assert not MK._stage_fusable(st)


def test_non_blockable_capacity_defeats_fusion():
    src = _src("S", k=np.int64, v=np.int64)
    root = F.reduce_(F.map_(src, _keep_all, name="Keep"), ["k"], _agg,
                     hints=Hints(distinct_keys=4))
    stages = PL.lower(root)
    assert MK.plan_routes(stages, {"S": 64}) is not None
    assert MK.plan_routes(stages, {"S": 12}) is None  # not %8
    assert MK.plan_routes(stages, {"S": 4}) is None   # below the floor


def test_vmem_budget_defeats_fusion():
    src = _src("S", k=np.int64, v=np.int64)
    root = F.reduce_(F.map_(src, _keep_all, name="Keep"), ["k"], _agg,
                     hints=Hints(distinct_keys=4))
    stages = PL.lower(root)
    assert MK.plan_routes(stages, {"S": 1024}) is not None
    assert MK.plan_routes(stages, {"S": 1024}, vmem_bytes=64) is None


def test_shared_subtree_stays_solo():
    """An interior stage output consumed by TWO stages cannot be fused
    through — the span would hide a result another stage needs.  The flow
    API cannot express a rejoined diamond (schema unions collide on the
    key), so the guard is pinned on a hand-extended stage list."""
    import dataclasses

    src = _src("S", k=np.int64, v=np.int64)
    root = F.reduce_(F.map_(src, _keep_all, name="Keep"), ["k"], _agg,
                     hints=Hints(distinct_keys=4))
    stages = PL.lower(root)
    assert _mega_entries(MK.plan_routes(stages, {"S": 256}))
    # a second consumer of the chain stage defeats fusing through it
    extra = dataclasses.replace(stages[-1], inputs=(("stage", 0),))
    routes = MK.plan_routes(stages + (extra,), {"S": 256})
    for e in _mega_entries(routes or ()):
        assert not (e[1] <= 0 < e[2] - 1)


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv(PL.MEGAKERNEL_ENV, "0")
    root, mk = flows.FLOWS["q15"]()
    cp = compile_plan(root, cache=ExecutableCache())
    assert not cp.use_megakernel
    cp.run(mk(1024, seed=0))
    assert cp._last_routes is None


# ---------------------------------------------------------------------------
# Cache-key separation
# ---------------------------------------------------------------------------
def test_fused_and_composed_never_share_an_executable():
    root, mk = flows.FLOWS["q15"]()
    cache = ExecutableCache()
    b = mk(1024, seed=3)
    on = compile_plan(root, cache=cache, use_megakernel=True)
    off = compile_plan(root, cache=cache, use_megakernel=False)
    on.run(b)
    off.run(b)
    s = cache.stats()
    assert s.misses == 2 and s.traces == 2
    # warm re-runs hit their OWN entries
    on.run(b)
    off.run(b)
    assert cache.stats().traces == 2
    assert cache.stats().hits == 2


# ---------------------------------------------------------------------------
# Obs side-channel parity
# ---------------------------------------------------------------------------
def test_observe_and_caps_parity_between_routes():
    """The adaptive layer's inputs — per-stage boundary counts, aux counts
    and planned capacities — must be identical whichever route executed."""
    root, mk = flows.FLOWS["q15"]()
    cp = compile_plan(root, cache=ExecutableCache(), use_megakernel=True)
    masked = cp.bind_device(mk(2048, seed=9))
    stats_memo = seed_source_stats(
        root, {n: b.capacity for n, b in masked.items()}, {})
    routes = cp._routes({n: b.capacity for n, b in masked.items()})
    assert _mega_entries(routes)

    def run(route):
        obs, caps = [], []
        out = PL.run_stages(cp.stages, masked, cp.compact_slack, stats_memo,
                            observe=obs, caps=caps, routes=route)
        return out, obs, caps

    out_m, obs_m, caps_m = run(routes)
    out_c, obs_c, caps_c = run(None)
    assert caps_m == caps_c
    assert len(obs_m) == len(obs_c) == len(cp.stages)
    for (cm, am), (cc, ac) in zip(obs_m, obs_c):
        assert int(cm) == int(cc)
        assert int(am) == int(ac)
    assert flowgen.canonical_rows(out_m.to_record_batch()) \
        == flowgen.canonical_rows(out_c.to_record_batch())


# ---------------------------------------------------------------------------
# Truncation force-swap stays on the megakernel route
# ---------------------------------------------------------------------------
def test_truncation_force_swap_keeps_megakernel_route():
    """An underestimated hint overruns a capacity INSIDE the fused span;
    the adaptive re-plan must repair it without falling back to the
    composed lowering (the route is replanned, not abandoned)."""
    n = 2048
    src = F.source("I", Schema.of(k=np.int64, v=np.int64), num_records=n)

    def keep(ir, out):
        out.emit(ir.copy(), where=ir.get("v") >= 0)  # keeps ~90%

    root = F.reduce_(
        F.map_(src, keep, name="Keep", hints=Hints(selectivity=0.005)),
        ["k"], _agg, hints=Hints(distinct_keys=64))
    rng = np.random.default_rng(7)
    b = {"I": batch_from_dict({"k": rng.integers(0, 64, n),
                               "v": rng.integers(-1, 10, n)})}
    ref = executor.execute(root, b)
    cp = compile_plan(root, cache=ExecutableCache(),
                      adaptive=AdaptiveConfig(), use_megakernel=True)
    assert _mega_entries(cp._routes({"I": n}))
    out = cp.run(b)
    assert out.equivalent(ref, atol=0)
    assert cp.swaps >= 1
    # after the force-swap the handle still plans (and serves) fused
    assert cp.use_megakernel
    assert _mega_entries(cp._last_routes)


def test_interior_compaction_capacity_is_route_agnostic():
    """The capacities a mega span compacts to are exactly the composed
    boundary capacities (planned_capacity per stage), so truncation
    detection reads the same reference either route."""
    root, mk = flows.FLOWS["clickstream"]()
    cp = compile_plan(root, cache=ExecutableCache(), use_megakernel=True)
    masked = cp.bind_device(mk(1024, seed=5))
    caps = {n: b.capacity for n, b in masked.items()}
    stats_memo = seed_source_stats(root, caps, {})
    planned = [M.planned_capacity(st.top, stats_memo, cp.compact_slack)
               for st in cp.stages]
    routes = cp._routes(caps)
    assert _mega_entries(routes)
    got: list = []
    PL.run_stages(cp.stages, masked, cp.compact_slack, stats_memo,
                  caps=got, routes=routes)
    assert [min(c, p) for c, p in zip(got, planned)] == got
