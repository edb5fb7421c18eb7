"""Cross-tenant common-subplan sharing (DESIGN.md §13, serving side).

Two tenants in DIFFERENT plan groups whose flows open with the same
source → map-chain prefix — detected through the commute-invariant
`semantic_key` of the prefix subtree — execute one fused upstream stage per
batch, feeding each tenant's own suffix plan.  These tests cover the
detection, result parity, the statistics contract (fused-prefix
observations are attributed ONCE to the share group's store, never
per-consuming tenant), drift isolation (one sharer drifting re-links under
its new regime and leaves the group; the other stays warm), and the
`REPRO_SUBPLAN_SHARING` kill switch.
"""

import numpy as np
import pytest

from repro.core import executor, flow as F
from repro.core.operators import Hints
from repro.core.record import RecordBatch, Schema
from repro.serve.dataflow import (DataflowEngine, ServeConfig,
                                  coalesce_flow, shared_prefix)

SCH = Schema.of(a=np.int64, b=np.int64, c=np.int64)


def _keep(r, out):
    out.emit(r.copy(), where=r.get("c") < 80)


def _inc(r, out):
    out.emit(r.copy().set("c", r.get("c") + 1))


def _agg_b(g, out):
    out.emit(g.keys().set("s", g.sum("b")))


def _agg_c(g, out):
    out.emit(g.keys().set("s", g.sum("c")))


def _flow(which: int, n: int = 128):
    """Shared prefix (keep → inc over source `s`), per-tenant suffix."""
    src = F.source("s", SCH, num_records=n)
    pre = F.map_(F.map_(src, _keep, name="keep",
                        hints=Hints(selectivity=0.8)), _inc, name="inc")
    if which == 0:
        return F.reduce_(pre, ["a"], _agg_b, name="aggb",
                         hints=Hints(distinct_keys=10))
    return F.reduce_(pre, ["b"], _agg_c, name="aggc",
                     hints=Hints(distinct_keys=6))


def _data(seed: int, n: int = 128, c_hi: int = 100) -> RecordBatch:
    rng = np.random.default_rng(seed)
    return RecordBatch(
        {"a": rng.integers(0, 10, n).astype(np.int64),
         "b": rng.integers(0, 6, n).astype(np.int64),
         "c": rng.integers(0, c_hi, n).astype(np.int64)})


def _rows(batch):
    b = batch.to_numpy().compact()
    fields = sorted(b.fields)
    return sorted(zip(*[np.asarray(b.columns[f]).tolist() for f in fields]))


# -- prefix detection --------------------------------------------------------
def test_shared_prefix_detection():
    sp = shared_prefix(_flow(0))
    assert sp is not None and sp.source == "s"
    assert set(sp.prefix.op_names()) == {"s", "keep", "inc"}
    # the suffix replaces the prefix with a stub Source of its out-schema,
    # under the ORIGINAL source's name (so serve-time rebinding is a dict put)
    assert set(sp.suffix.op_names()) == {"aggb", "s"}
    assert sp.suffix.children[0].out_schema == sp.prefix.out_schema
    # a bare map chain leaves no per-tenant suffix: nothing to share
    bare = F.map_(F.source("s", SCH), _keep)
    assert shared_prefix(bare) is None
    # a flow opening with a non-Map stage has no shareable prefix
    red = F.reduce_(F.source("s", SCH), ["a"], _agg_b,
                    hints=Hints(distinct_keys=10))

    def inc_s(r, out):
        out.emit(r.copy().set("s", r.get("s") + 1))

    assert shared_prefix(F.map_(red, inc_s)) is None


def test_shared_prefix_key_is_commute_invariant_and_regime_sensitive():
    from repro.core.pipeline import semantic_key

    k0 = semantic_key(shared_prefix(_flow(0)).prefix)
    k1 = semantic_key(shared_prefix(_flow(1)).prefix)
    assert k0 == k1    # same prefix, different suffixes
    # different hint regime on a prefix stage -> different share key
    src = F.source("s", SCH, num_records=128)
    other = F.reduce_(
        F.map_(F.map_(src, _keep, name="keep",
                      hints=Hints(selectivity=0.1)), _inc, name="inc"),
        ["a"], _agg_b, name="aggb", hints=Hints(distinct_keys=10))
    assert semantic_key(shared_prefix(other).prefix) != k0


# -- serving: sharing fires, results stay correct ----------------------------
def _engine(**kw) -> DataflowEngine:
    kw = {"async_swap": False, "probe_every": 1000, "share_subplans": True,
          **kw}
    eng = DataflowEngine(ServeConfig(**kw))
    eng.register("ta", _flow(0), seed_stats=False)
    eng.register("tb", _flow(1), seed_stats=False)
    return eng


def test_shared_serving_parity_and_counters():
    eng = _engine()
    assert eng.tenant_stats("ta")["share_group_size"] == 2
    data = _data(7)
    reqs = []
    for _ in range(4):
        reqs.append((eng.submit("ta", {"s": data}),
                     eng.submit("tb", {"s": data})))
        eng.drain()
    st = eng.stats()
    # round 1 probes both tenants solo; rounds 2-4 share the fused prefix
    assert st["shared_prefix_batches"] == 3, st
    assert st["shared_requests"] == 6, st
    assert st["share_groups"] == 1
    ref_a = _rows(executor.execute(_flow(0), {"s": data}))
    ref_b = _rows(executor.execute(_flow(1), {"s": data}))
    for ra, rb in reqs:
        assert _rows(ra.result(10)) == ref_a
        assert _rows(rb.result(10)) == ref_b


def test_shared_prefix_failure_reaches_the_request(monkeypatch):
    """A failure of the fused prefix executable is the sharing requests'
    error; it is never hidden by re-serving them solo."""
    eng = _engine()
    data = _data(7)
    ra, rb = eng.submit("ta", {"s": data}), eng.submit("tb", {"s": data})
    eng.drain()   # first requests probe solo
    ra.result(10), rb.result(10)
    (sg,) = eng._prefixes.values()

    def broken(*a, **k):
        raise RuntimeError("prefix executable failed")

    monkeypatch.setattr(sg.plan, "run_device_observed", broken)
    solo = eng.stats()["solo_requests"]
    ra, rb = eng.submit("ta", {"s": data}), eng.submit("tb", {"s": data})
    eng.drain()
    for r in (ra, rb):
        with pytest.raises(RuntimeError, match="prefix executable failed"):
            r.result(10)
    assert eng.stats()["solo_requests"] == solo


def test_sharing_requires_identical_source_batch():
    eng = _engine()
    da, db = _data(1), _data(2)
    for _ in range(3):
        ra = eng.submit("ta", {"s": da})
        rb = eng.submit("tb", {"s": db})   # different batch: no pairing
        eng.drain()
        ra.result(10), rb.result(10)
    assert eng.stats()["shared_prefix_batches"] == 0


def test_sharing_requires_distinct_plan_groups():
    # two tenants with THE SAME flow live in one plan group — coalescing
    # already covers them; the shared-prefix path must not hijack the queue
    cfg = ServeConfig(async_swap=False, probe_every=1000, share_subplans=True)
    eng = DataflowEngine(cfg)
    eng.register("ta", _flow(0), seed_stats=False)
    eng.register("tb", _flow(0), seed_stats=False)
    data = _data(3)
    for _ in range(3):
        ra, rb = eng.submit("ta", {"s": data}), eng.submit("tb", {"s": data})
        eng.drain()
        ra.result(10), rb.result(10)
    st = eng.stats()
    assert st["shared_prefix_batches"] == 0
    assert st["coalesced_requests"] >= 4


# -- the statistics contract -------------------------------------------------
def test_shared_stage_observed_once_and_tenant_stores_disjoint():
    eng = _engine()
    data = _data(11)
    for _ in range(5):
        eng.submit("ta", {"s": data})
        eng.submit("tb", {"s": data})
        eng.drain()
    ta, tb = eng._tenants["ta"], eng._tenants["tb"]
    sg = eng._prefixes[ta.prefix_key]
    # fused-prefix obs land in the share store: one tick per fused batch,
    # NOT one per consuming tenant
    assert sg.store.clock == eng.stats()["shared_prefix_batches"] == 4
    # each tenant's store: 1 solo probe + its 4 shared suffix runs
    assert ta.store.clock == tb.store.clock == 5
    # the prefix ops were observed into a tenant store only by its OWN solo
    # probe — shared batches never touched them
    for t in (ta, tb):
        pre_keys = [k for k in t.store._stages
                    if set(k) & {"keep", "inc"}]
        assert pre_keys, "solo probe should observe the prefix stage"
        assert all(t.store._stages[k].batches == 1 for k in pre_keys), \
            {k: t.store._stages[k].batches for k in pre_keys}
    # suffix stages accumulated per tenant, disjoint op names
    def has(store, op):
        return any(any(op in name for name in k) for k in store._stages)

    assert has(ta.store, "aggb") and not has(ta.store, "aggc")
    assert has(tb.store, "aggc") and not has(tb.store, "aggb")


# -- drift isolation ---------------------------------------------------------
def test_drifting_sharer_leaves_group_and_peer_stays_warm():
    eng = _engine(probe_every=2, drift_high=0.4, drift_low=0.2, patience=1,
                  min_drift_rows=0.0)
    warm = _data(21)              # matches the registered hint regime
    drifted = _data(22, c_hi=400)  # filter passes ~0.2 vs the hinted 0.8
    key0 = eng._tenants["ta"].prefix_key
    for i in range(14):
        eng.submit("ta", {"s": drifted})
        eng.submit("tb", {"s": warm})
        eng.drain()
    ta, tb = eng._tenants["ta"], eng._tenants["tb"]
    assert ta.swaps >= 1, eng.tenant_stats("ta")
    assert tb.swaps == 0, eng.tenant_stats("tb")
    # the drifter re-linked under its new regime's prefix key...
    assert ta.prefix_key != key0
    # ...and left the old share group; the peer keeps it (now solo-sized)
    assert tb.prefix_key == key0
    assert eng._prefixes[key0].members == {"tb"}
    # correctness throughout: spot-check the final round
    ra = eng.submit("ta", {"s": drifted})
    rb = eng.submit("tb", {"s": warm})
    eng.drain()
    assert _rows(ra.result(10)) == _rows(
        executor.execute(_flow(0), {"s": drifted}))
    assert _rows(rb.result(10)) == _rows(
        executor.execute(_flow(1), {"s": warm}))


# -- kill switch and coalescing gates ----------------------------------------
def test_share_subplans_kill_switch():
    cfg = ServeConfig(async_swap=False, probe_every=1000,
                      share_subplans=False)
    eng = DataflowEngine(cfg)
    eng.register("ta", _flow(0), seed_stats=False)
    eng.register("tb", _flow(1), seed_stats=False)
    data = _data(5)
    for _ in range(3):
        eng.submit("ta", {"s": data})
        eng.submit("tb", {"s": data})
        eng.drain()
    st = eng.stats()
    assert st["share_groups"] == 0 and st["shared_requests"] == 0


def test_subplan_sharing_env_default(monkeypatch):
    monkeypatch.setenv("REPRO_SUBPLAN_SHARING", "0")
    assert ServeConfig().share_subplans is False
    monkeypatch.setenv("REPRO_SUBPLAN_SHARING", "1")
    assert ServeConfig().share_subplans is True


def test_coalesce_flow_new_operator_gates():
    # anti joins coalesce with the anti flag intact (tag keys on both sides
    # keep the existence test per-request)
    f_anti = F.match(F.source("s", SCH, num_records=64),
                     F.source("r", Schema.of(k=np.int64), num_records=8),
                     ["a"], ["k"], anti=True, name="anti")
    cf = coalesce_flow(f_anti, 4)
    assert cf is not None
    assert any(getattr(n, "anti", False) for n in cf.root.iter_nodes())
    # a global top-k cannot be keyed per request: not coalescable
    f_lim = F.limit_(F.map_(F.source("s", SCH, num_records=64), _inc),
                     k=5, key=("a",))
    assert coalesce_flow(f_lim, 4) is None
