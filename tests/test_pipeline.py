"""Compiled pipeline layer: fusion lowering, eager parity on every
evaluation flow, executable-cache behaviour, capacity bucketing."""

import math

import numpy as np
import pytest

from repro.configs import flows
from repro.core import executor
from repro.core import flow as F
from repro.core import masked
from repro.core.masked import MaskedBatch, bucket_capacity
from repro.core.operators import Hints
from repro.core.optimizer import optimize
from repro.core.physical import Ctx
from repro.core.pipeline import (CompiledPlan, ExecutableCache, compile_plan,
                                 lower)
from repro.core.record import Schema, batch_from_dict
from repro.core.reorder import commute

N = 4000


@pytest.fixture(scope="module")
def flow_data():
    out = {}
    for name, builder in flows.FLOWS.items():
        root, bindings = builder()
        b = bindings(N, seed=7)
        out[name] = (root, bindings, executor.execute(root, b))
    return out


# ---------------------------------------------------------------------------
# Parity: the acceptance bar — every evaluation flow, fused vs eager
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(flows.FLOWS))
def test_pipeline_parity(name, flow_data):
    root, bindings, ref = flow_data[name]
    cp = compile_plan(root, cache=ExecutableCache())
    assert cp.run(bindings(N, seed=7)).equivalent(ref, atol=1e-4)


@pytest.mark.parametrize("name", list(flows.FLOWS))
def test_optimized_compile_parity(name, flow_data):
    """optimize(...).compile().run(bindings): the rewritten best plan is
    multiset-equal to the eager reference on the original flow."""
    root, bindings, ref = flow_data[name]
    res = optimize(root, Ctx(dop=8), include_commutes=False)
    cp = res.compile(cache=ExecutableCache())
    assert isinstance(cp, CompiledPlan)
    assert cp.run(bindings(N, seed=7)).equivalent(ref, atol=1e-4)


# ---------------------------------------------------------------------------
# Fusion lowering
# ---------------------------------------------------------------------------
def test_map_chain_fuses_to_one_stage():
    stages = lower(flows.map_chain(6))
    assert len(stages) == 1
    assert stages[0].kind == "chain"
    assert len(stages[0].ops) == 6


def test_fusion_breaks_at_kat_boundaries():
    root, _ = flows.q15()  # map -> reduce -> match
    kinds = [s.kind for s in lower(root)]
    assert kinds == ["chain", "reduce", "match"]


def test_fused_chain_matches_per_op_masked():
    """The fused stage (no intermediate compaction) and the per-operator
    masked walk produce the same multiset."""
    root, _ = flows.textmining()
    b = {"docs": batch_from_dict({
        "doc_id": np.arange(512),
        "text_h": np.arange(512) * 977 % (2 ** 30),
        "length": 50 + np.arange(512) % 1000})}
    per_op = masked.run_flow_jit(root, b)
    fused = compile_plan(root, cache=ExecutableCache()).run(b)
    assert fused.equivalent(per_op, atol=1e-4)


def test_shared_subtree_lowered_once():
    """A subtree OBJECT consumed by two parents becomes one shared stage
    (computed once), not one inlined copy per consumer."""
    src = F.source("I", Schema.of(A=np.int64, B=np.int64), num_records=100)

    def base(ir, out):
        out.emit(ir.copy().set("A", ir.get("A") + 1))

    def left_udf(ir, out):
        out.emit(ir.copy().drop("B").set("L", ir.get("A") * 2))

    def right_udf(ir, out):
        out.emit(ir.copy().drop("A").set("R", ir.get("B") * 3))

    shared = F.map_(src, base, name="Shared")
    left = F.map_(shared, left_udf, name="Left")
    right = F.map_(shared, right_udf, name="Right")
    root = F.match(left, right, ["A"], ["B"], name="J")

    stages = lower(root)
    total_map_ops = sum(len(s.ops) for s in stages if s.kind == "chain")
    assert total_map_ops == 3  # Shared lowered once, not once per branch
    assert len(stages) == 4    # Shared, Left, Right, J

    rng = np.random.default_rng(0)
    b = {"I": batch_from_dict({"A": rng.integers(0, 8, 64),
                               "B": rng.integers(0, 8, 64)})}
    ref = executor.execute(root, b)
    got = compile_plan(root, cache=ExecutableCache()).run(b)
    assert got.equivalent(ref, atol=1e-6)


# ---------------------------------------------------------------------------
# Executable cache behaviour
# ---------------------------------------------------------------------------
def _two_table_flow(dtype=np.int64, extra_field=False):
    fields = {"k": dtype, "v": np.float64}
    if extra_field:
        fields["w"] = np.int64
    left = F.source("L", Schema.of(**fields), num_records=1000)
    right = F.source("R", Schema.of(rk=np.int64, rv=np.int64),
                     num_records=100)
    return F.match(left, right, ["k"], ["rk"], name="J",
                   hints=Hints(pk_side="right"))


def _two_table_bindings(n=256, extra_field=False, seed=0):
    rng = np.random.default_rng(seed)
    cols = {"k": rng.integers(0, 64, n), "v": rng.uniform(0, 1, n)}
    if extra_field:
        cols["w"] = rng.integers(0, 9, n)
    return {"L": batch_from_dict(cols),
            "R": batch_from_dict({"rk": np.arange(64),
                                  "rv": np.arange(64) * 7})}


def test_cache_hit_same_struct_same_schema():
    cache = ExecutableCache()
    cp = compile_plan(_two_table_flow(), cache=cache)
    cp.run(_two_table_bindings(seed=1))
    assert cache.stats().traces == 1 and cache.stats().misses == 1
    # fresh batch, same shape signature: warm executable, no retrace
    cp.run(_two_table_bindings(seed=2))
    s = cache.stats()
    assert s.traces == 1 and s.hits == 1

    # a structurally identical but separately built flow also hits
    cp2 = compile_plan(_two_table_flow(), cache=cache)
    cp2.run(_two_table_bindings(seed=3))
    s = cache.stats()
    assert s.traces == 1 and s.hits == 2


def test_cache_hit_modulo_commute():
    """Two plans equal modulo Match argument order share one executable."""
    cache = ExecutableCache()
    flow_a = _two_table_flow()
    flow_b = commute(flow_a)
    assert flow_b is not None
    ref = executor.execute(flow_a, _two_table_bindings(seed=4))

    compile_plan(flow_a, cache=cache).run(_two_table_bindings(seed=4))
    assert cache.stats().traces == 1
    got = compile_plan(flow_b, cache=cache).run(_two_table_bindings(seed=4))
    s = cache.stats()
    assert s.traces == 1 and s.hits == 1  # commuted plan reuses the warm fn
    assert got.equivalent(ref, atol=1e-6)


def test_cache_miss_on_schema_change():
    cache = ExecutableCache()
    compile_plan(_two_table_flow(), cache=cache).run(_two_table_bindings())
    # same operator names/struct shape, different source schema -> miss
    compile_plan(_two_table_flow(extra_field=True), cache=cache).run(
        _two_table_bindings(extra_field=True))
    s = cache.stats()
    assert s.misses == 2 and s.traces == 2


def test_cache_miss_on_different_udf_same_name():
    """Two same-named operators with different UDFs must NOT share an
    executable — the key fingerprints UDF code, not just tree shape."""
    cache = ExecutableCache()
    sch = Schema.of(A=np.int64, B=np.int64)

    def build(mult):
        def m(ir, out):
            out.emit(ir.copy().set("B", ir.get("B") * mult))

        return F.map_(F.source("I", sch, num_records=100), m, name="m")

    b = {"I": batch_from_dict({"A": np.array([1, 2]),
                               "B": np.array([10, 20])})}
    out2 = compile_plan(build(2), cache=cache).run(b)
    out3 = compile_plan(build(3), cache=cache).run(b)
    assert cache.stats().misses == 2 and cache.stats().traces == 2
    assert out2.sorted_tuples() == [(1, 20), (2, 40)]
    assert out3.sorted_tuples() == [(1, 30), (2, 60)]


def test_cache_miss_on_global_constant_change():
    """UDFs identical in bytecode but reading different module-global values
    must not collide (the fingerprint resolves referenced globals)."""
    cache = ExecutableCache()
    sch = Schema.of(A=np.int64)
    src_code = ("def m(ir, out):\n"
                "    out.emit(ir.copy().set('A', ir.get('A') + OFF))\n")

    def build(off):
        ns = {"OFF": off}
        exec(src_code, ns)
        return F.map_(F.source("I", sch, num_records=100), ns["m"], name="m")

    b = {"I": batch_from_dict({"A": np.array([10, 20])})}
    out1 = compile_plan(build(1), cache=cache).run(b)
    out2 = compile_plan(build(2), cache=cache).run(b)
    assert cache.stats().traces == 2
    assert out1.sorted_tuples() == [(11,), (21,)]
    assert out2.sorted_tuples() == [(12,), (22,)]


def test_cache_miss_on_nested_lambda_constant_change():
    """Constants inside nested code objects are part of the fingerprint."""
    cache = ExecutableCache()
    sch = Schema.of(A=np.int64)

    def build(which):
        def m(ir, out):
            if which == 1:
                f = lambda v: v + 1  # noqa: E731
            else:
                f = lambda v: v + 2  # noqa: E731
            out.emit(ir.copy().set("A", f(ir.get("A"))))

        return F.map_(F.source("I", sch, num_records=100), m, name="m")

    b = {"I": batch_from_dict({"A": np.array([10])})}
    out1 = compile_plan(build(1), cache=cache).run(b)
    out2 = compile_plan(build(2), cache=cache).run(b)
    assert cache.stats().traces == 2
    assert out1.sorted_tuples() == [(11,)]
    assert out2.sorted_tuples() == [(12,)]


def test_semantic_key_heterogeneous_sides_no_crash():
    """Side canonicalization must not compare raw fingerprints (bytes vs
    str) — a join of a plain-function side with an opaque-callable side
    must still compile."""
    import functools

    def m_plain(ir, out):
        out.emit(ir.copy().set("A", ir.get("A") + 1))

    def m_partial(ir, out, bump=0):
        out.emit(ir.copy().set("B2", ir.get("B2") + bump))

    from repro.core.udf import Card, UdfProperties

    rprops = UdfProperties(reads=frozenset({"B2"}), writes=frozenset({"B2"}),
                           adds=frozenset(), drops=frozenset(),
                           implicit_copy=True, card=Card.ONE,
                           filter_fields=frozenset())
    left = F.map_(F.source("L", Schema.of(A=np.int64), num_records=10),
                  m_plain, name="m")
    right = F.map_(F.source("R", Schema.of(B2=np.int64), num_records=10),
                   functools.partial(m_partial, bump=1), name="m",
                   props=rprops)
    root = F.match(left, right, ["A"], ["B2"], name="J")
    cp = compile_plan(root, cache=ExecutableCache())  # must not raise
    assert len(cp.stages) == 3


def _reduce_bindings(seed=0):
    rng = np.random.default_rng(seed)
    return {"I": batch_from_dict({"k": rng.integers(0, 8, 64),
                                  "v": rng.integers(0, 9, 64)})}


def test_cache_miss_on_reduce_closure_constant_change():
    """Two Reduce UDFs identical in bytecode but closing over different
    constants must not share a semantic fingerprint."""
    cache = ExecutableCache()
    sch = Schema.of(k=np.int64, v=np.int64)

    def build(mult):
        def agg(g, out):
            out.emit(g.keys().set("s", g.sum("v") * mult))

        return F.reduce_(F.source("I", sch, num_records=256), ["k"], agg,
                         name="R")

    b = _reduce_bindings()
    ref2 = executor.execute(build(2), b)
    out2 = compile_plan(build(2), cache=cache).run(b)
    out3 = compile_plan(build(3), cache=cache).run(b)
    assert cache.stats().misses == 2 and cache.stats().traces == 2
    assert out2.equivalent(ref2, atol=1e-6)
    assert not out3.equivalent(ref2, atol=1e-6)
    # ...while a rebuilt-from-scratch identical flow still hits
    compile_plan(build(2), cache=cache).run(b)
    assert cache.stats().hits == 1 and cache.stats().traces == 2


def test_cache_miss_on_decomposability_only_change():
    """Two Reduces that differ ONLY in decomposability (same UDF code; the
    recipe suppressed via manual props) must not share a fingerprint."""
    import dataclasses

    cache = ExecutableCache()
    sch = Schema.of(k=np.int64, v=np.int64)

    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")))

    src = F.source("I", sch, num_records=256)
    auto = F.reduce_(src, ["k"], agg, name="R")
    assert auto.props.combine is not None
    manual = F.reduce_(src, ["k"], agg, name="R",
                       props=dataclasses.replace(auto.props, combine=None))
    from repro.core.pipeline import semantic_key

    assert semantic_key(auto) != semantic_key(manual)
    b = _reduce_bindings(1)
    compile_plan(auto, cache=cache).run(b)
    compile_plan(manual, cache=cache).run(b)
    assert cache.stats().misses == 2 and cache.stats().traces == 2


def test_split_stage_lowering_cache_hits_and_misses():
    """Split plans lower to pre+merge stages with their own fingerprint:
    repeated compilation of the SAME split plan shares one warm executable;
    split and unsplit plans never collide; and a re-derived split of the
    same flow (fresh closure objects) still hits by value."""
    from repro.core.reorder import split_reduce

    cache = ExecutableCache()
    sch = Schema.of(k=np.int64, v=np.int64)

    def build():
        def agg(g, out):
            out.emit(g.keys().set("s", g.sum("v")).set("n", g.count()))

        return F.reduce_(F.source("I", sch, num_records=256), ["k"], agg,
                         name="R", hints=Hints(distinct_keys=8))

    root = build()
    split = split_reduce(root)
    stages = [s.kind for s in lower(split)]
    assert stages == ["reduce", "reduce"]  # pre stage + merge stage

    b = _reduce_bindings(2)
    ref = executor.execute(root, b)
    cp = compile_plan(split, cache=cache)
    assert cp.run(b).equivalent(ref, atol=1e-6)
    assert cache.stats().misses == 1 and cache.stats().traces == 1
    # warm run: no retrace
    cp.run(_reduce_bindings(3))
    assert cache.stats().hits == 1 and cache.stats().traces == 1
    # the unsplit plan is a different executable
    compile_plan(root, cache=cache).run(b)
    assert cache.stats().misses == 2 and cache.stats().traces == 2
    # a split re-derived from a rebuilt flow hits the same warm executable
    split2 = split_reduce(build())
    compile_plan(split2, cache=cache).run(b)
    s = cache.stats()
    assert s.hits == 2 and s.traces == 2


def test_cache_miss_on_source_num_records_change():
    """num_records feeds cardinality scaling, so it is part of identity."""
    cache = ExecutableCache()
    sch = Schema.of(A=np.int64, B=np.int64)

    def build(nrec):
        def m(ir, out):
            out.emit(ir.copy())

        return F.map_(F.source("I", sch, num_records=nrec), m, name="m")

    b = {"I": batch_from_dict({"A": np.arange(4), "B": np.arange(4)})}
    compile_plan(build(100), cache=cache).run(b)
    compile_plan(build(100_000), cache=cache).run(b)
    assert cache.stats().misses == 2 and cache.stats().traces == 2


def test_cache_miss_on_capacity_bucket_change():
    cache = ExecutableCache()
    cp = compile_plan(_two_table_flow(), cache=cache)
    cp.run(_two_table_bindings(n=256))
    cp.run(_two_table_bindings(n=257))  # crosses the 256 bucket boundary
    s = cache.stats()
    assert s.misses == 2 and s.traces == 2
    # ...but anything inside one bucket stays warm
    cp.run(_two_table_bindings(n=300))
    assert cache.stats().traces == 2


# ---------------------------------------------------------------------------
# Bounded LRU eviction: a long multi-schema (or multi-regime) serve loop
# must not grow the executable cache without bound
# ---------------------------------------------------------------------------
def _schema_variant_flow(i):
    sch = Schema.of(**{f"A{i}": np.int64})

    def m(ir, out, i=i):
        out.emit(ir.copy().set(f"A{i}", ir.get(f"A{i}") + 1))

    return F.map_(F.source(f"I{i}", sch, num_records=64), m, name=f"m{i}")


def _schema_variant_bindings(i):
    return {f"I{i}": batch_from_dict({f"A{i}": np.arange(8)})}


def test_cache_eviction_bounds_size_and_counts_coherently():
    cache = ExecutableCache(maxsize=2)
    for i in range(3):
        compile_plan(_schema_variant_flow(i), cache=cache).run(
            _schema_variant_bindings(i))
    s = cache.stats()
    assert s.size == 2 and s.evictions == 1
    # cumulative counters are NOT rewound by eviction: 3 misses, 3 traces
    assert s.misses == 3 and s.traces == 3 and s.hits == 0
    # the evicted (LRU) entry re-enters as a fresh miss + retrace...
    compile_plan(_schema_variant_flow(0), cache=cache).run(
        _schema_variant_bindings(0))
    s = cache.stats()
    assert s.misses == 4 and s.traces == 4 and s.evictions == 2
    # ...while the most-recently-used entry stayed warm
    compile_plan(_schema_variant_flow(2), cache=cache).run(
        _schema_variant_bindings(2))
    s = cache.stats()
    assert s.hits == 1 and s.traces == 4
    assert s.size == 2


def test_cache_lru_order_tracks_use():
    cache = ExecutableCache(maxsize=2)
    cp0 = compile_plan(_schema_variant_flow(0), cache=cache)
    cp1 = compile_plan(_schema_variant_flow(1), cache=cache)
    cp0.run(_schema_variant_bindings(0))
    cp1.run(_schema_variant_bindings(1))
    cp0.run(_schema_variant_bindings(0))  # 0 is now most recently used
    compile_plan(_schema_variant_flow(2), cache=cache).run(
        _schema_variant_bindings(2))      # evicts 1, not 0
    traces = cache.stats().traces
    cp0.run(_schema_variant_bindings(0))
    assert cache.stats().traces == traces  # 0 still warm
    cp1.run(_schema_variant_bindings(1))
    assert cache.stats().traces == traces + 1  # 1 was the victim


def test_cache_resize_evicts_and_clear_resets():
    cache = ExecutableCache(maxsize=4)
    for i in range(3):
        compile_plan(_schema_variant_flow(i), cache=cache).run(
            _schema_variant_bindings(i))
    cache.resize(1)
    s = cache.stats()
    assert s.size == 1 and s.evictions == 2
    cache.clear()
    s = cache.stats()
    assert (s.size, s.hits, s.misses, s.traces, s.evictions) == (0,) * 5


def test_cache_capacity_env_tunable(monkeypatch):
    from repro.core.pipeline import EXEC_CACHE_CAP_ENV
    monkeypatch.setenv(EXEC_CACHE_CAP_ENV, "7")
    assert ExecutableCache().maxsize == 7
    monkeypatch.setenv(EXEC_CACHE_CAP_ENV, "not-a-number")
    assert ExecutableCache().maxsize == 256  # default survives bad input
    monkeypatch.setenv(EXEC_CACHE_CAP_ENV, "0")
    assert ExecutableCache().maxsize == 1  # floor: a cache must cache
    monkeypatch.delenv(EXEC_CACHE_CAP_ENV)
    assert ExecutableCache().maxsize == 256
    assert ExecutableCache(maxsize=3).maxsize == 3  # explicit arg wins


# ---------------------------------------------------------------------------
# Capacity bucketing
# ---------------------------------------------------------------------------
def test_bucket_capacity_ladder():
    assert bucket_capacity(1) == 8
    assert bucket_capacity(8) == 8
    assert bucket_capacity(9) == 16
    assert bucket_capacity(250) == 256
    assert bucket_capacity(257) == 512
    for x in (1, 5, 8, 17, 100, 4096, 99999):
        b = bucket_capacity(x)
        assert b >= x and b % 8 == 0
        # geometric: half the bucket would not fit (or we're at the floor)
        assert b == 8 or b // 2 < math.ceil(x)


def test_no_truncation_when_batch_exceeds_nominal_scale():
    """Compaction must scale its cardinality estimates up when the bound
    batch is larger than Source.num_records — otherwise valid rows are
    silently dropped (found via map-chain benchmarking)."""
    root = flows.map_chain(4)  # source declares num_records=1000
    n = 8000
    rng = np.random.default_rng(3)
    b = {"I": batch_from_dict({f"f{i}": rng.integers(0, 1000, n)
                               for i in range(4)})}
    ref = executor.execute(root, b)
    assert ref.capacity == n
    assert masked.run_flow_jit(root, b).equivalent(ref)
    assert compile_plan(root, cache=ExecutableCache()).run(b).equivalent(ref)


def test_chain_traced_capacities_logarithmic(monkeypatch):
    """A chain of n selective maps must compact through O(log n) distinct
    capacities, not O(n): one capacity per geometric bucket, so the jit
    cache sees a bounded shape vocabulary."""
    n_ops, n_rows, sel = 24, 4096, 0.8
    src = F.source("I", Schema.of(x=np.int64), num_records=n_rows)
    node = src
    for i in range(n_ops):
        def udf(ir, out, i=i):
            out.emit(ir.copy(), where=(ir.get("x") % (i + 2)) != 0)

        udf.__name__ = f"f{i}"
        node = F.map_(node, udf, name=f"f{i}", hints=Hints(selectivity=sel))

    caps: list[int] = []
    orig = MaskedBatch.compact

    def spy(self, capacity):
        caps.append(capacity)
        return orig(self, capacity)

    monkeypatch.setattr(MaskedBatch, "compact", spy)
    rng = np.random.default_rng(0)
    b = {"I": batch_from_dict({"x": rng.integers(0, 2 ** 31, n_rows)})}
    mb = {"I": MaskedBatch.from_record_batch(b["I"], n_rows)}
    masked.execute_masked(node, mb)  # per-op walk: worst case for compaction

    assert caps, "chain never compacted"
    distinct = len(set(caps))
    bound = math.ceil(math.log2(n_rows)) + 1
    assert distinct <= bound, (distinct, sorted(set(caps)))
    assert distinct < n_ops / 2  # clearly sub-linear in chain length
