"""Order-elision correctness (DESIGN.md §8) + composite-key code regression.

Every test uses integer columns only, so "same result" means BIT-identical
row multisets (`sorted_tuples`, no tolerance): elision must be a pure
no-op on values — with and without `use_order`, against the eager
reference, across declared source orders, gappy (filtered) inputs, and
Reduce-after-Reduce chains.

Also pins the `_exec_match_pk` composite-key fix: the old
`c * 2^31 + v` pairing collided/overflowed for key values >= 2^31; the
dense joint-rank codes must join large composite keys exactly.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import executor, flow as F
from repro.core.masked import run_flow_jit
from repro.core.operators import Hints
from repro.core.pipeline import ExecutableCache, compile_plan
from repro.core.record import Schema, batch_from_dict


def _rows(batch):
    """Valid rows, fields aligned BY NAME (schema order is not semantic),
    bit-exact."""
    b = batch.to_numpy().compact()
    fields = sorted(b.fields)
    return sorted(zip(*[np.asarray(b.columns[f]).tolist() for f in fields]))


def _ident(got, ref):
    assert _rows(got) == _rows(ref)


def _sorted_source_flow(sorted_on=("k",)):
    src = F.source("S", Schema.of(k=np.int64, v=np.int64, w=np.int64),
                   num_records=400, sorted_on=sorted_on)

    def thresh(ir, out):
        out.emit(ir.copy(), where=ir.get("v") % 3 != 0)

    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")).set("m", g.max("w"))
                 .set("lo", g.min("w")).set("c", g.count()))

    f = F.map_(src, thresh, name="Thresh")
    return F.reduce_(f, ["k"], agg, name="Agg",
                     hints=Hints(distinct_keys=24))


def _sorted_bindings(seed, n=300):
    rng = np.random.default_rng(seed)
    return {"S": batch_from_dict({
        "k": np.sort(rng.integers(0, 24, n)),
        "v": rng.integers(-50, 50, n),
        "w": rng.integers(-1000, 1000, n)})}


@pytest.mark.parametrize("seed", range(6))
def test_sorted_source_reduce_elision_bit_identical(seed):
    """Filter (opens validity gaps) + Reduce over a declared-sorted source:
    the elided (gappy, sort-free) path equals the sorted path equals eager,
    bit for bit."""
    root = _sorted_source_flow()
    b = _sorted_bindings(seed)
    ref = executor.execute(root, b)
    _ident(run_flow_jit(root, b, use_order=True), ref)
    _ident(run_flow_jit(root, b, use_order=False), ref)
    cache = ExecutableCache()
    _ident(compile_plan(root, cache=cache, use_order=True).run(b), ref)
    _ident(compile_plan(root, cache=cache, use_order=False).run(b), ref)


@pytest.mark.parametrize("seed", range(4))
def test_reduce_after_reduce_same_key_elision(seed):
    """The second Reduce's sort elides because the first one's output is
    key-ordered — no declared source order needed (intra-flow propagation)."""
    src = F.source("S", Schema.of(k=np.int64, v=np.int64), num_records=400)

    def keep(g, out):
        out.emit_records(where=g.any(g.get("v") > 0))

    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")).set("c", g.count()))

    r1 = F.reduce_(src, ["k"], keep, name="Keep",
                   hints=Hints(distinct_keys=16))
    root = F.reduce_(r1, ["k"], agg, name="Agg",
                     hints=Hints(distinct_keys=16))
    rng = np.random.default_rng(seed)
    b = {"S": batch_from_dict({"k": rng.integers(0, 16, 200),
                               "v": rng.integers(-9, 9, 200)})}
    ref = executor.execute(root, b)
    _ident(run_flow_jit(root, b, use_order=True), ref)
    _ident(run_flow_jit(root, b, use_order=False), ref)


@pytest.mark.parametrize("seed", range(4))
def test_pk_probe_elision_with_gappy_sorted_side(seed):
    """PK-side elision probes the sorted side in place, including when a
    pushed-down filter left validity gaps in it (cummax back-fill path)."""
    rng = np.random.default_rng(seed)
    nd = 32
    fact = F.source("fact", Schema.of(fk=np.int64, x=np.int64),
                    num_records=400)
    dim = F.source("dim", Schema.of(dk=np.int64, y=np.int64),
                   num_records=nd, sorted_on=("dk",))

    def dimfilter(ir, out):
        out.emit(ir.copy(), where=ir.get("y") % 2 == 0)

    fdim = F.map_(dim, dimfilter, name="DimFilter")
    root = F.match(fact, fdim, ["fk"], ["dk"], name="J",
                   hints=Hints(pk_side="right"))
    b = {"fact": batch_from_dict({"fk": rng.integers(0, nd, 200),
                                  "x": rng.integers(-99, 99, 200)}),
         "dim": batch_from_dict({"dk": np.arange(nd),
                                 "y": rng.integers(0, 100, nd)})}
    ref = executor.execute(root, b)
    _ident(run_flow_jit(root, b, use_order=True), ref)
    _ident(run_flow_jit(root, b, use_order=False), ref)


def test_cache_misses_on_order_assumption_change():
    """Two flows identical except for the declared source order, and one
    flow compiled with/without `use_order`, must NOT share executables —
    different elisions, different traces; a MISS, never wrong reuse."""
    cache = ExecutableCache()
    b = _sorted_bindings(0)

    sorted_flow = _sorted_source_flow(sorted_on=("k",))
    unsorted_flow = _sorted_source_flow(sorted_on=None)
    cp1 = compile_plan(sorted_flow, cache=cache)
    cp1.run(b)
    assert cache.stats().misses == 1 and cache.stats().traces == 1

    cp2 = compile_plan(unsorted_flow, cache=cache)
    cp2.run(b)
    assert cache.stats().misses == 2 and cache.stats().traces == 2

    # same flow, elision disabled: its own executable
    cp3 = compile_plan(sorted_flow, cache=cache, use_order=False)
    cp3.run(b)
    assert cache.stats().misses == 3 and cache.stats().traces == 3

    # warm calls: pure hits, zero retraces on every variant
    cp1.run(_sorted_bindings(1))
    cp2.run(_sorted_bindings(2))
    cp3.run(_sorted_bindings(3))
    s = cache.stats()
    assert s.hits == 3 and s.traces == 3


def test_device_serving_respects_runtime_order_signature():
    """`run_device` keys the executable on the batches' actual order
    metadata: stripping the order is a cache MISS (new trace), not a reuse
    of the elided executable."""
    from repro.core.masked import MaskedBatch

    cache = ExecutableCache()
    root = _sorted_source_flow()
    cp = compile_plan(root, cache=cache)
    b = _sorted_bindings(0)
    ref = executor.execute(root, b)
    staged = cp.bind_device(b)
    _ident(cp.run_device(staged).to_record_batch(), ref)
    n_exec = cache.stats().misses

    stripped = {"S": MaskedBatch(staged["S"].columns, staged["S"].valid, ())}
    # source declares sorted_on, so run_device re-attaches the order — the
    # declared order wins and the warm executable is reused
    _ident(cp.run_device(stripped).to_record_batch(), ref)
    assert cache.stats().misses == n_exec


LARGE = np.int64(2**31)


@pytest.mark.parametrize("seed", range(4))
def test_match_composite_codes_large_keys(seed):
    """Composite-key regression: values straddling 2^31 collided under the
    old `c * 2^31 + v` pairing (e.g. (c, v) and (c+1, v - 2^31) coded
    equal, and c >= 2^31 overflowed).  Joint-rank codes must join exactly.

    Key values stay int32-representable (jax canonicalizes int64 inputs to
    int32 under disabled x64); what must NOT overflow is the CODE built
    from two columns."""
    rng = np.random.default_rng(seed)
    hi = np.int64(2**31 - 3)
    base = np.array([0, 1, 2, hi - 2, hi - 1, hi], dtype=np.int64)
    nl = 24
    lk1 = rng.choice(base, nl)
    lk2 = rng.choice(base, nl)
    left = F.source("L", Schema.of(a=np.int64, b=np.int64, x=np.int64),
                    num_records=nl)
    # PK side: every distinct (a, b) pair once
    pairs = [(p, q) for p in base for q in base]
    rk1 = np.array([p for p, _ in pairs], dtype=np.int64)
    rk2 = np.array([q for _, q in pairs], dtype=np.int64)
    right = F.source("R", Schema.of(c=np.int64, d=np.int64, y=np.int64),
                     num_records=len(pairs))
    root = F.match(left, right, ["a", "b"], ["c", "d"], name="JJ",
                   hints=Hints(pk_side="right"))
    b = {"L": batch_from_dict({"a": lk1, "b": lk2,
                               "x": rng.integers(0, 100, nl)}),
         "R": batch_from_dict({"c": rk1, "d": rk2,
                               "y": rng.integers(0, 100, len(pairs))})}
    ref = executor.execute(root, b)
    assert ref.num_valid() == nl  # every left row finds its PK pair
    _ident(run_flow_jit(root, b), ref)


def test_pk_probe_elision_minimal_key_after_leading_gap():
    """Review regression: a valid PK row holding the dtype-minimal key,
    preceded by an invalid slot, must still match (the leading back-fill
    run can alias the minimal code; pos is clamped past it)."""
    import jax.numpy as jnp

    from repro.core.masked import MaskedBatch, _exec_match_pk

    lo = int(jnp.iinfo(jnp.int32).min)
    left = F.source("L", Schema.of(a=np.int64, x=np.int64), num_records=8)
    right = F.source("R", Schema.of(b=np.int64, y=np.int64), num_records=8,
                     sorted_on=("b",))
    root = F.match(left, right, ["a"], ["b"], name="JM",
                   hints=Hints(pk_side="right"))
    lb = MaskedBatch({"a": jnp.asarray([lo, 0, 7, lo]),
                      "x": jnp.asarray([1, 2, 3, 4])},
                     jnp.asarray([True, True, True, True]))
    rb = MaskedBatch({"b": jnp.asarray([99, lo, 0, 5]),
                      "y": jnp.asarray([-1, 10, 20, 30])},
                     jnp.asarray([False, True, True, True]),  # leading gap
                     order=("b",))
    out = _exec_match_pk(root, lb, rb, use_order=True)
    ref = _exec_match_pk(root, lb, rb, use_order=False)
    _ident(out.to_record_batch(), ref.to_record_batch())
    got = sorted(np.asarray(out.columns["y"])[np.asarray(out.valid)].tolist())
    assert got == [10, 10, 20], "minimal-key rows must match through the gap"


def test_cogroup_permuted_order_cover_not_elided():
    """Review regression: a side sorted on a PERMUTATION of the cogroup key
    must not take the valids-first fast path (union segment ids are not
    monotone over it, so per-side segments would not be contiguous)."""
    rng = np.random.default_rng(0)
    n = 16
    a = rng.integers(0, 3, n)
    bcol = rng.integers(0, 3, n)
    order = np.lexsort((a, bcol))  # sorted on (b, a): a PERMUTED cover
    left = F.source("L", Schema.of(a=np.int64, b=np.int64, v=np.int64),
                    num_records=n, sorted_on=("b", "a"))
    right = F.source("R", Schema.of(c=np.int64, d=np.int64, w=np.int64),
                     num_records=8)

    def udf(gl, gr, out):
        out.emit(gl.keys().set("sv", gl.sum("v") + gr.sum("w"))
                 .set("cnt", gl.count() - gr.count()))

    root = F.cogroup(left, right, ["a", "b"], ["c", "d"], udf, name="CG")
    b = {"L": batch_from_dict({"a": a[order], "b": bcol[order],
                               "v": rng.integers(-9, 9, n)}),
         "R": batch_from_dict({"c": rng.integers(0, 3, 8),
                               "d": rng.integers(0, 3, 8),
                               "w": rng.integers(-9, 9, 8)})}
    ref = executor.execute(root, b)
    _ident(run_flow_jit(root, b, use_order=True), ref)
    _ident(run_flow_jit(root, b, use_order=False), ref)
