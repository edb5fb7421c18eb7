"""The masked Reduce's tracing: the `segment` device scope and the
trace-time counters `reduce.sorted`, `reduce.sort`, `reduce.group_filter`,
`reduce.segments_sized` and `segment.scan` (`repro.obs`); and the group
arrays of a row-aligned Reduce sized from its group estimate, with the
full count as the exact fallback."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import executor
from repro.core import flow as F
from repro.core import masked as M
from repro.core.operators import Hints
from repro.core.record import RecordBatch, Schema

OP_NAME = re.compile(r'op_name="([^"]*)"')
ROWS = 4096     # above JitSegmentOps._SCAN_MIN_ROWS: aggregates scan


def _source(sorted_on=("k",)):
    return F.source("t", Schema.of(k=np.int64, v=np.int64, x=np.float64),
                    num_records=ROWS, sorted_on=sorted_on)


def _batch(rows: int = ROWS, order=("k",)):
    rng = np.random.default_rng(3)
    k = np.sort(rng.integers(0, rows // 4, rows))
    return M.MaskedBatch({"k": jnp.asarray(k),
                          "v": jnp.asarray(rng.integers(0, 9, rows)),
                          "x": jnp.asarray(rng.random(rows))},
                         jnp.ones(rows, bool), order)


def _counts(node, batch, segment_cap=None) -> dict:
    """Counters of one trace of `node`'s Reduce over `batch`."""
    obs.reset()
    obs.enable()
    try:
        jax.eval_shape(lambda b: M._exec_reduce(node, b,
                                                segment_cap=segment_cap),
                       batch)
    finally:
        obs.disable()
        counts = obs.snapshot()["counts"]
        obs.reset()
    return counts


def _keep_mixed(g, out):
    out.emit_records(where=g.min(g.get("v")) != g.max(g.get("v")))


def _keep_all(g, out):
    out.emit_records()


def _totals(g, out):
    out.emit(g.keys().set("n", g.count()).set("s", g.sum(g.get("v")))
             .set("m", g.max(g.get("x"))))


@pytest.mark.parametrize("order, sorted_, sort", [
    (("k",), 1, 0),          # the input order covers the key: no sort
    ((), 0, 1),              # no known order: the Reduce sorts
])
def test_reduce_counts_whether_it_sorted(order, sorted_, sort):
    node = F.reduce_(_source(order or None), ["k"], _totals)
    counts = _counts(node, _batch(order=order))
    assert counts.get("reduce.sorted", 0) == sorted_
    assert counts.get("reduce.sort", 0) == sort


@pytest.mark.parametrize("udf, filters", [(_keep_mixed, 1), (_keep_all, 0),
                                          (_totals, 0)])
def test_group_filter_counted_per_masked_passthrough(udf, filters):
    counts = _counts(F.reduce_(_source(), ["k"], udf), _batch())
    assert counts.get("reduce.group_filter", 0) == filters


@pytest.mark.parametrize("rows, scans", [
    (ROWS, 3),   # min and max of v, max of x; count and the integer sum
                 # difference a prefix sum instead
    (1024, 0),   # below the scan's minimum: segment_* scatters
])
def test_segment_scan_counted_per_traced_scan(rows, scans):
    def udf(g, out):
        out.emit_records(where=(g.min(g.get("v")) != g.max(g.get("v")))
                         & (g.max(g.get("x")) > 0.5) & (g.count() > 1)
                         & (g.sum(g.get("v")) > 3))

    counts = _counts(F.reduce_(_source(), ["k"], udf), _batch(rows))
    assert counts.get("segment.scan", 0) == scans


def test_counters_off_unless_enabled():
    obs.reset()
    node = F.reduce_(_source(), ["k"], _keep_mixed)
    jax.eval_shape(lambda b: M._exec_reduce(node, b), _batch())
    assert obs.snapshot()["counts"] == {}


@pytest.mark.parametrize("order", [("k",), ()])
def test_segment_scope_names_the_group_work_not_the_sort(order):
    """The segmentation, the UDF's aggregates and the group-mask broadcast
    run under `segment`; a sorting Reduce's sort stays under `sort`."""
    node = F.reduce_(_source(order or None), ["k"], _keep_mixed)
    hlo = jax.jit(lambda b: M._exec_reduce(node, b)).lower(
        _batch(order=order)).compile().as_text()
    paths = [m.group(1).split("/") for m in OP_NAME.finditer(hlo)]
    assert any("segment" in p for p in paths)
    sorts = [OP_NAME.search(line).group(1).split("/")
             for line in hlo.splitlines()
             if " sort(" in line and OP_NAME.search(line)]
    assert len(sorts) == (0 if order else 1)
    for p in sorts:
        assert "sort" in p and "segment" not in p


# --- segments sized from the group estimate --------------------------------

def _gappy_batch(order=("k",)):
    """`_batch` with a fifth of its slots invalid; its rows shuffled when
    `order` is empty, so the Reduce sorts."""
    b = _batch(order=order)
    rng = np.random.default_rng(5)
    valid = jnp.asarray(rng.random(ROWS) < 0.8)
    cols = b.columns
    if not order:
        perm = jnp.asarray(rng.permutation(ROWS))
        cols = {k: v[perm] for k, v in cols.items()}
    return M.MaskedBatch(cols, valid, order)


def _groups(b) -> int:
    return len(np.unique(np.asarray(b.columns["k"])[np.asarray(b.valid)]))


@pytest.mark.parametrize("order", [("k",), ()])
@pytest.mark.parametrize("segment_cap", [
    1024,       # the live groups fit: the sized branch
    256,        # 4x under the groups: the fallback
    8,          # the floor, far under: the fallback
])
def test_sized_segments_match_the_full_count(order, segment_cap):
    """A group filter with a segment capacity below its input capacity
    returns the capacity-sized path's output bit for bit, whether the live
    groups fit the capacity or take the fallback."""
    node = F.reduce_(_source(order or None), ["k"], _keep_mixed)
    b = _gappy_batch(order)
    assert segment_cap < ROWS and (segment_cap >= _groups(b)) \
        == (segment_cap == 1024)
    want = jax.jit(lambda b: M._exec_reduce(node, b))(b)
    got = jax.jit(lambda b: M._exec_reduce(
        node, b, segment_cap=segment_cap))(b)
    assert got.order == want.order
    assert set(got.columns) == set(want.columns)
    for c in want.columns:
        np.testing.assert_array_equal(got.columns[c], want.columns[c])
    np.testing.assert_array_equal(got.valid, want.valid)


@pytest.mark.parametrize("under", [1, 4, 64])
def test_pipeline_sized_group_filter_matches_the_eager_executor(under):
    """Through the compiled pipeline, the group filter's segments come from
    its `distinct_keys` hint: right, 4x under and 64x under the live
    groups.  Under-estimates take the fallback and the answer is still
    the eager executor's."""
    from repro.core.optimizer import optimize

    b = _gappy_batch(())
    node = F.reduce_(_source(None), ["k"], _keep_mixed, name="Mixed",
                     hints=Hints(distinct_keys=_groups(b) // under))
    assert (M.segment_capacity(node, {}) >= _groups(b)) == (under == 1)
    rb = RecordBatch({k: np.asarray(v) for k, v in b.columns.items()},
                     np.asarray(b.valid))
    obs.reset()
    obs.enable()
    try:
        got = optimize(node).compile().run({"t": rb})
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    assert got.equivalent(executor.execute(node, {"t": rb}))
    assert counts.get("reduce.segments_sized", 0) == 1


@pytest.mark.parametrize("udf, segment_cap, sized", [
    (_keep_mixed, 1024, 1),     # a group filter below its capacity
    (_keep_all, 1024, 1),       # a passthrough below its capacity
    (_keep_mixed, ROWS, 0),     # a capacity that saves nothing
    (_keep_mixed, None, 0),     # no capacity given
    (_totals, 1024, 0),         # per-group: its segments are its output
])
def test_segments_sized_counted_per_sized_reduce(udf, segment_cap, sized):
    counts = _counts(F.reduce_(_source(), ["k"], udf), _batch(),
                     segment_cap)
    assert counts.get("reduce.segments_sized", 0) == sized


@pytest.mark.parametrize("segment_cap", [None, 1024])
def test_sized_reduce_counts_once_per_reduce(segment_cap):
    """The sized branch and the fallback both trace the UDF; the counters
    still count once per Reduce."""
    def udf(g, out):
        out.emit_records(where=(g.min(g.get("v")) != g.max(g.get("v")))
                         & (g.max(g.get("x")) > 0.5))

    counts = _counts(F.reduce_(_source(), ["k"], udf), _batch(), segment_cap)
    assert counts.get("reduce.group_filter", 0) == 1
    assert counts.get("segment.scan", 0) == 3
    assert counts.get("reduce.sorted", 0) == 1
    assert counts.get("select.search", 0) \
        + counts.get("select.blocked", 0) == 1


@pytest.mark.parametrize("udf, hint, cap", [
    (_keep_mixed, 1000, 2048),  # 1000 + 4·sqrt(1000) = 1126.5
    (_keep_all, 100, 256),      # 100 + 40
    (_keep_mixed, 1, 8),        # the floor
    (_keep_mixed, None, 512),   # a tenth of the 4,096 rows: 409.6 + 81.0
    (_totals, 1000, None),      # per-group: its segments are its output
])
def test_segment_capacity_from_the_group_estimate(udf, hint, cap):
    node = F.reduce_(_source(), ["k"], udf, hints=Hints(distinct_keys=hint))
    assert M.segment_capacity(node, {}) == cap
    assert M.segment_capacity(node.child, {}) is None
