"""The masked Reduce's tracing: the `segment` device scope and the
trace-time counters `reduce.sorted`, `reduce.sort`, `reduce.group_filter`
and `segment.scan` (`repro.obs`)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import flow as F
from repro.core import masked as M
from repro.core.record import Schema

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


def _counts(node, batch) -> dict:
    """Counters of one trace of `node`'s Reduce over `batch`."""
    obs.reset()
    obs.enable()
    try:
        jax.eval_shape(lambda b: M._exec_reduce(node, b), batch)
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
