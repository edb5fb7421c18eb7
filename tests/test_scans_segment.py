"""The data plane's segmented scan, segment reductions and sorted probe
against their pure-jnp oracles (`repro.kernels.ref`): `scans.segmented_scan`,
`udf.JitSegmentOps` as the masked executor builds it, and
`scans.search_sorted` on both of its paths."""

from unittest import mock

import numpy as np
import jax
import jax.numpy as jnp
import pytest

# optional dependency: skip cleanly (instead of failing collection)
# in environments without hypothesis
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import masked as M
from repro.core import scans
from repro.core.udf import JitSegmentOps
from repro.kernels import ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n,c,op", [
    (64, 3, "add"), (512, 1, "max"), (1000, 2, "min"), (48, 4, "add"),
    (8, 1, "max"), (4096, 2, "add"),
])
def test_segmented_scan(n, c, op):
    v = jnp.asarray(RNG.normal(size=(n, c)).astype(np.float32))
    flags = jnp.asarray(RNG.random(n) < 0.2).at[0].set(True)
    scan = jax.jit(scans.segmented_scan, static_argnums=2)
    got = jnp.stack([scan(v[:, j], flags, op) for j in range(c)], axis=1)
    np.testing.assert_allclose(got, ref.segmented_scan(v, flags, op=op),
                               rtol=1e-5, atol=1e-5)


_REDUCE = {"add": "sum", "max": "max", "min": "min"}


@pytest.mark.parametrize("n,nseg,op,frac_valid", [
    (128, 16, "add", 0.8), (1000, 50, "max", 0.5), (256, 8, "min", 1.0),
    (64, 64, "add", 0.3),
])
def test_segment_reduce(n, nseg, op, frac_valid):
    """Key-sorted rows with invalid slots among them.  The Reduce path
    numbers the groups of VALID rows densely (`masked._segments_gappy`)
    and reduces with `is_start`, through the scatter and the segmented
    scan alike; the CoGroup path reduces by the raw ids with the validity
    mask.  Both must equal the oracle on every segment that holds a valid
    row."""
    sid = np.sort(RNG.integers(0, nseg, n)).astype(np.int32)
    v = jnp.asarray(RNG.normal(size=n).astype(np.float32))
    valid = jnp.asarray(RNG.random(n) < frac_valid)
    want = np.asarray(ref.segment_reduce(v, jnp.asarray(sid), nseg, op=op,
                                         valid=valid))
    live = np.unique(sid[np.asarray(valid)])

    for scan_min in (JitSegmentOps._SCAN_MIN_ROWS, 0):
        @jax.jit
        def reduce_path(sid, valid, v):  # traced afresh under each patch
            seg, is_start = M._segments_gappy({"k": sid}, ("k",), valid)
            ops = JitSegmentOps(seg, n, record_valid=valid, is_start=is_start)
            return getattr(ops, _REDUCE[op])(v)

        with mock.patch.object(JitSegmentOps, "_SCAN_MIN_ROWS", scan_min):
            got = np.asarray(reduce_path(jnp.asarray(sid), valid, v))
        np.testing.assert_allclose(got[:len(live)], want[live],
                                   rtol=1e-5, atol=1e-5)

    @jax.jit
    def cogroup_path(sid, valid, v):
        ops = JitSegmentOps(sid, nseg, record_valid=valid)
        return getattr(ops, _REDUCE[op])(v)

    got = np.asarray(cogroup_path(jnp.asarray(sid), valid, v))
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 300), m=st.integers(1, 300),
       lo=st.integers(-100, 0), hi=st.integers(1, 1000))
def test_sorted_probe_property(n, m, lo, hi):
    """Float codes take `jnp.searchsorted`; integer codes take the bucket
    directory once the probe side reaches `_PROBE_MIN` (patched down to
    one query here), whose `eq` is whether the query is a key."""
    keys = np.sort(RNG.integers(lo, hi, n))
    qs = RNG.integers(lo - 5, hi + 5, m)
    with mock.patch.object(scans, "_PROBE_MIN", 1):
        for dtype in (np.float64, np.int64):
            k = jnp.asarray(keys.astype(dtype))
            q = jnp.asarray(qs.astype(dtype))
            pos, eq = jax.jit(scans.search_sorted)(k, q)
            np.testing.assert_array_equal(
                np.asarray(pos), np.asarray(ref.sorted_probe(k, q)))
            if eq is not None:
                np.testing.assert_array_equal(np.asarray(eq),
                                              np.isin(qs, keys))
