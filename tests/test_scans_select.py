"""`scans.select` — the position of each set bit of a mask, in order —
equals the binary-search formula it replaces bit for bit, on masks long
enough for its blocked form and on those that keep the search; so do its
three callers: the compaction's pack, the sorted segments' boundaries and
CoGroup's valids-first permutation.  The blocked form compiles without a
loop, and each traced call site counts once.  The shift-and-combine
`cumsum` and `cummax` it builds on equal XLA's scans bit for bit."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import masked, scans
from repro.core.udf import JitSegmentOps

# 1000 and 4096 are too short for the blocked form; 65,536, 196,608 (not a
# power of two) and 100,003 (not a whole number of 128-slot blocks) take it
SIZES = [1000, 4096, 65_536, 196_608, 100_003]
DENSITIES = ["none", "one", 0.0378, 0.5, 1.0]


def _mask(n: int, density, seed: int = 7) -> np.ndarray:
    if density == "none":
        return np.zeros(n, bool)
    if density == "one":
        m = np.zeros(n, bool)
        m[np.random.default_rng(seed).integers(n)] = True
        return m
    return np.random.default_rng(seed).random(n) < density


def _search(mask, k):
    """The formula `select` replaces."""
    cv = jnp.cumsum(jnp.asarray(mask).astype(jnp.int32))
    return jnp.searchsorted(cv, jnp.arange(1, k + 1, dtype=jnp.int32))


@pytest.mark.parametrize("op", ["cumsum", "cummax_int", "cummax_float"])
@pytest.mark.parametrize("n", [1, 127, 128, 129, 1000, 16_384, 100_003])
def test_scans_equal_xla_scans(n, op):
    """Lengths within one row, at whole rows and with a padded last row, and
    two levels of carries at 100,003; signed values and -inf, since a fill
    value shifted in would be wrong for both."""
    rng = np.random.default_rng(n)
    if op == "cummax_float":
        v = rng.standard_normal(n) - 5.0
        v[rng.random(n) < 0.3] = -np.inf
    else:
        v = rng.integers(-1000, 1000, n).astype(np.int32)
    v = jnp.asarray(v)
    if op == "cumsum":
        got, want = jax.jit(scans.cumsum)(v), jnp.cumsum(v)
    else:
        got, want = jax.jit(scans.cummax)(v), jax.lax.cummax(v)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _ks(count: int) -> dict:
    return {"below": max(count // 2, 1), "at": max(count, 1),
            "above": count + 300}


@pytest.mark.parametrize("where", ["below", "at", "above"])
@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("n", SIZES)
def test_select_equals_the_search(n, density, where):
    mask = _mask(n, density)
    k = _ks(int(mask.sum()))[where]
    got = jax.jit(scans.select, static_argnums=1)(jnp.asarray(mask), k)
    want = _search(mask, k)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [65_536, 131_072, 100_003])
def test_select_every_k_past_n(n):
    """Every k from 1 to past n, on one mask: the rows of the output and
    the blocks of the mask fall at every alignment."""
    mask = _mask(n, 0.5, seed=n)
    sel = jax.jit(scans.select, static_argnums=1)
    for k in (1, 127, 128, 129, n // 2 + 1, n - 1, n, n + 129):
        np.testing.assert_array_equal(
            np.asarray(sel(jnp.asarray(mask), k)),
            np.asarray(_search(mask, k)), err_msg=f"k={k}")


@pytest.mark.parametrize("capacity_of_n", [0.25, 1.0, 1.5])
@pytest.mark.parametrize("n", [4096, 65_536, 100_003])
def test_pack_indices_unchanged(n, capacity_of_n):
    valid = jnp.asarray(_mask(n, 0.0378))
    cap = int(n * capacity_of_n)
    src, count = jax.jit(scans.pack_indices, static_argnums=1)(valid, cap)
    cv = jnp.cumsum(valid.astype(jnp.int32))
    want = jnp.minimum(jnp.searchsorted(
        cv, jnp.arange(1, cap + 1, dtype=jnp.int32)), n - 1)
    assert src.dtype == want.dtype and count.dtype == cv.dtype
    np.testing.assert_array_equal(np.asarray(src), np.asarray(want))
    assert int(count) == int(cv[-1])


def _starts_ends_by_search(is_start, num_segments):
    n = is_start.shape[0]
    c = jnp.cumsum(is_start.astype(jnp.int32))
    u = jnp.searchsorted(c, jnp.arange(1, num_segments + 2, dtype=jnp.int32))
    return (jnp.minimum(u[:-1], n - 1).astype(jnp.int32),
            jnp.clip(u[1:] - 1, 0, n - 1).astype(jnp.int32), c[-1])


@pytest.mark.parametrize("case", ["sparse", "full_last_valid"])
@pytest.mark.parametrize("n", [4096, 65_536, 100_003])
def test_starts_ends_unchanged(n, case):
    rng = np.random.default_rng(n)
    if case == "sparse":
        # ~1% of slots start a group, as the combiner's sorted batch does
        is_start = rng.random(n) < 0.01
    else:
        # a full batch whose last slot is valid and starts a group of one:
        # the last live group must end at n - 1
        is_start = rng.random(n) < 0.3
        is_start[0] = is_start[-1] = True
    is_start = jnp.asarray(is_start)
    seg = jnp.maximum(jnp.cumsum(is_start.astype(jnp.int32)) - 1, 0)
    ops = JitSegmentOps(seg, n, is_start=is_start)
    starts, ends, groups = jax.jit(lambda: ops._starts_ends())()
    want = _starts_ends_by_search(is_start, n)
    for got, w in zip((starts, ends, groups), want):
        assert got.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    g = int(groups)
    if case == "full_last_valid":
        assert int(ends[g - 1]) == n - 1 and int(starts[g - 1]) == n - 1


def _compact_perm_by_search(valid):
    n = valid.shape[0]
    cv = jnp.cumsum(valid.astype(jnp.int32))
    ci = jnp.cumsum((~valid).astype(jnp.int32))
    j = jnp.arange(n, dtype=jnp.int32)
    nv = cv[-1]
    return jnp.where(j < nv, jnp.searchsorted(cv, j + 1),
                     jnp.searchsorted(ci, j + 1 - nv)).astype(jnp.int32)


@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("n", [1000, 65_536, 100_003])
def test_compact_perm_unchanged(n, density):
    valid = jnp.asarray(_mask(n, density))
    got = jax.jit(masked._compact_perm)(valid)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_compact_perm_by_search(valid)))
    np.testing.assert_array_equal(
        np.asarray(got), np.argsort(~np.asarray(valid), kind="stable"))


@pytest.mark.parametrize("n", [65_536, 100_003])
def test_blocked_select_compiles_without_a_loop(n):
    x = jax.ShapeDtypeStruct((n,), jnp.bool_)
    blocked = jax.jit(scans.select, static_argnums=1).lower(x, 8192)
    assert "while" not in blocked.compile().as_text()
    short = jax.jit(scans.select, static_argnums=1).lower(
        jax.ShapeDtypeStruct((4096,), jnp.bool_), 512)
    assert "while" in short.compile().as_text()


def test_counters_count_one_site_per_trace():
    obs.reset()
    obs.enable()
    try:
        big = jnp.asarray(_mask(65_536, 0.5))
        small = jnp.asarray(_mask(4096, 0.5))
        # fresh functions, so that no earlier test's trace is reused
        pack = jax.jit(lambda v, k: scans.pack_indices(v, k),
                       static_argnums=1)
        for _ in range(2):      # the second call reuses the trace
            pack(big, 8192)
            pack(small, 512)
        perm = jax.jit(lambda v: masked._compact_perm(v))
        perm(big)
        perm(big)
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    # one pack of each size, and the permutation's two selects
    assert counts == {"select.blocked": 3, "select.search": 1}
