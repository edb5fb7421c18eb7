"""`scans.search_sorted` — the PK probe's sorted search — equals
`max(jnp.searchsorted(a, x), start)` exactly, on every key pattern the
joins meet and at the edges of the integer range, and its directory's
`eq` is exactly whether the query occurs in `a[start:]`; its directory
search is as deep as the widest bucket; each traced call site counts once;
the PK and anti matches give bit-identical results on either path; and on
the cummax path the hit test gathers neither the PK code nor its
validity."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import executor, flow as F, masked, scans
from repro.core.masked import MaskedBatch, run_flow_jit
from repro.core.operators import Hints
from repro.core.record import Schema, batch_from_dict

# probe sides of at least this many queries take the directory
QUERIES = scans._PROBE_MIN + 4_321

PATTERNS = ["dense", "orderkey", "uniform", "duplicates", "equal", "gaps",
            "extremes", "shift32", "shift33", "lowwords"]


def _orderkeys(k: int) -> np.ndarray:
    """TPC-H's `o_orderkey`: 8 keys of every 32, from 1."""
    i = np.arange(k, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def _cummax_filled(keys: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """The PK side as the probe sees it: invalid slots repeat the previous
    valid code, and leading ones hold the dtype's minimum."""
    fill = np.iinfo(keys.dtype).min
    return np.maximum.accumulate(np.where(valid, keys, fill))


def _keys(pattern: str, n: int, dtype, rng) -> tuple:
    """`(a, start)`: a nondecreasing code array of `n` slots and the clamp
    the probe gives it (the first valid slot on the cummax path)."""
    info = np.iinfo(dtype)
    wide = (1 << 40) if dtype == np.int64 else info.max
    if pattern == "dense":
        return np.arange(1, n + 1, dtype=dtype), 0
    if pattern == "orderkey":
        return _orderkeys(n).astype(dtype), 0
    if pattern == "uniform":
        return np.sort(rng.integers(0, wide, n)).astype(dtype), 0
    if pattern == "duplicates":
        return np.sort(rng.integers(0, max(n // 50, 1), n)).astype(dtype), 0
    if pattern == "equal":
        return np.full(n, 7, dtype), 0
    if pattern == "gaps":
        # leading, interior and trailing runs of invalid slots
        valid = rng.random(n) < 0.7
        valid[: n // 20] = False
        valid[n - n // 5:] = False
        keys = np.sort(rng.integers(-wide, wide, n)).astype(dtype)
        return _cummax_filled(keys, valid), int(np.argmax(valid))
    if pattern == "lowwords" and dtype == np.int64:
        # a shift of 32 (the narrow steps), and codes that share their low
        # 32 bits across buckets: only the bucket tells them apart
        hi = rng.integers(0, 1 << (n - 1).bit_length(), n)
        low = np.array([0, 1, 1 << 31, (1 << 32) - 1])[rng.integers(0, 4, n)]
        return np.sort((hi << 32) + low).astype(dtype), 0
    if pattern in ("shift32", "shift33", "lowwords"):
        # 64-bit codes whose directory shift is 32 (the last that compares
        # 32-bit offsets) or 33, with pairs of codes in one bucket that
        # differ only in bit 31 or bit 32
        if dtype == np.int32:
            return np.sort(rng.integers(info.min, info.max, n,
                                        dtype=dtype)), 0
        shift = int(pattern[-2:])
        bits = shift + (n - 1).bit_length()
        pair = 1 << (shift - 1)
        c = rng.integers(0, (1 << bits) - pair, (n + 1) // 2)
        keys = np.r_[c, c + pair][:n]
        keys[-1] = (1 << bits) - 1
        return np.sort(keys).astype(dtype), 0
    # extremes: codes near +-2^62 and +-2^63 (+-2^30 and +-2^31 for int32)
    half = 1 << (8 * np.dtype(dtype).itemsize - 2)
    centres = np.array([info.min + 500, -half, half, info.max - 500],
                       dtype=np.int64)
    vals = centres[rng.integers(0, 4, n)] + rng.integers(-500, 500, n)
    return np.sort(vals).astype(dtype), 0


def _queries(a: np.ndarray, rng, m: int = QUERIES) -> np.ndarray:
    """Queries below, between, equal to and above the keys, and the ends
    of the range."""
    info = np.iinfo(a.dtype)
    at = a[rng.integers(0, len(a), m)]
    parts = [at,
             np.where(at < info.max, at + 1, at),
             np.where(at > info.min, at - 1, at),
             rng.integers(info.min, info.max, m, dtype=a.dtype),
             np.array([info.min, info.max, a[0], a[-1]], a.dtype)]
    if a.dtype == np.int64:
        parts.append(at ^ (1 << 32))     # the same low word, another bucket
    q = np.concatenate(parts).astype(a.dtype)
    return q[rng.permutation(len(q))[:m]]


def _want(a, x, start):
    return np.asarray(jnp.maximum(jnp.searchsorted(jnp.asarray(a),
                                                   jnp.asarray(x)), start))


def _want_eq(a, x, start):
    """Whether each `x` occurs in `a[start:]`."""
    return np.isin(x, a[start:])


_search = jax.jit(scans.search_sorted)

CASES = ([(p, n, dt) for n in (1, 65_536, 100_003) for p in PATTERNS
          for dt in (np.int32, np.int64)]
         + [(p, 1 << 21, np.int64) for p in PATTERNS])


@pytest.mark.parametrize("pattern,n,dtype", CASES)
def test_search_sorted_equals_the_search(pattern, n, dtype):
    rng = np.random.default_rng(n + len(pattern))
    a, start = _keys(pattern, n, dtype, rng)
    assert (np.diff(a) >= 0).all()
    x = _queries(a, rng)
    for s in (start, int(rng.integers(0, n + 1))):
        got, eq = _search(jnp.asarray(a), jnp.asarray(x), jnp.int32(s))
        assert got.dtype == jnp.int32
        want = _want(a, x, s)
        np.testing.assert_array_equal(np.asarray(got), want,
                                      err_msg=f"start={s}")
        np.testing.assert_array_equal(np.asarray(eq), _want_eq(a, x, s),
                                      err_msg=f"eq, start={s}")
        # the hit test the search replaces
        np.testing.assert_array_equal(
            np.asarray(eq), (want < n) & (a[np.minimum(want, n - 1)] == x))


# (keys, start, the queries that must miss, the queries that must hit)
EDGES = {
    "below_lo": ([10, 10, 20, 30], 0, [9, -(1 << 31)], [10]),
    "above_last": ([10, 20, 30], 0, [31, (1 << 31) - 1], [30]),
    "empty_buckets": ([0, 1, 1000, 1_000_000], 0,
                      [2, 500, 999, 1001, 999_999], [0, 1, 1000, 1_000_000]),
    "leading_invalid_run": ([-(1 << 31), -(1 << 31), 5, 5, 7], 2,
                            [-(1 << 31), 4, 6, 8], [5, 7]),
    "one_code": ([7] * 6, 0, [6, 8], [7]),
    "start_at_the_end": ([1, 2, 3], 3, [1, 2, 3, 4], []),
}


@pytest.mark.parametrize("wide", [False, True])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_eq_at_the_edges(edge, dtype, wide):
    """`eq` at each edge of the directory; `wide` adds a far code, which
    crowds the rest into bucket 0 and, for 64-bit codes, takes the wide
    (64-bit) steps."""
    keys, start, miss, hit = EDGES[edge]
    a = np.array(keys, np.int64)
    if wide and dtype == np.int64:
        a = np.r_[a, 1 << 50]
    elif wide:
        a = np.r_[a, 1 << 30]
    a = a.astype(dtype)
    x = np.resize(np.array(miss + hit, dtype), QUERIES)
    pos, eq = _search(jnp.asarray(a), jnp.asarray(x), jnp.int32(start))
    np.testing.assert_array_equal(np.asarray(pos), _want(a, x, start))
    np.testing.assert_array_equal(np.asarray(eq), _want_eq(a, x, start))
    assert not np.asarray(eq)[:len(miss)].any()
    assert np.asarray(eq)[len(miss):len(miss) + len(hit)].all()


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_search_sorted_short_probe_and_float_codes(dtype):
    """Shorter probe sides and float codes keep `jnp.searchsorted`; a
    `start` of 0 is no clamp."""
    rng = np.random.default_rng(3)
    a, _ = _keys("gaps", 4096, dtype, rng)
    x = _queries(a, rng, 1000)
    for s in (0, 17):
        pos, eq = scans.search_sorted(jnp.asarray(a), jnp.asarray(x), s)
        np.testing.assert_array_equal(np.asarray(pos), _want(a, x, s))
        assert eq is None
    af = np.sort(rng.standard_normal(70_000))
    xf = rng.standard_normal(QUERIES)
    pos, eq = _search(jnp.asarray(af), jnp.asarray(xf), jnp.int32(5))
    np.testing.assert_array_equal(np.asarray(pos), _want(af, xf, 5))
    assert eq is None


def _steps(a, start=0):
    return int(jax.jit(lambda v: scans._directory(v, start)[-1])(
        jnp.asarray(a)))


@pytest.mark.parametrize("side", ["orders", "customer", "dense"])
def test_widest_bucket_steps(side):
    """Q7's PK sides at their bound capacities, trailing fill slots one
    code: 1,500,304 `o_orderkey`s in 2,097,152 slots search at most 3 steps
    deep, 150,000 dense customer keys in 262,144 slots 1, and 2^21 dense
    keys 1."""
    if side == "orders":
        n = 1 << 21
        a = _cummax_filled(_orderkeys(n), np.arange(n) < 1_500_304)
    elif side == "customer":
        n = 1 << 18
        a = _cummax_filled(np.arange(1, n + 1), np.arange(n) < 150_000)
    else:
        a = np.arange(1, (1 << 21) + 1, dtype=np.int64)
    steps = _steps(a)
    assert steps <= 3 if side == "orders" else steps == 1


def test_one_bucket_is_as_deep_as_the_plain_search():
    """A code far past the rest puts every other code in bucket 0."""
    assert _steps(np.array([0] * 1000 + [1 << 40] * 3, np.int64)) == 1
    assert _steps(np.r_[np.arange(1000), [1 << 40]]) == 10


def test_counters_count_one_site_per_trace():
    obs.reset()
    obs.enable()
    try:
        a = jnp.arange(1000, dtype=jnp.int64)
        big = jnp.arange(QUERIES, dtype=jnp.int64)
        small = jnp.arange(100, dtype=jnp.int64)
        fn = jax.jit(lambda a, b, s: (scans.search_sorted(a, b, 3),
                                      scans.search_sorted(a, s, 3)))
        for _ in range(2):      # the second call reuses the trace
            fn(a, big, small)
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    assert counts == {"probe.directory": 1, "probe.search": 1}


def _probe_hlo(n_keys: int, n_queries: int) -> list:
    """`op_name`s under `/probe/` in the CPU HLO of one PK probe."""
    a = jax.ShapeDtypeStruct((n_keys,), jnp.int64)
    x = jax.ShapeDtypeStruct((n_queries,), jnp.int64)
    valid = jax.ShapeDtypeStruct((n_keys,), jnp.bool_)
    hlo = jax.jit(lambda a, v, x: masked._probe(a, v, x, None, 0, False)
                  ).lower(a, valid, x).compile().as_text()
    return [p for p in re.findall(r'op_name="([^"]*)"', hlo)
            if "/probe/" in p]


@pytest.mark.parametrize("n_keys", [16_384, 2_097_152])
def test_no_search_loop_under_probe_at_the_threshold(n_keys):
    above = _probe_hlo(n_keys, scans._PROBE_MIN)
    assert above and not any("jit(searchsorted)" in p for p in above)
    below = _probe_hlo(n_keys, scans._PROBE_MIN - 1)
    assert any("jit(searchsorted)" in p for p in below)


# --- the PK and anti matches, directory against search --------------------

MATCH_CASES = ["ordered", "gappy", "unordered", "composite", "misses",
               "duplicates", "no_valid_pk", "no_valid_pk_unordered"]


def _match_inputs(case: str, rng):
    """`(op, lb, rb, use_order)` for one parity case; the left (probe)
    side holds `QUERIES` slots."""
    nl, nr = QUERIES, 20_000
    two = case == "composite"
    lkey, rkey = (["a", "a2"], ["b", "b2"]) if two else (["a"], ["b"])
    left = F.source("L", Schema.of(a=np.int64, a2=np.int64, x=np.int64),
                    num_records=nl)
    right = F.source("R", Schema.of(b=np.int64, b2=np.int64, y=np.int64),
                     num_records=nr)
    keys = _orderkeys(nr)
    rvalid = np.ones(nr, bool)
    if case == "gappy":
        rvalid = rng.random(nr) < 0.6
        rvalid[:500] = False
        rvalid[-3000:] = False
        keys[rvalid.argmax()] = np.iinfo(np.int64).min   # the minimal code
    if case == "duplicates":
        keys = np.sort(rng.integers(0, nr // 4, nr))
        rvalid = rng.random(nr) < 0.5
    if case.startswith("no_valid_pk"):
        rvalid = np.zeros(nr, bool)
    unordered = case in ("unordered", "composite", "no_valid_pk_unordered")
    if unordered:
        keys = rng.permutation(keys)
    lk = keys[rng.integers(0, nr, nl)]
    lk[rng.random(nl) < 0.05] = -5       # below every key but the minimum
    if case == "misses":
        lk = lk + rng.integers(0, 3, nl) * 9     # most in the gaps
    if case.startswith("no_valid_pk"):
        # the cummax fill's code: it matches unless validity is checked
        lk[rng.random(nl) < 0.05] = np.iinfo(np.int64).min
    lb = MaskedBatch({"a": jnp.asarray(lk), "a2": jnp.asarray(lk % 7),
                      "x": jnp.asarray(rng.integers(0, 99, nl))},
                     jnp.asarray(rng.random(nl) < 0.9))
    order = () if unordered else ("b",)
    rb = MaskedBatch({"b": jnp.asarray(keys), "b2": jnp.asarray(keys % 7),
                      "y": jnp.asarray(rng.integers(0, 99, nr))},
                     jnp.asarray(rvalid), order)
    return left, right, lkey, rkey, lb, rb


@pytest.mark.parametrize("anti", [False, True])
@pytest.mark.parametrize("case", MATCH_CASES)
def test_match_directory_equals_search(case, anti, monkeypatch):
    rng = np.random.default_rng(MATCH_CASES.index(case))
    left, right, lkey, rkey, lb, rb = _match_inputs(case, rng)
    op = F.match(left, right, lkey, rkey, name="J", anti=anti,
                 hints=Hints(pk_side="right"))
    run = masked._exec_match_anti if anti else masked._exec_match_pk

    def both():
        obs_ = {}
        out = jax.jit(lambda lb, rb: (run(op, lb, rb, True, obs_),
                                      obs_["groups"]))(lb, rb)
        return jax.tree_util.tree_map(np.asarray, out)

    obs.reset()
    obs.enable()
    try:
        directory = both()
        assert obs.snapshot()["counts"]["probe.hit_in_search"] == 1
        monkeypatch.setattr(scans, "_PROBE_MIN", 1 << 40)
        search = both()
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    assert counts["probe.directory"] == counts["probe.search"] == 1
    assert counts["probe.hit_in_search"] == 1
    (out_d, groups_d), (out_s, groups_s) = directory, search
    assert int(groups_d) == int(groups_s)
    if case.startswith("no_valid_pk") and not anti:
        assert int(groups_d) == 0
    else:
        assert 0 < int(groups_d) < QUERIES
    np.testing.assert_array_equal(out_d.valid, out_s.valid)
    assert out_d.columns.keys() == out_s.columns.keys()
    for name in out_d.columns:
        np.testing.assert_array_equal(out_d.columns[name],
                                      out_s.columns[name], err_msg=name)


def _gathers(jaxpr, stack=""):
    """`(name stack, operand aval, indices aval)` of every gather in a
    jaxpr and the jaxprs nested in it."""
    out = []
    for e in jaxpr.eqns:
        where = stack + "/" + str(e.source_info.name_stack)
        if e.primitive.name == "gather":
            out.append((where, e.invars[0].aval, e.invars[1].aval))
        for sub in jax.core.jaxprs_in_params(e.params):
            out += _gathers(sub, where)
    return out


def test_cummax_hit_gathers_neither_pk_code_nor_validity(monkeypatch):
    """A PK match on a key-ordered PK side, with int64 keys and 65,536
    probe slots, whose UDF keeps no PK column: after dead-code elimination
    its jaxpr gathers nothing from the PK side outside the `probe` scope.
    Under the plain search the hit test gathers the PK code (int64) and
    its validity (bool) there."""
    from jax._src.interpreters import partial_eval as pe

    nl, nr = scans._PROBE_MIN, 20_000
    left = F.source("L", Schema.of(a=np.int64, x=np.int64), num_records=nl)
    right = F.source("R", Schema.of(b=np.int64, y=np.int32),
                     num_records=nr)

    def keep_left(l, r, out):
        out.emit(l.copy())

    op = F.match(left, right, ["a"], ["b"], udf=keep_left, name="J",
                 hints=Hints(pk_side="right"))
    lb = MaskedBatch({"a": jnp.zeros(nl, jnp.int64),
                      "x": jnp.zeros(nl, jnp.int64)}, jnp.ones(nl, bool))
    rb = MaskedBatch({"b": jnp.arange(nr, dtype=jnp.int64),
                      "y": jnp.zeros(nr, jnp.int32)}, jnp.ones(nr, bool),
                     ("b",))

    def pk_gathers():
        jaxpr = jax.make_jaxpr(
            lambda lb, rb: masked._exec_match_pk(op, lb, rb))(lb, rb).jaxpr
        jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        return sorted(str(g[1].dtype) for g in _gathers(jaxpr)
                      if "probe" not in g[0].split("/")
                      and g[1].shape == (nr,))

    obs.reset()
    obs.enable()
    try:
        directory = pk_gathers()
        hits = obs.snapshot()["counts"].get("probe.hit_in_search", 0)
        monkeypatch.setattr(scans, "_PROBE_MIN", 1 << 40)
        search = pk_gathers()
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    assert directory == [] and hits == 1
    assert search == ["bool", "int64"]
    assert counts["probe.hit_in_search"] == 1 and counts["probe.search"] == 1


@pytest.mark.parametrize("composite", [False, True])
def test_flow_through_the_directory_matches_eager(composite, monkeypatch):
    """A fact-dimension flow with a pushed-down filter on the PK side, with
    every probe taking the directory, equals the eager executor."""
    monkeypatch.setattr(scans, "_PROBE_MIN", 1)
    rng = np.random.default_rng(11)
    nd, nf = 64, 500
    fact = F.source("fact", Schema.of(fk=np.int64, f2=np.int64,
                                      x=np.int64), num_records=nf)
    dim = F.source("dim", Schema.of(dk=np.int64, d2=np.int64, y=np.int64),
                   num_records=nd, sorted_on=("dk",))

    def dimfilter(ir, out):
        out.emit(ir.copy(), where=ir.get("y") % 3 != 0)

    keys = (["fk", "f2"], ["dk", "d2"]) if composite else (["fk"], ["dk"])
    root = F.match(fact, F.map_(dim, dimfilter, name="DimFilter"), *keys,
                   name="J", hints=Hints(pk_side="right"))
    dk = _orderkeys(nd)
    fk = dk[rng.integers(0, nd, nf)] + rng.integers(0, 2, nf)
    b = {"fact": batch_from_dict({"fk": fk, "f2": fk % 5,
                                  "x": rng.integers(-99, 99, nf)}),
         "dim": batch_from_dict({"dk": dk, "d2": dk % 5,
                                 "y": rng.integers(0, 100, nd)})}
    want = _rows(executor.execute(root, b))
    for use_order in (True, False):
        assert _rows(run_flow_jit(root, b, use_order=use_order)) == want


def _rows(batch):
    b = batch.to_numpy().compact()
    fields = sorted(b.fields)
    return sorted(zip(*[np.asarray(b.columns[f]).tolist() for f in fields]))


# --- the benchmark's queries at a tiny size -------------------------------

def _routes(run) -> tuple:
    """`(correct, probe counters)` of one tiny run through the cell's
    path."""
    from chipbench.tests._util import correct, drive

    obs.reset()
    obs.enable()
    try:
        out = drive(run)
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    return correct(out), {k: v for k, v in counts.items()
                          if k.startswith("probe.")}


def test_tiny_q15_decides_no_hit_in_search():
    """Q15's one PK probe (the top supplier into supplier) keeps the plain
    search and its hit test."""
    from chipbench.tests._util import tiny_run

    ok, counts = _routes(tiny_run("q15-sf1-pipeline"))
    assert ok
    assert counts == {"probe.search": 1}


def test_tiny_q7_decides_four_hits_in_search(monkeypatch):
    """With the threshold cut to the tiny sizes (32,768 probe slots for
    four joins, 64 for `JoinSuppNation`), Q7 takes SF1's routes: four
    cummax probes decide their hits in the search, and the answer matches
    the reference."""
    from chipbench.tests.test_chipbench_q7 import tiny_q7_run

    monkeypatch.setattr(scans, "_PROBE_MIN", 1024)
    ok, counts = _routes(tiny_q7_run())
    assert ok
    assert counts == {"probe.directory": 4, "probe.hit_in_search": 4,
                      "probe.search": 1}
