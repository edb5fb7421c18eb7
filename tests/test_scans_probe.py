"""`scans.search_sorted` — the PK probe's sorted search — equals
`max(jnp.searchsorted(a, x), start)` exactly, on every key pattern the
joins meet and at the edges of the integer range; its directory search is
as deep as the widest bucket; each traced call site counts once; and the
PK and anti matches give bit-identical results on either path."""

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
            "extremes", "shift32", "shift33"]


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
    if pattern in ("shift32", "shift33"):
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
    q = np.concatenate(parts).astype(a.dtype)
    return q[rng.permutation(len(q))[:m]]


def _want(a, x, start):
    return np.asarray(jnp.maximum(jnp.searchsorted(jnp.asarray(a),
                                                   jnp.asarray(x)), start))


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
        got = _search(jnp.asarray(a), jnp.asarray(x), jnp.int32(s))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), _want(a, x, s),
                                      err_msg=f"start={s}")


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_search_sorted_short_probe_and_float_codes(dtype):
    """Shorter probe sides and float codes keep `jnp.searchsorted`; a
    `start` of 0 is no clamp."""
    rng = np.random.default_rng(3)
    a, _ = _keys("gaps", 4096, dtype, rng)
    x = _queries(a, rng, 1000)
    for s in (0, 17):
        np.testing.assert_array_equal(
            np.asarray(scans.search_sorted(jnp.asarray(a), jnp.asarray(x),
                                           s)), _want(a, x, s))
    af = np.sort(rng.standard_normal(70_000))
    xf = rng.standard_normal(QUERIES)
    np.testing.assert_array_equal(
        np.asarray(_search(jnp.asarray(af), jnp.asarray(xf), jnp.int32(5))),
        _want(af, xf, 5))


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
    hlo = jax.jit(lambda a, x: masked._probe(a, x, 0)).lower(
        a, x).compile().as_text()
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
               "duplicates"]


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
    if case in ("unordered", "composite"):
        keys = rng.permutation(keys)
    lk = keys[rng.integers(0, nr, nl)]
    lk[rng.random(nl) < 0.05] = -5       # below every key but the minimum
    if case == "misses":
        lk = lk + rng.integers(0, 3, nl) * 9     # most in the gaps
    lb = MaskedBatch({"a": jnp.asarray(lk), "a2": jnp.asarray(lk % 7),
                      "x": jnp.asarray(rng.integers(0, 99, nl))},
                     jnp.asarray(rng.random(nl) < 0.9))
    order = ("b",) if case not in ("unordered", "composite") else ()
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
        monkeypatch.setattr(scans, "_PROBE_MIN", 1 << 40)
        search = both()
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    assert counts["probe.directory"] == counts["probe.search"] == 1
    (out_d, groups_d), (out_s, groups_s) = directory, search
    assert int(groups_d) == int(groups_s)
    assert 0 < int(groups_d) < QUERIES
    np.testing.assert_array_equal(out_d.valid, out_s.valid)
    assert out_d.columns.keys() == out_s.columns.keys()
    for name in out_d.columns:
        np.testing.assert_array_equal(out_d.columns[name],
                                      out_s.columns[name], err_msg=name)


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
