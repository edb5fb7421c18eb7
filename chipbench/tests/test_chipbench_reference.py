"""The yardstick's own pieces: the logical bytes of Q15, the comparison,
and the plain references against the program's eager executor."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import compare as C
from chipbench.tests._util import H


def test_q15_logical_bytes_at_sf1():
    cfg = H.load_json("configs", "tpch-q15-sf1.json")
    flows = H.load_module("configs", "tpch-q15-sf1.py")
    # the columns Q15 reads, once: lineitem's suppkey, extendedprice,
    # discount (8 bytes) and shipdate (4); supplier's suppkey and phone (8),
    # name and address codes (4)
    assert flows.logical_bytes(cfg, 0) == 6_001_215 * 28 + 10_000 * 24
    # each output row: suppkey, name, address, phone and revenue
    assert flows.logical_bytes(cfg, 2) == 168_274_020 + 2 * 32
    assert flows.rows_consumed(cfg) == 6_001_215


def test_q15_generator_keeps_sizes_and_spec_shapes():
    """Every seed gives the same sizes; one seed gives the same tables; the
    tables carry the full schemas, lineitem in orderkey order with its
    suppkeys unsorted."""
    cfg = dict(H.load_json("configs", "tpch-q15-sf1.json"),
               lineitem_rows=60_000, supplier_rows=100, part_rows=2_000)
    flows = H.load_module("configs", "tpch-q15-sf1.py")
    a, b, c = (flows.generate(cfg, s) for s in (2**33 + 1, 2**33 + 1, 9))
    for t, cols in (("lineitem", "lineitem_columns"),
                    ("supplier", "supplier_columns")):
        assert list(a[t]) == list(cfg[cols])
        for name, dtype in cfg[cols].items():
            assert a[t][name].dtype == np.dtype(dtype), name
            assert len(a[t][name]) == len(c[t][name])
            assert np.array_equal(a[t][name], b[t][name])
    li = a["lineitem"]
    assert len(li["l_orderkey"]) == 60_000
    assert np.all(np.diff(li["l_orderkey"]) >= 0)
    assert np.any(np.diff(li["l_suppkey"]) < 0)
    assert set(np.unique(li["l_suppkey"])) == set(range(1, 101))
    lo, hi = cfg["ship_window"]
    share = np.mean((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi))
    assert 0.03 < share < cfg["selectivity"]


def test_compare_counts_rows_and_float_gap():
    ref = {"k": np.array([1, 2, 3]), "v": np.array([1.0, 2.0, 4.0])}
    same = {"k": np.array([3, 1, 2]), "v": np.array([4.0, 1.0, 2.0])}
    assert C.compare(same, ref) == (0, 0.0)
    off = {"k": np.array([1, 2, 3]), "v": np.array([1.0, 2.0, 4.0 + 4e-9])}
    m, e = C.compare(off, ref)
    assert m == 0 and e == pytest.approx(1e-9)
    short = {"k": np.array([1, 2]), "v": np.array([1.0, 2.0])}
    assert C.compare(short, ref)[0] == 1
    wrong = {"k": np.array([1, 2, 5]), "v": np.array([1.0, 2.0, 4.0])}
    assert C.compare(wrong, ref)[0] == 2
    assert C.compare({"k": np.array([1, 2, 3])}, ref)[0] == 6
    nan = {"k": np.array([1, 2, 3]), "v": np.array([1.0, np.nan, 4.0])}
    assert not C.compare(nan, ref)[1] <= 1e-10


def _eager(root, data):
    from repro.core import executor
    from repro.core.record import batch_from_dict

    out = executor.execute(root, {n: batch_from_dict(c)
                                  for n, c in data.items()})
    return C.host_columns(out)


@pytest.mark.parametrize("seed", [2**31 + 3, 2**40 + 11, 5])
def test_q15_reference_matches_the_eager_executor(seed):
    cfg = dict(H.load_json("configs", "tpch-q15-sf1.json"),
               lineitem_rows=30_000, supplier_rows=50, part_rows=1_000)
    flows = H.load_module("configs", "tpch-q15-sf1.py")
    ref = H.load_module("configs", "tpch-q15-sf1.ref.py")
    data = flows.generate(cfg, seed)
    want = ref.reference(cfg, data)
    assert len(want["s_suppkey"]) >= 1
    m, e = C.compare(_eager(flows.flow(cfg), data), want)
    assert m == 0 and e < 1e-12
