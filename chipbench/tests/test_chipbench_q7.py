"""The Q7 cell at a tiny size on the CPU: its run through `paths/pipeline.py`
is correct against the plain reference, an altered answer and the
float32 control are not, its tables are Q15's where they overlap, and the
planner's counter reads."""

from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench.tests._util import H, altered, correct, drive

CELL = {"name": "q7-sf1-pipeline", "config": "tpch-q7-sf1",
        "traffic": "back-to-back", "chips": 1}
TINY_ROWS = 24_000      # lineitem rows of a tiny Q7


def tiny_config(name: str = "tpch-q7-sf1") -> dict:
    """`name`'s configuration at TINY_ROWS lineitem rows, the other tables
    cut in SF1's proportions."""
    cfg = H.load_json("configs", f"{name}.json")
    n_su = TINY_ROWS // 600
    cfg.update(lineitem_rows=TINY_ROWS, supplier_rows=n_su,
               part_rows=20 * n_su)
    if "orders_rows" in cfg:
        cfg.update(orders_rows=TINY_ROWS // 4, customer_rows=TINY_ROWS // 40)
    return cfg


def tiny_q7_run(seed: int = 2**31 + 91, control: bool = False) -> H.Run:
    import jax

    return H.Run(CELL, seed, 1.0, False, time.time(), jax.devices()[:1],
                 config=tiny_config(), control=control)


def test_q7_pipeline_cell_rehearsal():
    r = tiny_q7_run()
    out = drive(r)
    assert correct(out), out.checks
    assert r.window_compiles == 0
    assert out.attempted == out.counters["queries"] > 0
    assert out.counters["rows_out"] > 0
    assert "JoinSuppNation" in out.counters["plan"]


def test_q7_answer_altered(monkeypatch):
    from repro.core.pipeline import CompiledPlan

    run_device = CompiledPlan.run_device
    monkeypatch.setattr(CompiledPlan, "run_device",
                        lambda self, *a, **k: altered(
                            run_device(self, *a, **k)))
    out = drive(tiny_q7_run())
    assert not correct(out)
    assert out.checks["rows_mismatched"][0] > 0


@pytest.mark.parametrize("seed", [2**31 + 3, 11])
def test_q7_control_fails_the_limit(seed):
    """The reference's float32 control in the program's place comes out
    not correct: its revenue is off by more than the limit."""
    r = tiny_q7_run(seed, control=True)
    out = drive(r)
    assert out.counters["control"]
    assert not correct(out)
    assert out.checks["rel_err"][0] > r.config["limits"]["rel_err"]
    assert out.checks["rows_mismatched"][0] == 0


@pytest.mark.parametrize("seed", [2**33 + 5, 4])
def test_q7_tables_are_q15s_where_they_overlap(seed):
    q7 = H.load_module("configs", "tpch-q7-sf1.py").generate(
        tiny_config(), seed)
    q15 = H.load_module("configs", "tpch-q15-sf1.py").generate(
        tiny_config("tpch-q15-sf1"), seed)
    for t in ("lineitem", "supplier"):
        assert list(q7[t]) == list(q15[t])
        for c in q15[t]:
            assert np.array_equal(q7[t][c], q15[t][c]), (t, c)


def test_q7_generator_keeps_keys_and_spec_shapes():
    cfg = tiny_config()
    data = H.load_module("configs", "tpch-q7-sf1.py").generate(cfg, 2**32)
    for t in ("orders", "customer"):
        assert list(data[t]) == list(cfg[f"{t}_columns"])
        for c, dtype in cfg[f"{t}_columns"].items():
            assert data[t][c].dtype == np.dtype(dtype), c
    od, cu, li = data["orders"], data["customer"], data["lineitem"]
    assert np.array_equal(od["o_orderkey"], np.unique(li["l_orderkey"]))
    assert np.all(od["o_custkey"] % 3 != 0)
    assert np.array_equal(cu["c_custkey"],
                          np.arange(1, cfg["customer_rows"] + 1))
    # each line ships 1-121 days after its order
    o = np.searchsorted(od["o_orderkey"], li["l_orderkey"])
    lag = li["l_shipdate"] - od["o_orderdate"][o]
    assert lag.min() >= 1 and lag.max() <= 121
    for alias, prefix in cfg["nation_aliases"].items():
        n = data[alias]
        assert list(n) == [prefix + c[2:] for c in cfg["nation_columns"]]
        assert cfg["nations"][n[prefix + "name"][6]] == "FRANCE"
        assert cfg["nations"][n[prefix + "name"][7]] == "GERMANY"


def test_q7_logical_bytes_at_sf1():
    cfg = H.load_json("configs", "tpch-q7-sf1.json")
    flows = H.load_module("configs", "tpch-q7-sf1.py")
    # lineitem's orderkey, suppkey, extendedprice, discount (8 bytes) and
    # shipdate (4); orders' orderkey and custkey (8); customer's and
    # supplier's key (8) and nationkey (4); each nation's key and name (4)
    assert flows.logical_bytes(cfg, 0) == (
        6_001_215 * 36 + 1_500_000 * 16 + 150_000 * 12 + 10_000 * 12
        + 2 * 25 * 8) == 241_964_140
    # each output row: two nation codes, the year and the revenue
    assert flows.logical_bytes(cfg, 4) == 241_964_140 + 4 * 20
    assert flows.rows_consumed(cfg) == 6_001_215


@pytest.mark.parametrize("cell", ["q15-sf1-pipeline", "q15-sf1-mesh4",
                                  "q7-sf1-pipeline"])
def test_plans_priced_reader_counts(cell):
    """The reader plans the cell's flow and reads the planner's counter,
    leaving the program's recorder off and empty."""
    import jax

    from repro import obs

    w = H.cell(H.benchmark(), cell)
    name = w["config"]
    cfg = tiny_config() if name == "tpch-q7-sf1" else tiny_config(name)
    r = H.Run(w, 5, 1.0, False, time.time(), jax.devices()[:1], config=cfg)
    ctx = H.ReadContext(r, None, {})
    got = H.load_module("metrics", "planner.plans_priced.py").read(ctx)
    assert isinstance(got, int) and got > 0
    assert obs.snapshot() == {"spans": {}, "counts": {}}
