"""The pipeline cell's set-up and window at a tiny size on the CPU, called
here and not through the command (which refuses to run off a TPU): the run
is correct, nothing compiles inside the window, and its per-layer readers
read.  Then the faults the cell can have, planted under a full run, and the
lower-precision control: each must come out not correct."""

from __future__ import annotations

import pytest

from chipbench.tests._util import (H, altered, correct, drive, half_left_out,
                                   tiny_run)


def test_pipeline_cell_rehearsal():
    r = tiny_run("q15-sf1-pipeline")
    out = drive(r)
    assert correct(out), out.checks
    assert r.window_compiles == 0
    assert out.attempted == out.counters["queries"] > 0
    assert out.end_to_end["rows_per_s"] > 0
    assert r.setup_s > 0 and r.spans["plan"] > 0


def test_pipeline_answer_altered(monkeypatch):
    from repro.core.pipeline import CompiledPlan

    run_device = CompiledPlan.run_device
    monkeypatch.setattr(CompiledPlan, "run_device",
                        lambda self, *a, **k: altered(
                            run_device(self, *a, **k)))
    out = drive(tiny_run("q15-sf1-pipeline"))
    assert not correct(out)
    assert out.checks["rows_mismatched"][0] > 0


def test_pipeline_half_the_batch_left_out(monkeypatch):
    from repro.core.pipeline import CompiledPlan

    bind = CompiledPlan.bind_device
    monkeypatch.setattr(CompiledPlan, "bind_device",
                        lambda self, b: half_left_out(bind(self, b)))
    out = drive(tiny_run("q15-sf1-pipeline"))
    assert not correct(out)


@pytest.mark.parametrize("seed", [2**31 + 1, 2**32 + 5, 7])
def test_table_control_fails_the_limit(seed):
    """A run with the reference's float32 control in the program's place
    (`run.py --control 1`) comes out not correct: its revenue is off by
    more than the limit."""
    r = tiny_run("q15-sf1-pipeline", seed=seed, control=True)
    out = drive(r)
    assert out.counters["control"]
    assert not correct(out)
    assert out.checks["rel_err"][0] > r.config["limits"]["rel_err"]


def test_readers_on_a_rehearsal():
    """Every per-layer reader of the pipeline cell reads a number from a
    rehearsal given a trace summary in the shape `trace.summarize` makes."""
    r = tiny_run("q15-sf1-pipeline")
    out = drive(r)
    n = out.counters["queries"]
    r.trace_summary = {"window_s": 1.0, "busy_s": {"TPU:0": 0.5},
                       "busy_mean_s": 0.5, "busiest": "TPU:0",
                       "collective_s": {"TPU:0": 0.0},
                       "device_ops": [], "idle_gaps": []}
    ctx = H.ReadContext(r, out, H.peaks("TPU v5 lite"))
    got = {m["name"]: H.load_module("metrics", f"{m['name']}.py").read(ctx)
           for m in H.metrics_for(H.benchmark(), "per_layer",
                                  "q15-sf1-pipeline")}
    assert got["pipeline.device_ms_per_query"] == pytest.approx(500.0 / n)
    assert got["device.idle_share.query"] == pytest.approx(50.0)
    assert 0 < got["pipeline_roofline"] < 100
    assert got["planner.optimize_s"] > 0
    assert got["compile.backend_s"] >= 0
