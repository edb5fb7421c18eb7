"""Device time by stage and mechanism (`chipbench/stages.py`) on small
traces: each busy instant counts once, under the outermost operation, and
scoped plus unscoped seconds are the chip's busy seconds; program spans'
self times and the idle time under them."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import stages as S
from chipbench import trace as T

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MS = 1_000_000  # ns
FILTER = "jit(_body)/stage.chain.Filter"
AGG = "jit(_body)/stage.reduce.Agg"


def _loaded():
    # window [0, 100) ms.  TPU:0: the filter's compaction loop [10, 40)
    # with two body fusions nested in it, a fusion sticking out of the loop
    # [35, 45), the aggregate's sort [50, 60), one more aggregate fusion
    # [60, 62) and an unscoped copy [70, 75).  TPU:1 is less busy.
    ops0 = [
        (10 * MS, 40 * MS, "while.1:while",
         FILTER + "/compact/jit(searchsorted)/while"),
        (11 * MS, 20 * MS, "fusion.2:fusion",
         FILTER + "/compact/jit(searchsorted)/while/body/gather"),
        (20 * MS, 39 * MS, "fusion.3:fusion",
         FILTER + "/compact/jit(searchsorted)/while/body/add"),
        (35 * MS, 45 * MS, "fusion.4:fusion", FILTER + "/and"),
        (50 * MS, 60 * MS, "sort.5:sort", AGG + "/sort/sort"),
        (60 * MS, 62 * MS, "fusion.6:fusion", AGG + "/add"),
        (70 * MS, 75 * MS, "copy.7:copy", ""),
    ]
    ops1 = [(0, 5 * MS, "fusion.1:fusion", FILTER + "/and")]
    spans = S._depths([(0, 48 * MS, "run_device"),
                       (1 * MS, 2 * MS, "lookup"),
                       (2 * MS, 47 * MS, "dispatch"),
                       (76 * MS, 90 * MS, "run_device"),
                       (77 * MS, 80 * MS, "dispatch")], "main")
    return {"devices": {"TPU:0": ops0, "TPU:1": ops1}, "spans": spans,
            "window": (0, 100 * MS)}


def test_each_instant_once_under_the_outermost_op():
    a = S.attribute(_loaded())
    assert a["busiest"] == "TPU:0"
    assert a["busy_s"] == pytest.approx(0.052)
    assert a["mechanism_s"] == pytest.approx(
        {"compact": 0.030, "sort": 0.010, "probe": 0.0, "wire": 0.0,
         "other": 0.007})
    assert a["unscoped_s"] == pytest.approx(0.005)
    assert a["stage_s"] == pytest.approx(
        {"stage.chain.Filter": 0.035, "stage.reduce.Agg": 0.012})
    assert sum(a["mechanism_s"].values()) + a["unscoped_s"] == \
        pytest.approx(a["busy_s"])


def test_busy_agrees_with_the_trace_reduction():
    ld = _loaded()
    t = T.Trace({d: [o[:3] for o in ops] for d, ops in ld["devices"].items()},
                [(0, 100 * MS, "window")])
    s = T.summarize(t)
    a = S.attribute(ld)
    assert a["busy_s"] == pytest.approx(s["busy_s"][s["busiest"]])


def test_program_spans_self_time_and_idle():
    a = S.attribute(_loaded())
    assert a["span_self_s"] == pytest.approx(
        {"run_device": 0.002 + 0.011, "lookup": 0.001, "dispatch": 0.048})
    # TPU:0's gaps: [0,10) under run_device 1 ms, lookup 1, dispatch 8;
    # [45,50) dispatch 2, run_device 1, none 2; [62,70) none 8; [75,100)
    # none 1 + 10, run_device 1 + 10, dispatch 3
    assert a["idle_by_span_s"] == pytest.approx(
        {"run_device": 0.013, "lookup": 0.001, "dispatch": 0.013,
         "none": 0.021})
    assert sum(a["idle_by_span_s"].values()) == pytest.approx(
        a["window_s"] - a["busy_s"])


def test_scope_paths():
    assert S.scope_of(FILTER + "/compact/jit(searchsorted)/while") == \
        ("stage.chain.Filter", "compact")
    assert S.scope_of("jit(run)/stage.match.J/wire/sort/x") == \
        ("stage.match.J", "sort")
    assert S.scope_of("jit(run)/wire/psum") == (None, "wire")
    assert S.scope_of("") == (None, None)


def test_hlo_scopes():
    text = ('  %while.23 = (s32[], s32[8]) while(%t), condition=%c, '
            'body=%b, metadata={op_name="jit(_body)/stage.chain.F/compact/'
            'while" stack_frame_id=4}\n'
            '  ROOT %fusion.1 = f64[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(_body)/stage.reduce.R/add"}\n'
            '  %copy.2 = f64[8]{0} copy(%x)\n')
    assert S.hlo_scopes(text) == {
        "while.23": "jit(_body)/stage.chain.F/compact/while",
        "fusion.1": "jit(_body)/stage.reduce.R/add"}


def test_recorded_chip_trace_without_scopes():
    """The recorded chip trace (made before the program had scopes): every
    busy instant is unscoped, and the busy time is the trace reduction's."""
    with open(os.path.join(FIXTURES, "q15_sf1_two_calls.json")) as f:
        rec = json.load(f)
    devices = {d: [tuple(o) + ("",) for o in ops]
               for d, ops in rec["trace"]["devices"].items()}
    a = S.attribute({"devices": devices, "spans": [],
                     "window": tuple(rec["trace"]["host"][0][:2])})
    assert a["busy_s"] == pytest.approx(rec["summary"]["busy_s"]["TPU:0"])
    assert a["unscoped_s"] == pytest.approx(a["busy_s"])
    assert a["stage_s"] == {}


def test_load_reads_program_spans_from_a_profiler_trace(tmp_path):
    """On a CPU profiler trace (no TPU plane): the window, and the
    program's spans with their nesting depth on their thread."""
    import jax

    from chipbench.tests._util import tiny_run
    from repro import obs
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache
    from repro.core.record import batch_from_dict

    r = tiny_run("q15-sf1-pipeline")
    cp = optimize(r.flows.flow(r.config)).compile(cache=ExecutableCache())
    data = r.flows.generate(r.config, r.seed)
    staged = cp.bind_device({n: batch_from_dict(c) for n, c in data.items()})
    jax.block_until_ready(cp.run_device(staged))
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("cb.window"):
            jax.block_until_ready(cp.run_device(staged))
    finally:
        jax.profiler.stop_trace()
        obs.disable()
        obs.reset()
    ld = S.load(T.find_xplane(str(tmp_path)), "")
    assert ld["devices"] == {}
    lo, hi = ld["window"]
    depth = {(n, d) for s, e, n, d, _ in ld["spans"] if lo <= s <= e <= hi}
    assert depth == {("run_device", 0), ("lookup", 1), ("dispatch", 1)}
