"""`repro.obs`: host spans and counters are off until enabled and cost
nothing then; once on, spans nest with their parents, self times add up,
and the program's call boundaries show on the profiler's host plane."""

from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import flows
from repro.core.pipeline import ExecutableCache, compile_plan


@pytest.fixture
def recording():
    obs.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.reset()


def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: entered.append(name))
    obs.reset()
    assert obs.span("a") is obs.span("b")      # one shared no-op context
    with obs.span("a"):
        with obs.span("b"):
            obs.count("n", 3)
    assert entered == []
    assert obs.records() == []
    assert obs.snapshot() == {"spans": {}, "counts": {}}


def test_spans_nest_and_self_times_add_up(recording):
    with obs.span("outer"):
        with obs.span("inner"):
            with obs.span("leaf"):
                pass
        with obs.span("inner"):
            pass
    obs.count("n", 2)
    obs.count("n", 5)
    parents = {(r.name, r.parent) for r in obs.records()}
    assert parents == {("outer", None), ("inner", "outer"), ("leaf", "inner")}
    for r in obs.records():
        assert r.end >= r.start
    snap = obs.snapshot()
    s = snap["spans"]
    assert s["inner"]["count"] == 2 and s["outer"]["count"] == 1
    assert s["outer"]["total_s"] == pytest.approx(
        s["outer"]["self_s"] + s["inner"]["total_s"], abs=1e-9)
    assert s["inner"]["total_s"] == pytest.approx(
        s["inner"]["self_s"] + s["leaf"]["total_s"], abs=1e-9)
    assert s["leaf"]["self_s"] == pytest.approx(s["leaf"]["total_s"])
    assert snap["counts"] == {"n": 7}
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "counts": {}}


def test_pipeline_call_boundaries(recording):
    root, mk = flows.q15()
    cp = compile_plan(root, cache=ExecutableCache())
    staged = cp.bind_device(mk(2_000, seed=3))
    jax.block_until_ready(cp.run_device(staged))
    parents = {(r.name, r.parent) for r in obs.records()}
    assert {("compile", None), ("bind_device", None),
            ("prepare", "bind_device"), ("transfer", "bind_device"),
            ("run_device", None), ("lookup", "run_device"),
            ("dispatch", "run_device")} <= parents
    staged_bytes = sum(np.asarray(x).nbytes
                       for x in jax.tree_util.tree_leaves(staged))
    assert obs.snapshot()["counts"]["bind_bytes"] == staged_bytes


def test_run_device_on_the_profiler_host_plane(recording, tmp_path):
    """A CPU profiler trace holds `repro.run_device` on a host plane,
    inside the caller's own annotation and on the same clock."""
    from jax.profiler import ProfileData

    root, mk = flows.q15()
    cp = compile_plan(root, cache=ExecutableCache())
    staged = cp.bind_device(mk(2_000, seed=3))
    jax.block_until_ready(cp.run_device(staged))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("cb.window"):
            jax.block_until_ready(cp.run_device(staged))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    ((lo, hi),) = events["cb.window"]
    assert events["repro.run_device"]
    for s, e in events["repro.run_device"] + events["repro.dispatch"]:
        assert lo <= s <= e <= hi
