"""The benchmark's queries at a tiny size: which Reduces size their group
arrays from the group estimate (`reduce.segments_sized`), and the answers
against the plain reference, on the sized branch and on the fallback."""

import pytest

from repro import obs
from repro.core import masked as M


def _sized(run) -> tuple:
    """`(correct, reduce.segments_sized, reduce.sort)` of one tiny run
    through the cell's path."""
    from chipbench.tests._util import correct, drive

    obs.reset()
    obs.enable()
    try:
        out = drive(run)
        counts = obs.snapshot()["counts"]
    finally:
        obs.disable()
        obs.reset()
    return (correct(out), counts.get("reduce.segments_sized", 0),
            counts.get("reduce.sort", 0))


def _tiny(cell: str):
    if cell == "q21":
        from chipbench.tests.test_chipbench_q21 import tiny_q21_run

        return tiny_q21_run()
    if cell == "q7":
        from chipbench.tests.test_chipbench_q7 import tiny_q7_run

        return tiny_q7_run()
    from chipbench.tests._util import tiny_run

    return tiny_run("q15-sf1-pipeline")


@pytest.mark.parametrize("cell, sized", [
    ("q21", 1),     # OrderWaits, the group filter
    ("q15", 0),     # every Reduce emits one slot per group
    ("q7", 0),
])
def test_tiny_query_sizes_only_its_group_filter(cell, sized):
    ok, n_sized, _ = _sized(_tiny(cell))
    assert ok
    assert n_sized == sized


def test_tiny_q21_fallback_is_exact(monkeypatch):
    """With OrderWaits' segments cut to 8, far under its 15,000 orders, the
    fallback runs and the answer is still the reference's; the order
    elision holds either way (`NumWait`'s one sort)."""
    capacity = M.segment_capacity
    monkeypatch.setattr(M, "segment_capacity", lambda *a, **k: (
        None if capacity(*a, **k) is None else 8))
    ok, n_sized, sorts = _sized(_tiny("q21"))
    assert ok
    assert n_sized == 1
    assert sorts == 1
