"""Observability: device scopes, host spans and counters.

* `scope(name)` is `jax.named_scope`.  It acts while a program traces and
  writes the name into each HLO instruction's `metadata.op_name`, so a
  profiler trace (TensorBoard, Perfetto, `jax.profiler.ProfileData`) names
  every device operation by the plan stage and the mechanism that built it.
  It changes no computation and costs nothing when the program runs.
* `span(name)` is a host span, off by default: a shared no-op context behind
  one flag check.  Once `enable()` is called, a span enters
  `jax.profiler.TraceAnnotation("repro.<name>")` (the profiler's host plane,
  on the device ops' clock) and records `(name, start, end, parent)` in
  memory, the parent being the span open around it on the same thread.
* `count(name, n)` adds to a counter while spans are on.

Nothing is written out: the caller reads `snapshot()` (per span name: total
seconds, self seconds — the total less what child spans cover — and count;
and the counters), or `records()` for the spans themselves, and `reset()`
clears both.  Records accumulate until then.

Scope names used by the program: `stage.<kind>.<top operator>` around each
lowered stage (the operator names `pipeline.stage_key` uses), and inside
them `compact`, `sort`, `probe`, `segment` (a Reduce's segmentation, group
aggregates and group-mask broadcast) and `wire`.  A Reduce's trace counts
`reduce.sorted` (its input order covers the key) or `reduce.sort`, and
`reduce.group_filter` per group-masked passthrough emission; each traced
log-depth segmented scan counts `segment.scan`.  Span names: `optimize`
(counters `optimize.groups`, the logical groups of the plan-space memo
built, and `optimize.priced`, the physical alternatives the cost model
priced), `compile`, `bind_device` (children `prepare`, `transfer`;
counter `bind_bytes`), `run_device` (children `lookup`, `dispatch`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Optional

import jax

SPAN_PREFIX = "repro."

scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed host span; times are `time.perf_counter()` seconds."""

    name: str
    start: float
    end: float
    parent: Optional[str]


class Recorder:
    """Host spans and counters, kept in memory while `on`."""

    def __init__(self):
        self.on = False
        self._mu = threading.Lock()
        self._local = threading.local()
        self._records: list = []
        self._totals: dict = {}     # name -> [total_s, self_s, count]
        self._counts: dict = {}

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str):
        stack = self._stack()
        frame = [name, 0.0]        # name, seconds covered by child spans
        parent = stack[-1][0] if stack else None
        stack.append(frame)
        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            end = time.perf_counter()
            stack.pop()
            dur = end - start
            if stack:
                stack[-1][1] += dur
            with self._mu:
                self._records.append(Span(name, start, end, parent))
                t = self._totals.setdefault(name, [0.0, 0.0, 0])
                t[0] += dur
                t[1] += dur - frame[1]
                t[2] += 1

    def count(self, name: str, n) -> None:
        with self._mu:
            self._counts[name] = self._counts.get(name, 0) + n

    def snapshot(self) -> dict:
        with self._mu:
            return {"spans": {n: {"total_s": t[0], "self_s": t[1],
                                  "count": t[2]}
                              for n, t in self._totals.items()},
                    "counts": dict(self._counts)}

    def records(self) -> list:
        with self._mu:
            return list(self._records)

    def reset(self) -> None:
        with self._mu:
            self._records.clear()
            self._totals.clear()
            self._counts.clear()


_REC = Recorder()
_OFF = contextlib.nullcontext()


def span(name: str):
    """Host span `name` around a block; a no-op until `enable()`."""
    if not _REC.on:
        return _OFF
    return _REC.span(name)


def count(name: str, n) -> None:
    """Add `n` to counter `name`; a no-op until `enable()`."""
    if _REC.on:
        _REC.count(name, n)


def enable() -> None:
    _REC.on = True


def disable() -> None:
    _REC.on = False


def snapshot() -> dict:
    return _REC.snapshot()


def records() -> list:
    return _REC.records()


def reset() -> None:
    _REC.reset()
