"""The back-to-back query loop the pipeline and mesh drivers share."""

from __future__ import annotations

import collections
import time

import numpy as np


def back_to_back(r, call, in_flight: int, caches=()) -> tuple:
    """Run `call()` (a device-resident query, dispatched asynchronously)
    back to back for `r.seconds` with at most `in_flight` queries
    outstanding, blocking on each result in turn; the window closes when
    the last dispatched query has completed, so every query counted
    finished inside it.  Keeps the last output and one drawn from the
    seed (reservoir of one) for the comparison.

    Returns `(completed, seconds, kept_outputs)`."""
    import jax

    rng = np.random.default_rng([r.seed, 1])
    pending: collections.deque = collections.deque()
    kept, last = None, None
    done = 0

    def finish():
        nonlocal kept, last, done
        out = pending.popleft()
        with r.span("fetch"):
            jax.block_until_ready(out)
        if rng.random() * (done + 1) < 1.0:
            kept = out
        last = out
        done += 1

    with r.window(caches):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < r.seconds:
            with r.span("run_device"):
                pending.append(call())
            if len(pending) >= in_flight:
                finish()
        while pending:
            finish()
        seconds = time.perf_counter() - t0
    return done, seconds, [o for o in (kept, last) if o is not None]
