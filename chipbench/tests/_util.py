"""Shared helpers of the benchmark's tests: the program on the path, and a
`Run` of a cell at a tiny size on whatever devices JAX has."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness as H  # noqa: E402

TINY_ROWS = 24_000      # lineitem rows of a tiny Q15


# the mesh cell as its workload entry reads, so its driver stays tested
# whether or not BENCHMARK.json holds the cell
MESH_CELL = {"name": "q15-sf1-mesh4", "config": "tpch-q15-sf1",
             "traffic": "mesh-back-to-back", "chips": 4}


def tiny_run(workload, seconds: float = 1.0, seed: int = 2**31 + 77,
             trace: bool = False, control: bool = False) -> H.Run:
    """A `Run` of `workload` (a cell's name in BENCHMARK.json, or its
    entry) cut to a size a test can hold: a small Q15, with the parts per
    supplier of SF1."""
    import jax

    w = workload if isinstance(workload, dict) \
        else H.cell(H.benchmark(), workload)
    cfg = H.load_json("configs", f"{w['config']}.json")
    n_su = TINY_ROWS // 600
    cfg.update(lineitem_rows=TINY_ROWS, supplier_rows=n_su,
               part_rows=20 * n_su)
    return H.Run(w, seed, seconds, trace, time.time(),
                 jax.devices()[:w["chips"]], config=cfg, control=control)


def drive(r: H.Run) -> H.Outcome:
    return H.load_module("paths", f"{r.traffic['path']}.py").run(r)


def correct(outcome: H.Outcome) -> bool:
    return all(v <= lim for v, lim in outcome.checks.values())


def altered(out):
    """The same answer with one integer column moved by one."""
    import jax.numpy as jnp

    from repro.core import masked as M

    cols = dict(out.columns)
    name = next(n for n, v in cols.items()
                if jnp.issubdtype(v.dtype, jnp.integer))
    cols[name] = cols[name] + 1
    return M.MaskedBatch(cols, out.valid, out.order)


def half_left_out(staged: dict) -> dict:
    """The largest bound source with the second half of its rows left out."""
    import jax.numpy as jnp

    from repro.core import masked as M

    name = max(staged, key=lambda n: staged[n].capacity)
    b = staged[name]
    keep = jnp.arange(b.capacity) < b.capacity // 2
    return dict(staged, **{name: M.MaskedBatch(dict(b.columns),
                                               b.valid & keep, b.order)})
