"""Plain reference for Q15 (Top Supplier), in numpy float64; it imports
nothing of the program.

`control` is the same query computed a step below the precision the
configuration states — the revenue summed and its maximum taken in float32
on the default JAX device.  A run puts it in the program's place
(`run.py --control 1`) to show that the comparison fails it.
"""

from __future__ import annotations

import numpy as np


def _filtered(cfg: dict, li: dict):
    lo, hi = cfg["ship_window"]
    keep = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
    keys, inv = np.unique(li["l_suppkey"][keep], return_inverse=True)
    return keep, keys, inv


def _top(keys, rev, su: dict) -> dict:
    """The suppliers whose revenue equals the maximum, with Q15's columns."""
    top = rev == rev.max()
    idx = np.searchsorted(su["s_suppkey"], keys[top])
    return {"s_suppkey": su["s_suppkey"][idx], "s_name": su["s_name"][idx],
            "s_address": su["s_address"][idx], "s_phone": su["s_phone"][idx],
            "total_revenue": rev[top].astype(np.float64)}


def reference(cfg: dict, data: dict) -> dict:
    li, su = data["lineitem"], data["supplier"]
    keep, keys, inv = _filtered(cfg, li)
    val = li["l_extendedprice"][keep] * (1.0 - li["l_discount"][keep])
    rev = np.bincount(inv, weights=val, minlength=len(keys))
    return _top(keys, rev, su)


def control(cfg: dict, data: dict) -> dict:
    import jax
    import jax.numpy as jnp

    li, su = data["lineitem"], data["supplier"]
    keep, keys, inv = _filtered(cfg, li)
    ext = jnp.asarray(li["l_extendedprice"][keep], jnp.float32)
    disc = jnp.asarray(li["l_discount"][keep], jnp.float32)
    rev = jax.ops.segment_sum(ext * (jnp.float32(1) - disc),
                              jnp.asarray(inv, jnp.int32),
                              num_segments=len(keys))
    return _top(keys, np.asarray(rev), su)
