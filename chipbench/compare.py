"""The comparison that decides `correct`: one answer against the reference.

Answers are unordered row multisets (column name -> numpy array).  Columns
of an exact type (integers, booleans) must match exactly; floating-point
columns must match to the precision the configuration states, read as the
widest relative gap between a served value and the reference's.
"""

from __future__ import annotations

import collections

import numpy as np


def _is_float(a) -> bool:
    return np.issubdtype(np.asarray(a).dtype, np.floating)


def compare(got: dict, ref: dict) -> tuple:
    """`(rows_mismatched, rel_err)`: rows of either side whose exact
    columns find no partner on the other (a multiset difference; every row
    when the column sets differ), and the widest relative gap of the
    floating-point columns over the rows that pair up by their exact
    columns."""
    n_got = len(next(iter(got.values()))) if got else 0
    n_ref = len(next(iter(ref.values()))) if ref else 0
    if set(got) != set(ref):
        return n_got + n_ref, 0.0
    exact = sorted(c for c in ref if not _is_float(ref[c]))
    floats = sorted(c for c in ref if _is_float(ref[c]))

    def keyed(cols, n):
        keys = list(zip(*[np.asarray(cols[c]).tolist() for c in exact])) \
            if exact else [()] * n
        vals = np.stack([np.asarray(cols[c], np.float64) for c in floats], 1) \
            if floats else np.zeros((n, 0))
        return keys, vals

    gk, gv = keyed(got, n_got)
    rk, rv = keyed(ref, n_ref)
    cg, cr = collections.Counter(gk), collections.Counter(rk)
    mismatched = sum(((cg - cr) + (cr - cg)).values())
    if not floats:
        return mismatched, 0.0
    # pair rows whose exact key is unique on both sides
    where_g = {k: i for i, k in enumerate(gk) if cg[k] == 1}
    pairs = [(where_g[k], j) for j, k in enumerate(rk)
             if cr[k] == 1 and k in where_g]
    if not pairs:
        return mismatched, 0.0
    gi, ri = map(list, zip(*pairs))
    a, b = gv[gi], rv[ri]
    den = np.maximum(np.abs(b), np.finfo(np.float64).tiny)
    err = np.where(a == b, 0.0, np.abs(a - b) / den)
    return mismatched, float(np.max(err))


def host_columns(record_batch) -> dict:
    """A program's output batch as plain numpy columns (valid rows only)."""
    b = record_batch.to_numpy().compact()
    return {f: np.asarray(v) for f, v in b.columns.items()}
