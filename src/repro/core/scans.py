"""Vectorized prefix-scan primitives for the masked executor's hot path.

XLA lowers `cumsum`/`cummax` over a length-n axis to an O(n·w) reduce-window
on CPU and `jax.ops.segment_*` to element-at-a-time scatters — both cost
hundreds of microseconds at serving-batch capacities, which is the dominant
per-batch cost once sorts are elided (DESIGN.md §8).  The primitives here
replace them with blocked two-level scans: reshape to (n/W, W), scan within
rows by log-depth shift-and-combine, then scan the O(n/W) row carries the
same way — O(n·log W) work with W=128, and everything stays fused
elementwise ops XLA compiles well on every backend (on the TPU, XLA's own
scans are reduce-windows that take tens of seconds to compile at 1M+
slots).

`segmented_scan` is the flag-stopped (Hillis–Steele) variant the sorted
segment reductions build on: log-depth shift-and-combine within rows, one
tiny cross-row pass for carries.  For `add` it performs tree summation — no
prefix-sum differencing, so there is no catastrophic cancellation on float
aggregates.

`select` finds the position of every set bit of a mask in order — the
compaction's pack and the sorted segments' boundaries — with one gather
and no loop once the mask is long (see `select`).  `search_sorted` is the
PK probe's sorted search: a bucket directory over the distinct keys, then a
binary search only as deep as the widest bucket, whose compares also tell
whether each query is a key (see `search_sorted`).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .. import obs
from ..obs import scope

_BLOCK = 128
# shorter masks keep the binary search.  This is no measured crossover:
# on a TPU v5e the blocked form wins down to 4,096 slots, on the CPU it
# loses at every size (PERF.md §6).  The floor sits above 32,768 because
# `chipbench/tests/test_chipbench_scopes.py` requires the 32,768-slot
# filter compaction of its tiny Q15 to hold a `while` loop.
_SELECT_MIN = 1 << 16
# shorter probe sides keep `jnp.searchsorted`.  `_SELECT_MIN`'s value, no
# measured crossover (PERF.md §6).
_PROBE_MIN = _SELECT_MIN

_OPS = {
    "add": jnp.add,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


def identity_for(op: str, dtype):
    if op == "add":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        info = jnp.finfo(dtype)
    else:
        info = jnp.iinfo(dtype)
    return jnp.asarray(info.min if op == "max" else info.max, dtype)


def _blockable(n: int) -> bool:
    return n >= 2 * _BLOCK and n % _BLOCK == 0


def pack_indices(valid: jnp.ndarray, capacity: int):
    """Gather indices of the stable valids-first prefix pack.

    Returns `(src, count)`: `src[i]` is the source slot of output slot `i`
    under the pack that moves valid rows to the front in original order
    (slots past `count` hold a clamped repeat of the last row and must be
    masked by the caller).  This is THE compaction inner loop — shared by
    `MaskedBatch.compact` and the megakernel's pruned interior compactions —
    a `select` of the valid slots, no comparator sort."""
    src = select(valid, capacity)
    return (jnp.minimum(src, valid.shape[0] - 1),
            jnp.sum(valid, dtype=jnp.int32))


def select(mask: jnp.ndarray, k: int) -> jnp.ndarray:
    """Position of the (i+1)-th set bit of `mask` for each `i < k`, or
    `n = len(mask)` where there is none: exactly
    `searchsorted(cumsum(mask), arange(1, k + 1))`, as int32.

    A mask of at least `_SELECT_MIN` slots takes the blocked form: dense
    scans over 128-slot blocks, a histogram of the block counts and one
    gather of `k` elements.  A shorter one keeps the binary search, a loop
    of ~log2(n) gathers of `k` elements.  Runs under the device scope
    `select`; each trace counts `select.blocked` or `select.search`."""
    n = mask.shape[0]
    with scope("select"):
        if n >= _SELECT_MIN and k > 0:
            obs.count("select.blocked", 1)
            return _select_blocked(mask, k)
        obs.count("select.search", 1)
        cv = cumsum(mask.astype(jnp.int32))
        return jnp.searchsorted(cv, jnp.arange(1, k + 1, dtype=jnp.int32))


def _select_blocked(mask, k):
    n, W = mask.shape[0], _BLOCK
    B = -(-n // W)
    # clear slots pad the mask to whole blocks and move no set bit
    m = jnp.pad(mask.astype(jnp.int32), (0, B * W - n))
    # within[b, j]: set bits of block b up to slot j; incl[b]: up to block b
    within = _row_scan(m.reshape(B, W), jnp.add)
    incl = cumsum(within[:, -1])
    # output i lies in block b(i) = #{b : incl[b] <= i} (B means none): a
    # histogram of the clamped block totals, then its prefix sum
    hist = jnp.zeros((k + 1,), jnp.int32).at[jnp.minimum(incl, k)].add(
        1, indices_are_sorted=True)
    blk = cumsum(hist[:k])
    # its rank within the block: i less the first output of its block
    i = jnp.arange(k, dtype=jnp.int32)
    starts = blk != jnp.pad(blk[:-1], (1, 0), constant_values=-1)
    rank = i - cummax(jnp.where(starts, i, 0))
    # table[b, r]: the slot of block b's (r+1)-th set bit, a fused
    # compare-and-count over the block
    r = jnp.arange(W, dtype=jnp.int32)
    table = (within[:, :, None] <= r).sum(1, dtype=jnp.int32).reshape(-1)
    at = table[jnp.minimum(blk, B - 1) * W + jnp.minimum(rank, W - 1)]
    return jnp.where(blk < B, blk * W + at, n)


def search_sorted(a: jnp.ndarray, x: jnp.ndarray, start=0):
    """`(pos, eq)`: `pos` is the left insertion point of each `x` in the
    nondecreasing `a`, clamped to at least `start` (0 <= start <= len(a)):
    exactly `max(jnp.searchsorted(a, x, side='left'), start)`, as int32.
    `eq` is whether `x` occurs in `a[start:]`, that is `pos < len(a)` and
    `a[pos] == x`, decided by the search with no gather of `a[pos]`; it is
    None where the plain search ran.

    Integer codes probed by at least `_PROBE_MIN` queries take a bucket
    directory (`_search_directory`): its in-bucket binary search is as deep
    as the widest bucket, one or a few steps on real keys, where the plain
    search takes ~log2(len(a)).  Float codes and shorter probe sides keep
    `jnp.searchsorted`; a `start` of 0 then adds no clamp.  Each trace
    counts `probe.directory` or `probe.search`."""
    ct = jnp.promote_types(a.dtype, x.dtype)
    if (jnp.issubdtype(ct, jnp.integer) and x.shape[0] >= _PROBE_MIN
            and a.shape[0] > 0):
        obs.count("probe.directory", 1)
        return _search_directory(a.astype(ct), x.astype(ct), start)
    obs.count("probe.search", 1)
    pos = jnp.searchsorted(a, x)
    if isinstance(start, int) and start == 0:
        return pos, None
    return jnp.maximum(pos, start), None


def _search_directory(a, x, start):
    """`search_sorted` through a directory over the distinct codes.

    The left insertion point of `x` in `a[start:]` is the first slot of the
    smallest distinct code >= `x`, so the search runs over the distinct
    codes (`_directory`).  Buckets are monotone in the code, so the
    answer's index among them lies in `[dir[b], dir[b + 1])` of the
    query's bucket `b`, found by a binary search of `steps` =
    `bit_length(widest bucket)` steps: exact for any data, and as deep as
    the plain search only when every code shares one bucket.  The steps
    compare offsets from `lo`, and 64-bit codes compare their low 32 bits
    while the shift is at most 32: a bucket's offsets then differ in those
    bits alone, and a 32-bit gather costs a third of a 64-bit one on a TPU
    v5e (PERF.md §6).

    The last step that moves `rgt` moves it to the answer, so its compare
    gives `eq`; where no step moves it, the answer is the first code of a
    later bucket (or none), greater than `x`.  `x < lo` has offset 0, as
    `lo` has, and is no hit."""
    if a.dtype.itemsize < 4:
        a, x = a.astype(jnp.int32), x.astype(jnp.int32)
    n = a.shape[0]
    U, d, ku, lo, offset, shift, dir_, steps = _directory(a, start)
    B = dir_.shape[0] - 1
    bx = jnp.minimum(offset(x) >> shift, B).astype(jnp.int32)
    init = (dir_[bx], dir_[jnp.minimum(bx + 1, B)], jnp.zeros(x.shape, bool))

    def search(narrow: bool):
        keys, kx = ku, offset(x)
        if narrow:
            keys, kx = keys.astype(jnp.uint32), kx.astype(jnp.uint32)

        def step(_, c):
            lft, rgt, eq = c
            mid = (lft + rgt) >> 1
            key = keys[jnp.minimum(mid, n - 1)]
            less = key < kx
            live = lft < rgt
            moves = live & ~less
            return (jnp.where(live & less, mid + 1, lft),
                    jnp.where(moves, mid, rgt),
                    jnp.where(moves, key == kx, eq))

        j, _, eq = lax.fori_loop(0, steps, step, init)
        return j, eq

    if a.dtype.itemsize == 4:
        j, eq = search(False)
    else:
        j, eq = lax.cond(shift <= 32, lambda: search(True),
                         lambda: search(False))
    return jnp.where(j < d, U[jnp.minimum(j, n - 1)], n), eq & (x >= lo)


def _directory(a, start):
    """The directory over the distinct codes of `a[start:]` (int32 or
    wider): `(U, d, ku, lo, offset, shift, dir, steps)`.

    `U` holds the slots of the `d` run starts of `a[start:]` (as `select`
    gives them: `n` past them) and `ku` their codes' offsets: a cummax fill
    of invalid slots repeats one code many times, and the repeats must not
    widen a bucket.  `offset(v)` is `v - lo`, with `lo = a[start]`, as an
    unsigned integer of the code's width (exact for every `v >= lo`, with
    no overflow near either end of the range), and 0 for `v < lo`.  A
    code's bucket is `offset >> shift`, `shift` the least that puts `a[-1]`
    in one of `B` (a power of two >= len(a)) buckets; codes past `a[-1]`
    may reach `B` and are clamped to it.  `dir[b]` (B + 1 entries) counts
    the distinct codes in buckets below `b`: a sorted-index histogram and
    its prefix sum.  `steps` is the widest bucket's bit length."""
    n = a.shape[0]
    ut = jnp.dtype(f"uint{8 * a.dtype.itemsize}")
    B = 1 << (n - 1).bit_length()
    start = jnp.asarray(start, jnp.int32)
    i = jnp.arange(n, dtype=jnp.int32)
    prev = jnp.concatenate([a[:1], a[:-1]])
    # the blocked select at any length: the short form's binary search
    # would put a loop back into the probe
    U = _select_blocked((i >= start) & ((i == start) | (a != prev)), n)
    d = jnp.sum(U < n, dtype=jnp.int32)
    lo = a[jnp.minimum(start, n - 1)]
    ulo = lax.bitcast_convert_type(lo, ut)

    def offset(v):
        return jnp.where(v < lo, jnp.zeros((), ut),
                         lax.bitcast_convert_type(v, ut) - ulo)

    span = lax.bitcast_convert_type(a[-1], ut) - ulo
    shift = jnp.maximum(8 * ut.itemsize - lax.clz(span).astype(jnp.int32)
                        - (B.bit_length() - 1), 0).astype(ut)
    ku = offset(a[jnp.minimum(U, n - 1)])
    bu = jnp.where(i < d, (ku >> shift).astype(jnp.int32), B)
    hist = jnp.zeros((B + 1,), jnp.int32).at[bu].add(
        1, indices_are_sorted=True)
    dir_ = jnp.concatenate([jnp.zeros((1,), jnp.int32), cumsum(hist[:B])])
    steps = 32 - lax.clz(jnp.max(hist[:B]))
    return U, d, ku, lo, offset, shift, dir_, steps


def cumsum(v: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum, blocked two-level (`_scan`)."""
    return _scan(v, jnp.add)


def cummax(v: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative max, blocked two-level (`_scan`)."""
    return _scan(v, jnp.maximum)


def _scan(v, fn):
    """Inclusive scan of a 1-D array: `_row_scan` within 128-wide rows (the
    last one padded out), then `_scan` of the row totals for the carries.
    Exact for integers and for `max`; float sums go through
    `segmented_scan`."""
    n = v.shape[0]
    if n <= _BLOCK:
        return _row_scan(v, fn)
    a = _row_scan(jnp.pad(v, (0, -n % _BLOCK)).reshape(-1, _BLOCK), fn)
    carry = _scan(a[:, -1], fn)[:-1, None]
    return jnp.concatenate([a[:1], fn(a[1:], carry)]).reshape(-1)[:n]


def _row_scan(a, fn):
    """Inclusive scan along the last axis by log-depth shift-and-combine;
    each step combines a slot with the one `s` before it, if any."""
    s = 1
    while s < a.shape[-1]:
        a = jnp.concatenate([a[..., :s], fn(a[..., s:], a[..., :-s])], axis=-1)
        s <<= 1
    return a


def segmented_scan(v: jnp.ndarray, flags: jnp.ndarray, op: str
                   ) -> jnp.ndarray:
    """Inclusive segmented scan: `out[i]` combines `v` over the run of slots
    since the last `flags`-marked position (inclusive).  `flags[i]` marks a
    RESET at `i` (a segment start); the caller pre-fills slots that must not
    contribute (invalid rows) with the op identity.

    Log-depth shift-and-combine within 128-wide rows plus one carry pass."""
    fn = _OPS[op]
    n = v.shape[0]
    ident = identity_for(op, v.dtype)
    if not _blockable(n):
        return _seg_scan_flat(v, flags, fn, ident)
    B, W = n // _BLOCK, _BLOCK
    a = v.reshape(B, W)
    f = flags.reshape(B, W)
    # "a segment start occurs at or before column j of this row" — decides
    # which slots a cross-row carry may reach.  The in-loop flag array below
    # additionally marks the shifted-in row boundary (col 0 has no left
    # neighbour), which must NOT count as a segment start here.
    fstop = jnp.cumsum(f.astype(jnp.int32), axis=1) > 0
    s = 1
    while s < W:
        pv = jnp.concatenate(
            [jnp.full((B, s), ident, a.dtype), a[:, :-s]], axis=1)
        pf = jnp.concatenate(
            [jnp.ones((B, s), bool), f[:, :-s]], axis=1)
        a = jnp.where(f, a, fn(a, pv))
        f = f | pf
        s <<= 1
    # cross-row carries: row r's carry is the scan of previous rows' last
    # columns, reset wherever a row contains any segment start
    cv = _seg_scan_flat(a[:, -1], fstop[:, -1], fn, ident)
    carry = jnp.concatenate([jnp.full((1,), ident, a.dtype), cv[:-1]])
    out = jnp.where(fstop, a, fn(a, carry[:, None]))
    return out.reshape(n)


def _seg_scan_flat(v, flags, fn, ident):
    n = v.shape[0]
    f = flags
    s = 1
    while s < n:
        pv = jnp.concatenate([jnp.full((s,), ident, v.dtype), v[:-s]])
        pf = jnp.concatenate([jnp.ones((s,), bool), f[:-s]])
        v = jnp.where(f, v, fn(v, pv))
        f = f | pf
        s <<= 1
    return v
