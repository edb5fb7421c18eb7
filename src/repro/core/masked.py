"""jit-safe masked executor — flows under XLA static shapes.

Stratosphere streams records of dynamic cardinality; XLA requires static
shapes.  The adaptation (DESIGN.md §3.2): every intermediate data set is a
`MaskedBatch` — fixed-capacity columns + a validity mask.  Filters flip mask
bits; grouping uses sort + segment reductions with a static segment count;
PK joins use sorted-search probes.  `compact()` re-packs valid rows to a
smaller static capacity chosen by the optimizer's cardinality estimate.

This is what lets a PACT flow run *inside* jit/shard_map — e.g. on-device
record preprocessing fused ahead of a train step — which the paper's Java
runtime could not express at all.

Order-aware execution (DESIGN.md §8): every `MaskedBatch` carries trace-time
static ORDER metadata (`order`: the column prefix its valid rows are sorted
on).  Sources propagate `Source.sorted_on`, record-wise operators preserve
whatever the UDF does not write, and a Reduce emits key-ordered output — so
`_exec_reduce`, the PK-probe side of `_exec_match_pk` and `_exec_cogroup`
skip their lexsorts whenever the input is already ordered.  Compaction is a
prefix-sum pack (cumsum over the mask → monotone positions → gather), linear
apart from a vectorized binary search, and stable by construction, so it
PRESERVES sort order — the property that lets order survive stage
boundaries.

Segment reductions run through `udf.JitSegmentOps` and PK probes through
`scans.search_sorted`: one composed XLA body per operator on every backend.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import invoke, scans
from ..obs import count, muted, scope
from .cost import estimate
from .operators import (CoGroupOp, CrossOp, LimitOp, MapOp, MatchOp, Node,
                        ReduceOp, Source)
from .record import RecordBatch
from .reorder import eff_writes
from .udf import JitSegmentOps, KatEmit


# ---------------------------------------------------------------------------
# Order metadata (static, trace-time)
# ---------------------------------------------------------------------------
def order_prefix(order: Sequence[str], fields, writes=frozenset()) -> tuple:
    """Longest prefix of `order` that survives projection to `fields` and is
    not clobbered by `writes`.  Sortedness is lexicographic, so it only
    survives as a PREFIX: once a column is dropped or rewritten, everything
    after it stops meaning anything."""
    out = []
    for k in order:
        if k not in fields or k in writes:
            break
        out.append(k)
    return tuple(out)


def order_covers(order: Sequence[str], key: Sequence[str]) -> bool:
    """Does `order` guarantee rows with equal `key` are contiguous?  True iff
    some prefix of `order` is a permutation of `key` (column names are unique,
    so that prefix has exactly `len(key)` entries)."""
    return (len(key) > 0 and len(order) >= len(key)
            and set(order[:len(key)]) == set(key))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MaskedBatch:
    """Fixed-capacity struct-of-arrays + validity mask (a pytree).

    `order` is STATIC aux data (part of the pytree structure, so traces with
    different order assumptions never unify): the subsequence of valid rows
    is lexicographically nondecreasing on this column-name prefix.  `()`
    means no known order.  Validity gaps are allowed — order claims nothing
    about invalid slots."""

    columns: dict
    valid: jnp.ndarray  # bool[capacity]
    order: tuple = ()

    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return (tuple(self.columns[n] for n in names) + (self.valid,),
                (names, self.order))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        names, order = aux
        return cls(columns=dict(zip(names, leaves[:-1])), valid=leaves[-1],
                   order=order)

    def with_order(self, order: Sequence[str]) -> "MaskedBatch":
        """Same data, annotated with a (caller-guaranteed) sort order."""
        order = order_prefix(order, self.columns.keys())
        if order == self.order:
            return self
        return MaskedBatch(self.columns, self.valid, order)

    @staticmethod
    def from_record_batch(b: RecordBatch, capacity: Optional[int] = None,
                          order: Sequence[str] = ()) -> "MaskedBatch":
        b = b.to_numpy().compact()
        n = b.capacity
        cap = capacity or max(n, 1)
        cols = {}
        for f in b.fields:
            v = np.asarray(b.columns[f])
            pad = np.zeros((cap - n,) + v.shape[1:], dtype=v.dtype)
            cols[f] = jnp.asarray(np.concatenate([v, pad]))
        valid = jnp.asarray(np.arange(cap) < n)
        return MaskedBatch(cols, valid,
                           order_prefix(order, b.fields))

    def to_record_batch(self) -> RecordBatch:
        cols = {k: np.asarray(v) for k, v in self.columns.items()}
        return RecordBatch(cols, np.asarray(self.valid)).compact()

    def compact(self, capacity: int) -> "MaskedBatch":
        """Re-pack valid rows first and truncate/grow to `capacity`.

        Prefix-sum pack (`scans.pack_indices`): `scans.select(valid)`
        gives each output slot's source row, then one gather per column —
        no comparator sort.  Stable by construction (positions are strictly
        increasing in source order), so it PRESERVES `order`; slots past the
        valid count hold clamped garbage under valid=False."""
        with scope("compact"):
            src, count = scans.pack_indices(self.valid, capacity)
            cols = {k: v[src] for k, v in self.columns.items()}
            valid = jnp.arange(capacity, dtype=jnp.int32) < count
        return MaskedBatch(cols, valid, self.order)


def _compact_perm(valid: jnp.ndarray) -> jnp.ndarray:
    """The stable valids-first PERMUTATION of all slots (valid rows in
    original order, then invalid rows in original order) — what
    `argsort(~valid, stable=True)` computes, via two `scans.select`s
    instead of a comparator sort: the valid slots, then the invalid ones
    rotated to follow them."""
    n = valid.shape[0]
    j = jnp.arange(n, dtype=jnp.int32)
    nv = jnp.sum(valid, dtype=jnp.int32)
    pv = scans.select(valid, n)
    pi = jnp.roll(scans.select(~valid, n), nv)
    return jnp.where(j < nv, pv, pi).astype(jnp.int32)


def _concat(batches: Sequence[MaskedBatch]) -> MaskedBatch:
    if len(batches) == 1:
        return batches[0]
    fields = batches[0].columns.keys()
    cols = {f: jnp.concatenate([b.columns[f] for b in batches]) for f in fields}
    # interleaving parts destroys any one part's order
    return MaskedBatch(cols, jnp.concatenate([b.valid for b in batches]))


def _project(cols: Mapping, schema, n: int) -> dict:
    out = {}
    for f in schema.fields:
        v = jnp.asarray(cols[f])
        if v.ndim == 0:
            v = jnp.broadcast_to(v, (n,))
        out[f] = v.astype(schema.dtype(f))
    return out


# ---------------------------------------------------------------------------
# Grouping machinery (static shapes)
# ---------------------------------------------------------------------------
def _segments_contiguous(cols: Mapping, key: Sequence[str], valid):
    """Segment fields for rows already arranged valids-first and key-sorted
    (the post-`_sort_by_key` layout): adjacent-slot key compares suffice."""
    cap = valid.shape[0]
    same = jnp.ones(cap, bool)
    for k in key:
        kv = jnp.asarray(cols[k])
        same = same & jnp.concatenate([jnp.zeros(1, bool), kv[1:] == kv[:-1]])
    prev_valid = jnp.concatenate([jnp.zeros(1, bool), valid[:-1]])
    is_start = valid & (~same | ~prev_valid)
    seg = jnp.maximum(scans.cumsum(is_start.astype(jnp.int32)) - 1, 0)
    return seg, is_start


def _segments_gappy(cols: Mapping, key: Sequence[str], valid):
    """Segment fields for key-ordered rows with validity GAPS: each valid row
    compares against the previous VALID row's key (a cummax scan finds it),
    so interspersed invalid slots neither split nor merge groups.  Returned
    `seg` is nondecreasing over ALL slots (invalid slots inherit the previous
    group), as the segment-scan kernels require."""
    cap = valid.shape[0]
    i32 = jnp.arange(cap, dtype=jnp.int32)
    pvi = scans.cummax(jnp.where(valid, i32, jnp.int32(-1)))
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), pvi[:-1]])
    pidx = jnp.maximum(prev, 0)
    differs = prev < 0
    for k in key:
        kv = jnp.asarray(cols[k])
        differs = differs | (kv != kv[pidx])
    is_start = valid & differs
    seg = jnp.maximum(scans.cumsum(is_start.astype(jnp.int32)) - 1, 0)
    return seg, is_start


def _sort_by_key(b: MaskedBatch, key: Sequence[str]):
    """Valid rows first, ordered by composite key.  Returns (sorted batch,
    segment_ids, is_start).  Single-key inputs sort one sentinel code (a
    cheaper single-operand sort; the gap-tolerant segmentation makes a
    sentinel collision with a genuine max-value key harmless)."""
    with scope("sort"):
        if len(key) == 1:
            kv = jnp.asarray(b.columns[key[0]])
            big = (jnp.finfo(kv.dtype).max if jnp.issubdtype(kv.dtype, jnp.floating)
                   else jnp.iinfo(kv.dtype).max)
            code = jnp.where(b.valid, kv, big)
            _, order = jax.lax.sort_key_val(
                code, jnp.arange(b.capacity, dtype=jnp.int32))
        else:
            keys = tuple(jnp.asarray(b.columns[k]) for k in key)
            order = jnp.lexsort(tuple(reversed(keys)) + (~b.valid,))
        cols = {f: v[order] for f, v in b.columns.items()}
        valid = b.valid[order]
    segments = _segments_gappy if len(key) == 1 else _segments_contiguous
    with scope("segment"):
        seg, is_start = segments(cols, key, valid)
    return MaskedBatch(cols, valid, tuple(key)), seg, is_start


def planned_capacity(node: Node, stats_memo: dict, slack: float,
                     scale: float = 1.0, shards: int = 1) -> int:
    """Bucketed compaction capacity for `node`'s output under the current
    cardinality estimate (`estimate * slack * scale / shards`, floored at 8).
    `shards` doubles as the estimator's degree of parallelism so a combiner's
    per-shard capacity covers the worst case of every group present on every
    worker.  Exposed separately from `compact_to_estimate` so the observing
    pipeline can record the capacity each stage was priced at — the
    reference point for runtime truncation detection (DESIGN.md §9)."""
    est = estimate(node, stats_memo, dop=shards).rows / shards * scale
    # variance guard: actual cardinalities fluctuate ~Poisson around the
    # estimate, so the multiplicative slack alone under-provisions SMALL
    # estimates (std/mean ~ 1/sqrt(est)).  Taking the max of the two terms
    # (rather than stacking them) keeps worst-case-bound estimates like the
    # combiner's `groups * dop` from being inflated past their bound.
    rows = max(est * slack, est + 4.0 * np.sqrt(max(est, 0.0)))
    return int(max(bucket_capacity(rows), 8))


# Reduce emissions whose every output row is an input row: the segment count
# sizes only the per-group arrays inside the stage, never its output
_ROW_EMITS = (KatEmit.PASSTHROUGH, KatEmit.PASSTHROUGH_FILTER)


def segment_capacity(node: Node, stats_memo: dict,
                     scale: float = 1.0) -> Optional[int]:
    """Static segment count for a row-aligned Reduce (emit kind
    `PASSTHROUGH` or `PASSTHROUGH_FILTER`): the bucketed group estimate
    under `planned_capacity`'s variance guard, `est + 4·sqrt(est)`, floored
    at 8.  None for every other node: a per-group Reduce emits one slot per
    segment, so its segment count is its output capacity.  No multiplicative
    slack: `_exec_reduce` falls back to the full count when more groups
    arrive, so the estimate buys speed, never exactness."""
    if not isinstance(node, ReduceOp) or node.props.kat_emit not in _ROW_EMITS:
        return None
    est = estimate(node, stats_memo).distinct * scale
    return int(bucket_capacity(max(est + 4.0 * np.sqrt(est), 8.0)))


def compact_to_estimate(b: "MaskedBatch", node: Node, stats_memo: dict,
                        slack: float, scale: float = 1.0,
                        shards: int = 1) -> "MaskedBatch":
    """Compact `b` to `planned_capacity` — the single compaction policy
    shared by the per-op masked walk, the compiled pipeline and the
    distributed per-shard body."""
    cap = min(b.capacity, planned_capacity(node, stats_memo, slack, scale,
                                           shards))
    return b.compact(cap) if cap < b.capacity else b


def cardinality_scale(root: Node, bindings: Mapping[str, "MaskedBatch"]) -> float:
    """Upward correction for cost-model row estimates when bound batches
    exceed a Source's declared `num_records`.  Capacities are static, so the
    factor is trace-time static too; it never scales below 1 — estimates
    generous relative to the actual data are already bounded by
    `min(b.capacity, ...)` at every compaction site."""
    s = 1.0
    for node in root.iter_nodes():
        if isinstance(node, Source) and node.name in bindings:
            s = max(s, bindings[node.name].capacity
                    / max(node.num_records, 1))
    return s


# ---------------------------------------------------------------------------
# Per-operator execution
# ---------------------------------------------------------------------------
def _exec_map(op: MapOp, b: MaskedBatch) -> MaskedBatch:
    col = invoke.run_map_udf(op.udf, dict(b.columns))
    out_order = order_prefix(b.order, op.out_schema.fields, eff_writes(op))
    parts = []
    for em in col.emissions:
        if em.builder is None:
            continue
        cols = _project(em.builder.columns(), op.out_schema, b.capacity)
        valid = b.valid
        if em.where is not None:
            valid = valid & jnp.asarray(em.where).astype(bool)
        # emissions are slot-aligned with the input, so a where-mask only
        # opens validity gaps — the valid subsequence stays ordered
        parts.append(MaskedBatch(cols, valid, out_order))
    if not parts:
        return MaskedBatch(
            {f: jnp.zeros(1, op.out_schema.dtype(f)) for f in op.out_schema.fields},
            jnp.zeros(1, bool))
    return _concat(parts)


def _exec_reduce(op: ReduceOp, b: MaskedBatch, use_order: bool = True,
                 obs: Optional[dict] = None,
                 contiguous: bool = False,
                 segment_cap: Optional[int] = None) -> MaskedBatch:
    """`obs`, when given, receives the traced observed group count under
    key "groups" — the stage-boundary statistic the adaptive feedback loop
    calibrates `distinct_keys` from (DESIGN.md §9).  It costs one reduction
    over a mask already computed for segment numbering.

    `contiguous` asserts the caller just PACKED `b` (valid rows form a
    prefix, e.g. a megakernel interior compaction, DESIGN.md §10): when the
    order also covers the key, segmentation uses adjacent-slot compares
    instead of the gap-tolerant cummax walk.  On a valids-first batch the
    two produce identical `(seg, is_start)` arrays — the previous valid row
    IS the adjacent slot — so results are bit-identical, minus the cummax
    and the gather it feeds.

    `segment_cap` (`segment_capacity`, a static int) sizes the group arrays
    of a row-aligned Reduce below its input capacity: the UDF runs over
    `segment_cap` segments when the live group count fits, and over
    `b.capacity` (every valid row its own group) when it does not — a
    `lax.cond` on the group count, so the answer is exact for any data and
    any estimate, and a wrong estimate costs only the full count's time.
    Each trace that takes the pair counts `reduce.segments_sized`; the
    fallback branch traces with counters muted, so every other counter
    still counts once per Reduce."""
    key = tuple(op.key)
    if use_order and order_covers(b.order, key):
        # input already groups equal keys contiguously: segment directly over
        # the (possibly gappy) slots, no sort, no repack
        count("reduce.sorted", 1)
        sb = b
        segments = _segments_contiguous if contiguous else _segments_gappy
        with scope("segment"):
            seg, is_start = segments(b.columns, key, b.valid)
        base_order = b.order
    else:
        count("reduce.sort", 1)
        sb, seg, is_start = _sort_by_key(b, key)
        base_order = key
    ngroups = jnp.sum(is_start)
    if obs is not None:
        obs["groups"] = ngroups.astype(jnp.int32)

    def body(nseg: int, sb, seg, is_start):
        return _reduce_groups(op, sb, seg, is_start, ngroups, nseg,
                              base_order)

    if segment_cap is None or segment_cap >= b.capacity \
            or op.props.kat_emit not in _ROW_EMITS:
        # worst case: every valid row its own group
        return body(b.capacity, sb, seg, is_start)
    count("reduce.segments_sized", 1)

    def full(*args):
        with muted():
            return body(b.capacity, *args)

    return jax.lax.cond(ngroups <= segment_cap,
                        functools.partial(body, segment_cap), full,
                        sb, seg, is_start)


def _reduce_groups(op: ReduceOp, sb: MaskedBatch, seg, is_start, ngroups,
                   nseg: int, base_order: tuple) -> MaskedBatch:
    """The Reduce's UDF over `nseg` static segments of the segmented batch
    `sb` (`nseg` >= the live group count `ngroups`), and its emissions:
    row-aligned ones at `sb.capacity` slots, per-group ones at `nseg`."""
    key = tuple(op.key)
    segops = JitSegmentOps(seg, nseg, record_valid=sb.valid,
                           is_start=is_start)
    with scope("segment"):
        col = invoke.run_kat_udf(op.udf, dict(sb.columns), segops, op.key)
    group_valid = jnp.arange(nseg) < ngroups
    w = eff_writes(op)

    parts = []
    for em in col.emissions:
        if em.records:
            cols = (em.builder.columns() if em.builder is not None
                    else dict(sb.columns))
            valid = sb.valid
            if em.group_where is not None:
                # the group filter: each row takes its group's verdict
                count("reduce.group_filter", 1)
                gw = jnp.asarray(em.group_where).astype(bool)
                with scope("segment"):
                    valid = valid & gw[seg]
            parts.append(MaskedBatch(
                _project(cols, op.out_schema, sb.capacity), valid,
                order_prefix(base_order, op.out_schema.fields, w)))
        else:
            cols = em.builder.columns()
            valid = group_valid
            if em.where is not None:
                valid = valid & jnp.asarray(em.where).astype(bool)
            # one slot per segment; segments were numbered in key order
            parts.append(MaskedBatch(
                _project(cols, op.out_schema, nseg), valid,
                order_prefix(tuple(base_order)[:len(key)],
                             op.out_schema.fields, w)))
    return _concat(parts)


def _match_codes(op: MatchOp, lb: MaskedBatch, rb: MaskedBatch):
    """Collision-free comparable key codes for a Match: one code per row such
    that `lcode[i] == rcode[j]` iff the composite keys are equal, and codes
    sort in key order.  Single-column keys ARE their own code (after dtype
    promotion); composite keys get dense joint ranks from one shared sort
    over both sides — no `c * 2^31 + v` pairing, which silently collided and
    overflowed for key values >= 2^31."""
    if len(op.left_key) == 1:
        lc = jnp.asarray(lb.columns[op.left_key[0]])
        rc = jnp.asarray(rb.columns[op.right_key[0]])
        ct = jnp.promote_types(lc.dtype, rc.dtype)
        return lc.astype(ct), rc.astype(ct)
    nl = lb.capacity
    ks = []
    for a, b_ in zip(op.left_key, op.right_key):
        la = jnp.asarray(lb.columns[a])
        ra = jnp.asarray(rb.columns[b_])
        ct = jnp.promote_types(la.dtype, ra.dtype)
        ks.append(jnp.concatenate([la.astype(ct), ra.astype(ct)]))
    n = ks[0].shape[0]
    with scope("sort"):
        order = jnp.lexsort(tuple(reversed(ks)))
    is_new = jnp.zeros(n, bool).at[0].set(True)
    for k in ks:
        sk = k[order]
        is_new = is_new | jnp.concatenate([jnp.ones(1, bool),
                                           sk[1:] != sk[:-1]])
    ranks_sorted = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    rank = jnp.zeros(n, jnp.int32).at[order].set(ranks_sorted,
                                                 unique_indices=True)
    return rank[:nl], rank[nl:]


def _probe(rcode, rvalid, lcode, lvalid, start, runs_valid: bool):
    """The PK probe: `(pos, hit)` for the sorted PK codes `rcode` and their
    validity `rvalid`.  `pos` is `max(searchsorted(rcode, lcode), start)`
    (a `start` of 0 clamps nothing), clamped to the PK side; the search
    runs under the device scope `probe`.  `hit` is whether `pos` holds a
    valid row of the code `lcode`, and the left row is valid (`lvalid`,
    None for every row).

    Where the search took the directory, its `eq` decides the code with no
    gather of `rcode[pos]`, and each trace counts `probe.hit_in_search`.
    With `runs_valid` (the cummax fill from the first valid slot `start`)
    every run start the directory can return is a valid row, so neither is
    `rvalid[pos]` gathered; one scalar guards a PK side with no valid row,
    whose fill codes would otherwise match."""
    with scope("probe"):
        pos, eq = scans.search_sorted(rcode, lcode, start)
    pos = jnp.clip(pos, 0, rcode.shape[0] - 1)
    if eq is None:
        hit = rcode[pos] == lcode
    else:
        count("probe.hit_in_search", 1)
        hit = eq
    if lvalid is not None:
        hit = hit & lvalid
    if eq is not None and runs_valid:
        return pos, hit & jnp.any(rvalid)
    return pos, hit & rvalid[pos]


def _exec_match_pk(op: MatchOp, lb: MaskedBatch, rb: MaskedBatch,
                   use_order: bool = True,
                   obs: Optional[dict] = None) -> MaskedBatch:
    """Equi-join where the right side is unique on its key (PK side): each
    left row matches at most one right row — sorted-search probe.  When the
    PK side is already ordered on its key, the probe runs directly against
    its slots (a cummax fills validity gaps monotonically) and the per-batch
    re-sort is skipped."""
    lcode, rcode_raw = _match_codes(op, lb, rb)

    # elide only for single-column keys: their codes are the column itself,
    # so a key-ordered PK side needs no per-batch work at all (composite
    # keys pay the joint rank sort in _match_codes either way)
    if use_order and len(op.right_key) == 1 \
            and tuple(rb.order[:1]) == tuple(op.right_key):
        # the valid subsequence of rcode_raw is nondecreasing; back-fill
        # invalid slots with the previous valid code (cummax) so the whole
        # array is monotone.  A fill slot repeats the code of a valid slot
        # BEFORE it, so searchsorted(left) lands on the valid occurrence —
        # except in the leading all-invalid run, whose -inf/min fill can
        # equal a genuine minimal key; clamping pos past that run restores
        # the invariant (slots before the first valid row never match).
        lo = (-jnp.inf if jnp.issubdtype(rcode_raw.dtype, jnp.floating)
              else jnp.iinfo(rcode_raw.dtype).min)
        rcode = scans.cummax(
            jnp.where(rb.valid, rcode_raw, jnp.asarray(lo, rcode_raw.dtype)))
        first_valid = jnp.argmax(rb.valid).astype(jnp.int32)
        rcols, rvalid, runs_valid = rb.columns, rb.valid, True
    else:
        first_valid, runs_valid = 0, False
        # sort by (code, valid-first): equal-code invalid rows land AFTER the
        # valid ones, so no sentinel arithmetic is needed and a left search
        # still finds the valid row first
        with scope("sort"):
            order = jnp.lexsort((~rb.valid, rcode_raw))
            rcode = rcode_raw[order]
            rcols = {f: v[order] for f, v in rb.columns.items()}
            rvalid = rb.valid[order]

    pos, hit = _probe(rcode, rvalid, lcode, lb.valid, first_valid,
                      runs_valid)
    if obs is not None:  # observed probe hits (adaptive join-fanout feedback)
        obs["groups"] = jnp.sum(hit.astype(jnp.int32))

    gathered = {f: v[pos] for f, v in rcols.items()}
    col = invoke.run_pair_udf(op.udf, dict(lb.columns), gathered)
    out_order = order_prefix(lb.order, op.out_schema.fields, eff_writes(op))
    parts = []
    for em in col.emissions:
        if em.builder is None:
            continue
        valid = hit
        if em.where is not None:
            valid = valid & jnp.asarray(em.where).astype(bool)
        # output is slot-aligned with the LEFT input (each left row matches
        # at most one PK row), so the left side's order survives
        parts.append(MaskedBatch(
            _project(em.builder.columns(), op.out_schema, lb.capacity), valid,
            out_order))
    return _concat(parts)


def _exec_match_anti(op: MatchOp, lb: MaskedBatch, rb: MaskedBatch,
                     use_order: bool = True,
                     obs: Optional[dict] = None) -> MaskedBatch:
    """Left anti join: keep exactly the LEFT rows whose key has NO valid
    partner on the right.  No UDF runs; the output is a slot-aligned mask
    over the left input, so the left side's order survives.  The presence
    probe is the `_exec_match_pk` sorted search (duplicates on the right are
    harmless — any valid occurrence of the code marks presence), including
    the cummax elision when the right side is already key-ordered."""
    lcode, rcode_raw = _match_codes(op, lb, rb)
    if use_order and len(op.right_key) == 1 \
            and tuple(rb.order[:1]) == tuple(op.right_key):
        lo = (-jnp.inf if jnp.issubdtype(rcode_raw.dtype, jnp.floating)
              else jnp.iinfo(rcode_raw.dtype).min)
        rcode = scans.cummax(
            jnp.where(rb.valid, rcode_raw, jnp.asarray(lo, rcode_raw.dtype)))
        first_valid = jnp.argmax(rb.valid).astype(jnp.int32)
        rvalid, runs_valid = rb.valid, True
    else:
        first_valid, runs_valid = 0, False
        with scope("sort"):
            order = jnp.lexsort((~rb.valid, rcode_raw))
            rcode = rcode_raw[order]
            rvalid = rb.valid[order]
    _, present = _probe(rcode, rvalid, lcode, None, first_valid, runs_valid)
    keep = lb.valid & ~present
    if obs is not None:  # observed survivors (adaptive selectivity feedback)
        obs["groups"] = jnp.sum(keep.astype(jnp.int32))
    return MaskedBatch(dict(lb.columns), keep, lb.order)


def _exec_limit(op: LimitOp, b: MaskedBatch,
                use_order: bool = True) -> MaskedBatch:
    """WITH-TIES top-k: keep every valid row whose key is lexicographically
    <= the k-th smallest valid key.  A deterministic multiset function of the
    input, so serial/sharded/reordered executions agree bit-identically.
    The result is a slot-aligned mask — input order survives — and when the
    input order already covers the key, the threshold row is found with a
    prefix sum instead of a lexsort (DESIGN.md §8 elision)."""
    keys = [jnp.asarray(b.columns[k]) for k in op.key]
    nv = jnp.sum(b.valid.astype(jnp.int32))
    kth = jnp.clip(jnp.minimum(jnp.int32(op.k), nv) - 1, 0, b.capacity - 1)
    if use_order and order_covers(b.order, op.key):
        # valid rows are already key-sorted in slot order: the k-th smallest
        # key sits at the slot where cumsum(valid) first reaches k
        cum = scans.cumsum(b.valid.astype(jnp.int32))
        pos = jnp.clip(jnp.searchsorted(cum, kth + 1), 0, b.capacity - 1)
    else:
        with scope("sort"):
            perm = jnp.lexsort(tuple(reversed(keys)) + (~b.valid,))
        pos = perm[kth]
    # lexicographic key <= threshold key (empty input: valid is all-False
    # anyway, so the garbage threshold never leaks a row)
    le = keys[-1] <= keys[-1][pos]
    for k in reversed(keys[:-1]):
        t = k[pos]
        le = (k < t) | ((k == t) & le)
    return MaskedBatch(dict(b.columns), b.valid & le, b.order)


def _exec_cross(op, lb: MaskedBatch, rb: MaskedBatch,
                left_key=(), right_key=()) -> MaskedBatch:
    """Full pairwise product (also used for small general equi-joins)."""
    nl, nr = lb.capacity, rb.capacity
    li = jnp.repeat(jnp.arange(nl), nr)
    ri = jnp.tile(jnp.arange(nr), nl)
    lcols = {f: v[li] for f, v in lb.columns.items()}
    rcols = {f: v[ri] for f, v in rb.columns.items()}
    valid = lb.valid[li] & rb.valid[ri]
    for lk, rk in zip(left_key, right_key):
        valid = valid & (lcols[lk] == rcols[rk])
    col = invoke.run_pair_udf(op.udf, lcols, rcols)
    parts = []
    for em in col.emissions:
        if em.builder is None:
            continue
        v = valid
        if em.where is not None:
            v = v & jnp.asarray(em.where).astype(bool)
        parts.append(MaskedBatch(
            _project(em.builder.columns(), op.out_schema, nl * nr), v))
    return _concat(parts)


def _exec_cogroup(op: CoGroupOp, lb: MaskedBatch, rb: MaskedBatch,
                  use_order: bool = True,
                  obs: Optional[dict] = None) -> MaskedBatch:
    """Align both sides on the union key domain with static shapes."""
    nl, nr = lb.capacity, rb.capacity
    # joint sort of all keys to build dense codes over the union domain
    lkeys = [jnp.asarray(lb.columns[k]) for k in op.left_key]
    rkeys = [jnp.asarray(rb.columns[k]) for k in op.right_key]
    allkeys = [jnp.concatenate([a, b_]) for a, b_ in zip(lkeys, rkeys)]
    allvalid = jnp.concatenate([lb.valid, rb.valid])
    with scope("sort"):
        order = jnp.lexsort(tuple(reversed(allkeys)) + (~allvalid,))
        sorted_keys = [k[order] for k in allkeys]
        sorted_valid = allvalid[order]
    same = jnp.ones(nl + nr, bool)
    for k in sorted_keys:
        same = same & jnp.concatenate([jnp.zeros(1, bool), k[1:] == k[:-1]])
    prev_valid = jnp.concatenate([jnp.zeros(1, bool), sorted_valid[:-1]])
    is_start = sorted_valid & (~same | ~prev_valid)
    seg_sorted = jnp.maximum(jnp.cumsum(is_start.astype(jnp.int32)) - 1, 0)
    with scope("sort"):
        inv = jnp.argsort(order)
    seg_all = seg_sorted[inv]
    lseg, rseg = seg_all[:nl], seg_all[nl:]
    nseg = nl + nr
    ngroups = jnp.sum(is_start)
    if obs is not None:
        obs["groups"] = ngroups.astype(jnp.int32)
    group_valid = jnp.arange(nseg) < ngroups

    # Per-side segment-sorted order (first()/group scans need contiguity).
    # A side ordered EXACTLY on its key (not a permuted cover: union
    # segments are numbered in the operator's key order, so only the exact
    # prefix makes this side's segment ids nondecreasing) degenerates its
    # segment sort to the stable valids-first permutation — two prefix sums
    # instead of a lexsort.
    def side_perm(b_, key, seg):
        if use_order and tuple(b_.order[:len(key)]) == tuple(key):
            return _compact_perm(b_.valid)
        with scope("sort"):
            return jnp.lexsort((~b_.valid, seg))

    lord = side_perm(lb, op.left_key, lseg)
    rord = side_perm(rb, op.right_key, rseg)
    lcols = {f: v[lord] for f, v in lb.columns.items()}
    rcols = {f: v[rord] for f, v in rb.columns.items()}
    lseg, rseg = lseg[lord], rseg[rord]
    lvalid, rvalid = lb.valid[lord], rb.valid[rord]

    lops = JitSegmentOps(lseg, nseg, record_valid=lvalid)
    rops = JitSegmentOps(rseg, nseg, record_valid=rvalid)
    col = invoke.run_cogroup_udf(op.udf, lcols, lops, rcols, rops,
                                 op.left_key, op.right_key)
    parts = []
    for em in col.emissions:
        if em.records:
            raise NotImplementedError("CoGroup passthrough under jit")
        valid = group_valid
        if em.where is not None:
            valid = valid & jnp.asarray(em.where).astype(bool)
        parts.append(MaskedBatch(
            _project(em.builder.columns(), op.out_schema, nseg), valid))
    return _concat(parts)


# ---------------------------------------------------------------------------
# Flow execution
# ---------------------------------------------------------------------------
def execute_masked(root: Node, bindings: Mapping[str, MaskedBatch],
                   compact_slack: float = 2.0,
                   compact: bool = True,
                   use_order: bool = True) -> MaskedBatch:
    """Execute `root` on masked batches (traceable: call under jit).

    `compact=True` re-packs intermediates to `estimate(node) * slack`
    capacity (static — derived from the cost model at trace time, rounded up
    to a geometric `bucket_capacity` so repeated traces share shapes),
    bounding memory exactly the way the paper's optimizer uses cardinality
    hints.  When the bound batches are LARGER than the flow's nominal
    `Source.num_records`, estimates are scaled up proportionally —
    compaction must never drop valid rows just because the request outgrew
    the scale the flow was declared at.

    `use_order=True` honors `Source.sorted_on` at execution time and lets
    key-ordered intermediates skip their sorts (DESIGN.md §8); order
    metadata is still PROPAGATED either way, only elision is gated.
    """
    stats_memo: dict = {}
    memo: dict[int, MaskedBatch] = {}
    scale = cardinality_scale(root, bindings)

    def maybe_compact(node: Node, b: MaskedBatch) -> MaskedBatch:
        if not compact:
            return b
        return compact_to_estimate(b, node, stats_memo, compact_slack, scale)

    def run(node: Node) -> MaskedBatch:
        if id(node) in memo:
            return memo[id(node)]
        if isinstance(node, Source):
            out = bindings[node.name]
            if use_order and node.sorted_on and not out.order:
                out = out.with_order(tuple(node.sorted_on))
        elif isinstance(node, MapOp):
            out = _exec_map(node, run(node.child))
        elif isinstance(node, ReduceOp):
            out = _exec_reduce(node, run(node.child), use_order)
        elif isinstance(node, LimitOp):
            out = _exec_limit(node, run(node.child), use_order)
        elif isinstance(node, MatchOp):
            lb, rb = run(node.left), run(node.right)
            if node.anti:
                out = _exec_match_anti(node, lb, rb, use_order)
            elif node.hints.pk_side == "right":
                out = _exec_match_pk(node, lb, rb, use_order)
            elif node.hints.pk_side == "left":
                from .reorder import commute as _commute

                flipped = _commute(node)
                out = _exec_match_pk(flipped, rb, lb, use_order)
            else:
                out = _exec_cross(node, lb, rb, node.left_key, node.right_key)
        elif isinstance(node, CrossOp):
            out = _exec_cross(node, run(node.left), run(node.right))
        elif isinstance(node, CoGroupOp):
            out = _exec_cogroup(node, run(node.left), run(node.right),
                                use_order)
        else:
            raise TypeError(type(node).__name__)
        out = maybe_compact(node, out)
        memo[id(node)] = out
        return out

    return run(root)


def _round8(x: float) -> int:
    return int(np.ceil(max(x, 1.0) / 8.0) * 8)


def bucket_capacity(x: float) -> int:
    """Geometric capacity bucket: the smallest 8·2^k >= x.

    Every static capacity a trace sees (source padding, intermediate
    compaction) is drawn from this ladder, so a flow of n operators with n
    distinct cardinality estimates traces O(log n) distinct shapes instead of
    O(n) — the jit-cache analogue of the paper's spill-buffer size classes.
    """
    n8 = _round8(x) // 8
    return 8 * (1 << (n8 - 1).bit_length())


def run_flow_jit(root: Node, bindings: Mapping[str, RecordBatch],
                 capacities: Optional[Mapping[str, int]] = None,
                 use_order: bool = True) -> RecordBatch:
    """Convenience: bind numpy batches, jit-execute, return a RecordBatch."""
    caps = capacities or {}
    masked = {name: MaskedBatch.from_record_batch(b, caps.get(name))
              for name, b in bindings.items()}

    @functools.partial(jax.jit, static_argnums=())
    def go(mb):
        return execute_masked(root, mb, use_order=use_order)

    return go(masked).to_record_batch()
