"""Whole-stage megakernels: fuse a run of pipeline stages into one body
that keeps the batch block-resident across stage boundaries (DESIGN.md §10).

The composed pipeline (`pipeline.run_stages`) already jit-compiles every
stage into one XLA computation, but each stage boundary still materializes
the FULL intermediate: the boundary compaction gathers every column the
producer emits, and the downstream segmentation re-walks validity gaps with
a cummax scan.  A fused span removes both costs without changing a single
result bit:

* **Dead-column pruning** — before an interior compaction the producer's
  columns are intersected with what the consuming stage can observe: its
  SCA effective read set (`reorder.eff_reads`, which includes its keys)
  plus every field its operators re-emit (`out_schema`, covering KAT
  passthrough and `ir.copy()`-style projections whose reads SCA cannot
  narrow).  Dead columns skip the compaction gather entirely.  Order
  metadata is truncated to the surviving prefix; elision decisions cannot
  flip because `order_covers` only inspects the key-length prefix and keys
  are always live, and the span OUTPUT's order metadata provably equals the
  composed path's (a pruned column is absent from the consumer's output
  fields, where the composed `order_prefix` stops anyway).

* **Contiguity exploitation** — an interior compaction leaves valid rows as
  a prefix, so the next Reduce segments with adjacent-slot compares
  (`masked._segments_contiguous`) instead of the gap-tolerant cummax walk —
  bit-identical on a packed batch (the previous valid row IS the adjacent
  slot).

The span body reuses the masked executors verbatim (`pipeline.
execute_stage`), compacts interior boundaries to exactly the capacities the
composed path would (`masked.planned_capacity` min output capacity), and
returns the same per-stage `(valid-count, kat-aux)` observation pairs
`run_stages` emits — the PR-5 adaptive side-channel is preserved
boundary-for-boundary, so `record_batch_obs`, truncation detection and
`StatsStore` keys all work unchanged.

Dispatch: on every backend the span body inlines into the enclosing jit,
which XLA compiles for the chip.  Mosaic could not compile it as one
Pallas kernel for a TPU: every flow column is 64-bit, and it has no
lowering for the sort and cumsum the body needs (DESIGN.md §10.2).

Fallback (`plan_routes`): Cross, CoGroup and hint-less Match stages, spans
shorter than two stages, multi-consumer interior edges, non-8-blockable
capacities and VMEM-budget overruns all route "solo" — the composed path,
byte-for-byte the pre-megakernel behavior.
"""

from __future__ import annotations

import collections
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .. import hw
from ..core import masked as M
from ..core.reorder import eff_reads

# ---------------------------------------------------------------------------
# Fusability predicate + route planning
# ---------------------------------------------------------------------------
def _stage_fusable(st) -> bool:
    if st.kind in ("chain", "reduce"):
        return True
    if st.kind == "match":
        # a hint-less Match executes as a cross product — not fusable; an
        # anti Match has its own executor the span body does not route
        return not st.top.anti \
            and st.top.hints.pk_side in ("left", "right")
    return False  # cross / cogroup / limit: stay composed


def _input_nodes(st) -> tuple:
    if st.kind == "chain":
        return (st.ops[0].child,)
    return tuple(st.top.children)


def _row_bytes(node) -> int:
    sch = node.out_schema
    total = sum(np.dtype(sch.dtype(f)).itemsize for f in sch.fields)
    return max(total, 8) + 1  # +1: the validity mask


def plan_routes(stages: Sequence, src_caps, vmem_bytes: Optional[int] = None,
                require_forward: bool = False) -> Optional[tuple]:
    """Partition a lowered stage list into megakernel spans and solo stages.

    Returns a tuple of `("mega", i, j)` (stages[i:j] fused) and
    `("solo", i)` entries covering the list in order, or None when nothing
    fuses (the composed path).  A span is a maximal run where

    * every stage kind is fusable (`chain` / `reduce` / PK `match`);
    * each interior output is consumed ONLY by the next stage (checked
      against every stage's input refs — shared subtrees stay solo);
    * every resolvable input capacity is 8-blockable (source capacities come
      bucketed from `_bind`; arbitrary user-masked batches may not be);
    * the running resident-bytes estimate (inputs + a same-width output
      bound per stage, from the operator schemas) fits `vmem_bytes`
      (default `hw.CHIP.vmem_bytes`) — the VMEM residency budget;
    * with `require_forward` (the distributed per-shard walk), every span
      stage ships all inputs `forward` — collectives stay at solo-stage
      inputs, so the same kernel runs on every shard.

    Deterministic in (stages, src_caps): every shard and every retrace of
    one source signature computes identical routes.
    """
    n = len(stages)
    if n < 2:
        return None
    vmem = vmem_bytes if vmem_bytes is not None else hw.CHIP.vmem_bytes
    consumers: collections.Counter = collections.Counter()
    for st in stages:
        for ref in st.inputs:
            if ref[0] == "stage":
                consumers[ref[1]] += 1
    max_src = max(src_caps.values(), default=8)

    def cap_of(ref) -> int:
        if ref[0] == "source":
            return int(src_caps.get(ref[1], max_src))
        return int(max_src)  # out-of-span stage ref: conservative bound

    def admissible(k: int) -> bool:
        st = stages[k]
        if not _stage_fusable(st):
            return False
        if any(cap_of(r) % 8 or cap_of(r) < 8 for r in st.inputs):
            return False
        if require_forward and any(s != "forward" for s in (st.ship or ())):
            return False
        return True

    def resident(k: int) -> int:
        st = stages[k]
        caps = [cap_of(r) for r in st.inputs]
        total = sum(c * _row_bytes(kid)
                    for c, kid in zip(caps, _input_nodes(st)))
        return total + max(caps) * _row_bytes(st.top)

    def extends(k: int) -> bool:
        st = stages[k]
        if not admissible(k):
            return False
        hits = sum(1 for r in st.inputs if r == ("stage", k - 1))
        # prev's output must flow ONLY into this stage (and must be used)
        return hits > 0 and consumers[k - 1] == hits

    entries: list = []
    i = 0
    while i < n:
        j = i
        if admissible(i) and resident(i) <= vmem:
            budget = resident(i)
            j = i + 1
            while j < n and extends(j) and budget + resident(j) <= vmem:
                budget += resident(j)
                j += 1
        if j - i >= 2:
            entries.append(("mega", i, j))
            i = j
        else:
            entries.append(("solo", i))
            i += 1
    if all(e[0] == "solo" for e in entries):
        return None
    return tuple(entries)


def span_has_aux(span: Sequence) -> tuple:
    """Which span stages emit a KAT/Match side-channel (static): the
    distributed walk psums only these, keeping the composed path's
    convention that aux-free stages report an un-psum'd -1."""
    return tuple(st.kind != "chain" for st in span)


# ---------------------------------------------------------------------------
# Dead-column pruning (SCA liveness at interior boundaries)
# ---------------------------------------------------------------------------
def _live_fields(consumer, fields) -> tuple:
    """Columns of a producer batch the `consumer` stage can observe: the
    union over its fused operators of the SCA effective read set (which
    includes every operator's keys) and the operator's output fields (KAT
    passthrough projects `dict(sb.columns)` through `out_schema`, and
    `ir.copy()`-style UDFs re-emit fields SCA does not list as reads)."""
    live: set = set()
    for op in consumer.ops:
        live |= eff_reads(op)
        live |= set(op.out_schema.fields)
    return tuple(f for f in fields if f in live)


# ---------------------------------------------------------------------------
# Span execution
# ---------------------------------------------------------------------------
def run_span(span: Sequence, ins_per_stage: Sequence, planned_caps: Sequence,
             use_order: bool):
    """Execute a fused span (traceable).

    `ins_per_stage[k]` lists stage k's resolved input batches with None
    marking the in-span edge (the previous stage's output, substituted
    internally); `planned_caps[k]` is stage k's planned compaction capacity
    (`masked.planned_capacity`).  Interior boundaries compact inside the
    span (pruned to live columns); the LAST stage's output returns RAW for
    the caller's usual boundary compaction, keeping the solo/mega caps and
    observation protocols aligned.

    Returns `(raw_out, obs, caps)`: `obs` is the per-stage
    `(pre-compaction valid count, kat aux)` list matching `run_stages`
    (aux = int32 -1 for aux-free stages), `caps` the interior capacities
    actually applied (static trace-time ints — the truncation-detection
    reference for all but the last span stage)."""
    from ..core import pipeline as PL

    prev: Optional[M.MaskedBatch] = None
    prev_packed = False
    obs_out, caps = [], []
    out = None
    for k, (st, raw_ins) in enumerate(zip(span, ins_per_stage)):
        with PL.stage_scope(st):
            ins = [prev if b is None else b for b in raw_ins]
            obs: dict = {}
            out = PL.execute_stage(st, ins, use_order, obs,
                                   contiguous_in=prev_packed)
            obs_out.append((jnp.sum(out.valid.astype(jnp.int32)),
                            jnp.asarray(obs.get("groups", jnp.int32(-1)),
                                        jnp.int32)))
            if k == len(span) - 1:
                break
            # interior boundary: prune dead columns, compact to exactly the
            # capacity the composed path would, and record packedness for the
            # consumer's contiguous segmentation
            nxt = span[k + 1]
            live = _live_fields(nxt, out.columns.keys())
            if len(live) < len(out.columns):
                out = M.MaskedBatch({f: out.columns[f] for f in live}, out.valid,
                                    M.order_prefix(out.order, live))
            cap = min(out.capacity, planned_caps[k])
            caps.append(cap)
            if cap < out.capacity:
                out = out.compact(cap)
                prev_packed = True
            else:
                prev_packed = False
            # attach the lowered order assumption on the in-span edge, exactly
            # as run_stages does for solo stages
            orders = nxt.in_orders or ((),) * len(nxt.inputs)
            for t, b in enumerate(ins_per_stage[k + 1]):
                if b is None and use_order and orders[t] and not out.order:
                    out = out.with_order(orders[t])
                    break
            prev = out
    return out, obs_out, tuple(caps)
