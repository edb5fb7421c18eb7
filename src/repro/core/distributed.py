"""Distributed flow execution under shard_map (the Nephele-engine analogue).

A physical plan (`repro.core.physical.PhysPlan`) runs data-parallel over the
mesh `data` axis.  The per-shard body executes the SAME fused stages as the
local compiled pipeline — Map chains fuse, megakernel spans keep interior
boundaries VMEM-resident (DESIGN.md §10), combiner halves of a split Reduce
pre-aggregate per shard BEFORE any collective fires, and the adaptive
side-channel psums every stage's boundary counts over the mesh so one global
observation per batch feeds the §9 feedback loop.  The paper's shipping
strategies map onto collectives:

    partition  -> hash repartition via jax.lax.all_to_all, on the partition
                  columns the optimizer chose (`PhysPlan.ship_keys` — a
                  multi-column Reduce may hash a key SUBSET for a more
                  reusable co-location class)
    broadcast  -> replicate via jax.lax.all_gather(tiled)
    forward    -> no communication (the plan proved co-location)

Micro-batched collective/compute overlap (DESIGN.md §12): each collective's
payload is bit-packed into one byte matrix and shipped in K independent
slices (`REPRO_OVERLAP_SLICES`, kill switch `REPRO_OVERLAP=0`), so the
transfer of slice i can overlap whatever else the scheduler has in flight —
the slices carry disjoint buffer ranges and reassemble to EXACTLY the serial
receive layout, so sliced execution is bit-identical to the unpipelined
path (pure data movement, no arithmetic reassociation).

Capacity management: a repartition temporarily expands the per-worker buffer
to p x local capacity (every worker reserves one slot block per peer) and
compacts back using the optimizer's cardinality estimate — the masked-batch
analogue of Nephele's spill buffers.  The same hash is used host-side
(numpy) to honor `Source.partitioned_on`, so plans whose costing assumed
pre-partitioned sources execute correctly.

Entry points: `execute_distributed` (one-shot, retraces per call) and
`DistributedPlan` (cached + jitted serving handle whose executable identity
includes the layout — ship strategies, partition columns, dop, slicing).
"""

from __future__ import annotations

import functools
import os
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import masked as M
from ..obs import count, scope, span
from .operators import CoGroupOp, MatchOp, Node, ReduceOp, Source
from .physical import MESH_SHARDS_ENV, PhysPlan, default_mesh_shards
from .record import RecordBatch

_MIX = 0x9E3779B97F4A7C15  # Fibonacci hashing constant

# Collective/compute overlap knobs (DESIGN.md §12).  REPRO_OVERLAP=0 is the
# kill switch (forces the serial per-column wire); REPRO_OVERLAP_SLICES sets
# the slice count K (clamped to a divisor of the buffer capacity at the
# collective site, so slices stay equal-sized).
OVERLAP_ENV = "REPRO_OVERLAP"
OVERLAP_SLICES_ENV = "REPRO_OVERLAP_SLICES"
DEFAULT_OVERLAP_SLICES = 4


def overlap_slices_default() -> int:
    """Effective slice count from the environment (1 = overlap off)."""
    if os.environ.get(OVERLAP_ENV, "1") == "0":
        return 1
    try:
        k = int(os.environ.get(OVERLAP_SLICES_ENV,
                               str(DEFAULT_OVERLAP_SLICES)))
    except ValueError:
        return DEFAULT_OVERLAP_SLICES
    return max(k, 1)


class ShuffleStats:
    """Trace-time accounting of what crosses the shipping collectives.

    `wire_rows` counts buffer slots through a collective per plan execution
    (per-shard capacity x workers — the actual tensor rows on the wire,
    masked slots included); `wire_bytes` are those slots priced at the
    batch's per-row byte width (column itemsizes + 1 validity byte), so the
    §12 comms cost model can be validated against observed traffic.
    `collectives`/`broadcasts` count repartition/replication SITES (logical
    edges, independent of slicing); `dispatches` counts the collective ops
    actually issued (serial: one per column + validity; sliced: one packed
    op per slice); `slices` sums the slice counts, so
    `1 - sites/slices` is the fraction of transfers with an independent
    in-flight peer — the overlap fraction the bench reports.  Incremented
    while the shard_map body is traced, so a combiner plan — whose
    pre-Reduce compacts to ~groups rows BEFORE the collective — shows
    proportionally fewer wire rows than the unsplit plan
    (benchmarks/bench_aggregation.py asserts the ratio)."""

    def __init__(self):
        self.clear()

    def clear(self) -> None:
        self.wire_rows = 0
        self.wire_bytes = 0
        self.collectives = 0
        self.broadcasts = 0
        self.dispatches = 0
        self.slices = 0

    @property
    def sites(self) -> int:
        return self.collectives + self.broadcasts

    def overlap_fraction(self) -> float:
        """Fraction of shipped slices that had an independent in-flight
        peer slice ((K-1)/K under uniform K-slicing; 0 when serial)."""
        if self.slices <= 0:
            return 0.0
        return 1.0 - self.sites / self.slices


_SHUFFLE_STATS = ShuffleStats()


def shuffle_stats() -> ShuffleStats:
    """Process-wide collective accounting (cleared by the caller)."""
    return _SHUFFLE_STATS


def _account(b: M.MaskedBatch, p: int, k: int, broadcast: bool) -> None:
    width = sum(np.dtype(v.dtype).itemsize
                for v in b.columns.values()) + 1  # + validity byte
    s = _SHUFFLE_STATS
    s.wire_rows += b.capacity * p
    s.wire_bytes += b.capacity * p * width
    if broadcast:
        s.broadcasts += 1
    else:
        s.collectives += 1
    s.slices += k
    if k == 1:  # serial: one collective per column, plus the validity mask
        s.dispatches += len(b.columns) + 1
    else:  # sliced: K collectives per lane buffer; validity rides in one
        s.dispatches += k * len({_lane_kind(v.dtype)
                                 for v in b.columns.values()} | {"u64"})


def _hash_u64(x):
    x = (x ^ (x >> 30)) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> 31)


def _key_hash_jnp(cols: Mapping, keys, valid):
    h = jnp.zeros_like(valid, dtype=jnp.uint64)
    for k in keys:
        v = jnp.asarray(cols[k]).astype(jnp.uint64)
        h = _hash_u64((h * jnp.uint64(_MIX)) ^ v)
    return h


def _key_hash_np(cols: Mapping, keys, n):
    with np.errstate(over="ignore"):
        h = np.zeros(n, dtype=np.uint64)
        for k in keys:
            v = np.asarray(cols[k]).astype(np.uint64)
            h = _hash_u64((h * np.uint64(_MIX)) ^ v)
    return h


# ---------------------------------------------------------------------------
# Lane packing for sliced collectives
#
# All columns (plus the validity mask) are packed into [lanes, capacity]
# matrices, one per lane kind, so each slice ships as one collective per
# kind regardless of column count.  Integer, bool and 4-byte float columns
# bitcast into uint64 lanes: 8-byte dtypes to one lane, narrower dtypes
# zero-extended (truncation on unpack is the exact inverse).  float64
# columns ship as themselves in a float64 matrix: a TPU's 64-bit rewrite has
# no bitcast from f64 to bits, and moving the values is the same data
# movement the serial wire does.  Reassembly is a pure transpose/reshape
# back to the serial receive layout — the bit-identity argument of
# DESIGN.md §12.  Wide 8-byte lanes (rather than a uint8 byte matrix) keep
# the pack/reassemble transposes ~8x smaller.
# ---------------------------------------------------------------------------
_UINT_OF = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _lane_kind(dtype) -> str:
    """The matrix a column ships in: "f64" for float64, else "u64"."""
    return "f64" if np.dtype(dtype) == np.float64 else "u64"


def _lane_rows(v):
    """[capacity] column -> [lanes, capacity] of its lane kind (bit-exact)."""
    dt = np.dtype(v.dtype)
    if dt == np.bool_:
        return v.astype(jnp.uint64)[None, :]
    if dt.itemsize < 8:
        u = jax.lax.bitcast_convert_type(v, _UINT_OF[dt.itemsize])
        return u.astype(jnp.uint64)[None, :]
    if _lane_kind(dt) == "u64":
        v = jax.lax.bitcast_convert_type(v, jnp.uint64)
    return v[None, :] if v.ndim == 1 else v.T


def _from_lane_rows(rows, dtype):
    """Inverse of `_lane_rows`: [lanes, n] of its lane kind -> [n] of
    `dtype`."""
    dt = np.dtype(dtype)
    if dt == np.bool_:
        return rows[0] != 0
    if dt.itemsize < 8:
        u = rows[0].astype(_UINT_OF[dt.itemsize])
        return jax.lax.bitcast_convert_type(u, dtype)
    v = rows[0] if rows.shape[0] == 1 else rows.T
    return jax.lax.bitcast_convert_type(v, dtype) \
        if _lane_kind(dt) == "u64" else v


def _pack_payload(cols: Mapping, valid=None):
    """Pack columns into `{lane kind: [lanes, capacity] matrix}`; `valid`,
    when given, rides as the last "u64" lane.  `meta` records each column's
    kind, offset and lane count."""
    rows: dict = {}
    meta = []
    for f, v in cols.items():
        r = _lane_rows(v)
        kind = rows.setdefault(_lane_kind(v.dtype), [])
        meta.append((f, v.dtype, _lane_kind(v.dtype),
                     sum(x.shape[0] for x in kind), r.shape[0]))
        kind.append(r)
    if valid is not None:
        rows.setdefault("u64", []).append(valid.astype(jnp.uint64)[None, :])
    return {k: jnp.concatenate(rs, axis=0) for k, rs in rows.items()}, meta


def _unpack_payload(bufs, meta) -> dict:
    return {f: _from_lane_rows(bufs[kind][off:off + m], dt)
            for f, dt, kind, off, m in meta}


def _gather_slices(b: M.MaskedBatch, axis: str, p: int, k: int):
    """Ship `b` to every peer as K tiled all_gathers per lane kind, over
    disjoint slot ranges, and reassemble columns and validity in the
    serial receive layout ([p*cap], peer-major).  One concat per column —
    no full-payload transpose — because slice j holds slot range
    [j*cs, (j+1)*cs) of every peer's block."""
    bufs, meta = _pack_payload(b.columns, b.valid)
    cs = b.capacity // k
    recv = [{kind: jax.lax.all_gather(buf[:, j * cs:(j + 1) * cs], axis,
                                      axis=1, tiled=True
                                      ).reshape(buf.shape[0], p, cs)
             for kind, buf in bufs.items()}
            for j in range(k)]
    cols = {}
    for f, dt, kind, off, m in meta:
        lane = jnp.concatenate([r[kind][off:off + m] for r in recv], axis=2)
        cols[f] = _from_lane_rows(lane.reshape(m, -1), dt)
    valid = jnp.concatenate([r["u64"][-1] for r in recv],
                            axis=1).reshape(-1) != 0
    return cols, valid


def _slice_count(capacity: int, slices: int) -> int:
    """Largest divisor of `capacity` not exceeding the requested count
    (capacities are 8·2^k buckets, so 2/4/8 divide whenever cap >= 8)."""
    k = max(1, min(int(slices), capacity))
    while capacity % k:
        k -= 1
    return k


# ---------------------------------------------------------------------------
# Collective shipping (inside shard_map)
# ---------------------------------------------------------------------------
def _repartition(b: M.MaskedBatch, keys, axis: str, p: int,
                 slices: int = 1) -> M.MaskedBatch:
    """Hash-partition rows by key over the `axis` workers (all_to_all).

    With `slices` > 1 the packed payload ships in K independent collectives
    over disjoint slot ranges (software-pipelined wire, DESIGN.md §12).
    Because the serial path replicates every column to all peers and lets
    per-peer validity select rows, the payload a peer receives is identical
    for every peer — so the sliced path ships it as K tiled all_gathers (no
    materialized p-way replication on the send side), with the GLOBAL
    validity packed as one extra lane; each receiver recomputes the
    partition hash on the received key columns and keeps its own rows.
    The hash is a pure function of column values, so the resulting mask is
    bit-identical to the mask the serial path ships, and the slice
    reassembly is a per-column concat back to the serial receive layout —
    both paths return bit-identical batches."""
    if p == 1:
        return b
    cap = b.capacity
    k = _slice_count(cap, slices)
    _account(b, p, k, broadcast=False)

    if k == 1:  # serial reference path: one collective per column + validity
        tgt = (_key_hash_jnp(b.columns, keys, b.valid)
               % jnp.uint64(p)).astype(jnp.int32)
        slots = jnp.arange(p, dtype=jnp.int32)
        send_valid = b.valid[None, :] & (tgt[None, :] == slots[:, None])

        def ship(v):
            sv = jnp.broadcast_to(v[None], (p,) + v.shape)
            rv = jax.lax.all_to_all(sv, axis, split_axis=0, concat_axis=0)
            return rv.reshape((-1,) + v.shape[1:])

        cols = {f: ship(v) for f, v in b.columns.items()}
        valid = jax.lax.all_to_all(send_valid, axis, split_axis=0,
                                   concat_axis=0).reshape(-1)
        return M.MaskedBatch(cols, valid)

    cols, valid = _gather_slices(b, axis, p, k)
    tgt = (_key_hash_jnp(cols, keys, valid)
           % jnp.uint64(p)).astype(jnp.int32)
    return M.MaskedBatch(cols, valid & (tgt == jax.lax.axis_index(axis)))


def _broadcast(b: M.MaskedBatch, axis: str, p: int,
               slices: int = 1) -> M.MaskedBatch:
    """Replicate all rows on every worker (all_gather, tiled); sliced the
    same way as `_repartition`, with the same bit-identity guarantee."""
    if p == 1:
        return b
    cap = b.capacity
    k = _slice_count(cap, slices)
    _account(b, p, k, broadcast=True)

    if k == 1:
        cols = {f: jax.lax.all_gather(v, axis, axis=0, tiled=True)
                for f, v in b.columns.items()}
        valid = jax.lax.all_gather(b.valid, axis, axis=0, tiled=True)
        return M.MaskedBatch(cols, valid)

    return M.MaskedBatch(*_gather_slices(b, axis, p, k))


# ---------------------------------------------------------------------------
# Stage walking (inside shard_map)
#
# The plan is lowered once (host-side) through pipeline.lower_phys, so the
# per-shard body executes the same fused stages as the local compiled
# pipeline: Map chains run as one stage with a single boundary compaction;
# shipping collectives fire at stage inputs exactly where the physical plan
# placed them, hashing the partition columns the plan chose.
# ---------------------------------------------------------------------------
def _exec_stages(stages, shards: Mapping[str, M.MaskedBatch],
                 axis: str, p: int, stats_memo: dict, slack: float,
                 root: Node, use_order: bool = True,
                 observe: Optional[list] = None,
                 use_megakernel: bool = True,
                 overlap_slices: int = 1) -> M.MaskedBatch:
    from . import pipeline as PL
    from .cost import seed_source_stats
    from ..kernels import megakernel as MK

    # runtime re-estimation (same as the local pipeline body): price every
    # compaction at the GLOBAL scale of the batches actually bound — a shard
    # holds capacity/p rows of each source
    seed_source_stats(root, {name: b.capacity * p
                             for name, b in shards.items()}, stats_memo)

    def compact(b: M.MaskedBatch, n: Node) -> M.MaskedBatch:
        return M.compact_to_estimate(b, n, stats_memo, slack, shards=p)

    # fused-span routing (DESIGN.md §10): require_forward keeps every
    # collective at a SOLO stage input, so a mega span runs the identical
    # kernel on every shard with no communication inside it
    routes = None
    if use_megakernel and len(stages) >= 2:
        routes = MK.plan_routes(stages,
                                {n: b.capacity for n, b in shards.items()},
                                require_forward=True)

    results: list[Optional[M.MaskedBatch]] = [None] * len(stages)

    def resolve(st, t, ref, how, order_t):
        node = st.top
        b = shards[ref[1]] if ref[0] == "source" else results[ref[1]]
        if how == "forward":
            # only forwarded streams keep their per-shard order; the
            # collectives below interleave rows, and _repartition /
            # _broadcast construct order-free batches accordingly
            if use_order and order_t and not b.order:
                b = b.with_order(order_t)
        elif how == "partition":
            # the optimizer's chosen partition columns (possibly a key
            # subset) ride on Stage.ship_keys; fall back to the operator key
            keys = None
            if st.ship_keys and len(st.ship_keys) > t:
                keys = st.ship_keys[t]
            if not keys:
                if isinstance(node, ReduceOp):
                    keys = node.key
                elif isinstance(node, (MatchOp, CoGroupOp)):
                    keys = node.left_key if t == 0 else node.right_key
                else:
                    raise ValueError(
                        f"partition ship on {type(node).__name__}")
            with scope("wire"):
                b = _repartition(b, keys, axis, p, overlap_slices)
            b = compact(b, st.input_plans[t].node)
        elif how == "broadcast":
            with scope("wire"):
                b = _broadcast(b, axis, p, overlap_slices)
        else:
            raise ValueError(how)
        return b

    def psum_scalar(count, aux, has_aux):
        # global (cross-shard) boundary counts: per-shard valid rows and
        # KAT/Match side-channels summed over the mesh axis — the
        # distributed leg of the adaptive feedback loop (DESIGN.md §9),
        # aggregated exactly where shuffle_stats counts the wire.  Aux-free
        # stages keep the composed convention of an un-psum'd -1.
        with scope("wire"):
            return (jax.lax.psum(count, axis),
                    jax.lax.psum(aux, axis) if has_aux else jnp.int32(-1))

    def psum_obs(valid, aux, has_aux):
        # sliced observation psums (DESIGN.md §12): under overlap each slot
        # slice contributes its own psum, summed on-shard afterwards —
        # integer sums, so the total is exactly the unsliced count while
        # each slice's collective can overlap neighboring compute
        k = overlap_slices if (overlap_slices > 1
                               and valid.shape[0] % overlap_slices == 0) \
            else 1
        parts = valid.astype(jnp.int32).reshape(k, -1)
        count = jnp.int32(0)
        with scope("wire"):
            for j in range(k):
                count = count + jax.lax.psum(jnp.sum(parts[j]), axis)
            return (count,
                    jax.lax.psum(aux, axis) if has_aux else jnp.int32(-1))

    entries = routes or tuple(("solo", i) for i in range(len(stages)))
    for entry in entries:
        if entry[0] == "solo":
            i = entry[1]
            st = stages[i]
            with PL.stage_scope(st):
                in_orders = st.in_orders or ((),) * len(st.inputs)
                ins = [resolve(st, t, ref, how, in_orders[t])
                       for t, (ref, how) in enumerate(zip(st.inputs,
                                                          st.ship))]
                obs: Optional[dict] = {} if observe is not None else None
                out = PL.execute_stage(st, ins, use_order, obs)
                if st.kind == "limit" and p > 1 and "broadcast" in st.ship:
                    # global WITH-TIES limit: the input was replicated, so
                    # every shard computed the IDENTICAL survivor mask on
                    # slot-aligned batches — deterministic per-slot
                    # ownership keeps the shards disjoint while their union
                    # is exactly the one-shard result
                    own = (jnp.arange(out.capacity, dtype=jnp.int32)
                           % jnp.int32(p)) == jax.lax.axis_index(axis)
                    out = M.MaskedBatch(dict(out.columns), out.valid & own,
                                        out.order)
                if observe is not None:
                    observe.append(psum_obs(
                        out.valid,
                        obs.get("groups", jnp.int32(-1)), "groups" in obs))
                results[i] = compact(out, st.top)
        else:
            _, i, j = entry
            span = stages[i:j]
            ins_per = []
            for k, st in enumerate(span):
                in_orders = st.in_orders or ((),) * len(st.inputs)
                ins_per.append([
                    None if (ref == ("stage", i + k - 1) and k > 0)
                    else resolve(st, t, ref, how, in_orders[t])
                    for t, (ref, how) in enumerate(zip(st.inputs, st.ship))])
            planned = [M.planned_capacity(st.top, stats_memo, slack,
                                          shards=p) for st in span]
            raw, span_obs, _ = MK.run_span(span, ins_per, planned, use_order)
            if observe is not None:
                # span interiors surface scalar counts (the megakernel's
                # own side-channel), so they psum unsliced
                observe.extend(psum_scalar(c, a, h) for (c, a), h in
                               zip(span_obs, MK.span_has_aux(span)))
            with PL.stage_scope(span[-1]):
                results[j - 1] = compact(raw, span[-1].top)
    return results[-1]


# ---------------------------------------------------------------------------
# Host-side source binding
# ---------------------------------------------------------------------------
def bind_global(root: Node, bindings: Mapping[str, RecordBatch],
                p: int) -> dict[str, M.MaskedBatch]:
    """Bind record batches to global mesh batches (p-divisible capacity).

    Honors `Source.partitioned_on` by pre-hashing rows to shard blocks with
    the same hash the device-side repartition uses; otherwise rows split
    into contiguous per-shard blocks.  Both layouts keep each shard a stable
    subsequence of the bound batch, so `Source.sorted_on` elisions stay
    sound inside `shard_map`."""
    sources = {n.name: n for n in root.iter_nodes()
               if isinstance(n, Source)}
    host: dict = {}
    nbytes = 0
    with span("prepare"):
        for name, src in sources.items():
            b = bindings[name].to_numpy().compact().project(
                list(src.out_schema.fields))
            n = b.capacity
            per = int(np.ceil(max(n, 1) / p))
            cap = per * p
            if src.partitioned_on:
                tgt = _key_hash_np(b.columns, src.partitioned_on, n) % np.uint64(p)
                order = np.argsort(tgt, kind="stable")
                counts = np.bincount(tgt.astype(np.int64), minlength=p)
                if counts.max() > per:
                    per = int(counts.max())
                    cap = per * p
                cols, valid = {}, np.zeros(cap, bool)
                starts = np.cumsum(counts) - counts
                dest = np.concatenate(
                    [np.arange(c) + t * per for t, c in enumerate(counts)]
                ).astype(np.int64)
                for f in b.fields:
                    arr = np.zeros(cap, dtype=b.columns[f].dtype)
                    arr[dest] = np.asarray(b.columns[f])[order]
                    cols[f] = arr
                valid[dest] = True
            else:
                cols = {f: np.concatenate(
                    [np.asarray(v), np.zeros(cap - n, dtype=v.dtype)])
                    for f, v in b.columns.items()}
                valid = np.arange(cap) < n
            host[name] = (cols, valid)
            nbytes += valid.nbytes + sum(v.nbytes for v in cols.values())
    count("bind_bytes", nbytes)
    with span("transfer"):
        return {name: M.MaskedBatch({f: jnp.asarray(v) for f, v in cols.items()},
                                    jnp.asarray(valid))
                for name, (cols, valid) in host.items()}


def _default_mesh(mesh: Optional[Mesh], axis: str,
                  mesh_shards: Optional[int]) -> Mesh:
    if mesh is not None:
        return mesh
    devs = np.array(jax.devices())
    if mesh_shards is None:
        # default stays "all devices"; REPRO_MESH_SHARDS narrows it when set
        mesh_shards = default_mesh_shards(len(devs)) \
            if MESH_SHARDS_ENV in os.environ else len(devs)
    return Mesh(devs[:max(1, min(int(mesh_shards), len(devs)))], (axis,))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def execute_distributed(plan: PhysPlan, bindings: Mapping[str, RecordBatch],
                        mesh: Optional[Mesh] = None, axis: str = "data",
                        slack: float = 4.0,
                        out_capacity: Optional[int] = None,
                        use_order: bool = True,
                        stats_store=None,
                        use_megakernel: Optional[bool] = None,
                        overlap_slices: Optional[int] = None,
                        mesh_shards: Optional[int] = None) -> RecordBatch:
    """Execute a physical plan data-parallel over `mesh[axis]` (one-shot:
    re-traces per call — long-lived callers want `DistributedPlan`).

    With `stats_store` (a `cost.StatsStore`), every stage's GLOBAL boundary
    counts — per-shard observations psum'd over the mesh axis inside the
    shard body — are folded into the store, feeding the same adaptive
    calibration loop the local serving handle uses (DESIGN.md §9).

    `overlap_slices` (default: `REPRO_OVERLAP_SLICES`, kill switch
    `REPRO_OVERLAP=0`) slices every collective into K software-pipelined
    transfers, bit-identical to the serial wire; `mesh_shards` bounds the
    mesh width when no explicit `mesh` is given (default: all devices, or
    `REPRO_MESH_SHARDS` when set)."""
    mesh = _default_mesh(mesh, axis, mesh_shards)
    p = mesh.shape[axis]
    if overlap_slices is None:
        overlap_slices = overlap_slices_default()

    global_batches = bind_global(plan.node, bindings, p)

    from . import pipeline as PL

    if use_megakernel is None:
        use_megakernel = PL._megakernel_default()
    stages = PL.lower_phys(plan)
    stats_memo: dict = {}
    names = sorted(global_batches)
    in_specs = tuple(jax.tree.map(lambda _: P(axis), global_batches[n])
                     for n in names)
    out_specs = P(axis) if stats_store is None else (P(axis), P())

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    def run(*shards):
        local = dict(zip(names, shards))
        observe: Optional[list] = None if stats_store is None else []
        if not stages:
            out = local[plan.node.name]
        else:
            out = _exec_stages(stages, local, axis, p, stats_memo, slack,
                               plan.node, use_order, observe, use_megakernel,
                               overlap_slices)
        if stats_store is None:
            return out
        # psum'd counts are replicated over the axis, so they leave the
        # shard body under a replicated out-spec
        with scope("wire"):
            src = {n: jax.lax.psum(jnp.sum(b.valid.astype(jnp.int32)), axis)
                   for n, b in local.items()}
        obs = {"src": src,
               "out": tuple(o[0] for o in (observe or ())),
               "aux": tuple(o[1] for o in (observe or ()))}
        return out, obs

    res = run(*[global_batches[n] for n in names])
    if stats_store is None:
        return res.to_record_batch()
    out, obs = res
    obs = jax.device_get(obs)
    PL.record_batch_obs(stats_store, stages, obs["src"], obs["out"],
                        obs["aux"])
    return out.to_record_batch()


class DistributedPlan:
    """Cached, jitted distributed serving handle (mesh analogue of
    `pipeline.CompiledPlan`).

    Lowers the physical plan once, then compiles one jitted shard_map
    executable per (layout, source signature, observe) key in a shared
    `pipeline.ExecutableCache` — the layout (per-stage ship strategies and
    partition columns via `pipeline._order_sig`, the mesh width `p`, the
    overlap slice count, megakernel routing) joins the executable identity,
    so plans that differ only in wire choices never alias and warm serving
    never re-traces.

    `run(bindings)` host-binds then executes; `run_device(staged)` is the
    mesh serving path for batches already bound via `bind` (device-resident
    across calls, no host round-trip)."""

    def __init__(self, plan, mesh: Optional[Mesh] = None, axis: str = "data",
                 mesh_shards: Optional[int] = None,
                 overlap_slices: Optional[int] = None, slack: float = 4.0,
                 use_order: bool = True,
                 use_megakernel: Optional[bool] = None, cache=None):
        from . import pipeline as PL

        plan = getattr(plan, "best", plan)   # OptResult / LayoutResult
        plan = getattr(plan, "plan", plan)   # RankedPlan
        if not isinstance(plan, PhysPlan):
            raise TypeError(f"expected a PhysPlan, got {type(plan).__name__}")
        self.plan = plan
        self.axis = axis
        self.mesh = _default_mesh(mesh, axis, mesh_shards)
        self.p = self.mesh.shape[axis]
        self.overlap_slices = overlap_slices_default() \
            if overlap_slices is None else max(1, int(overlap_slices))
        self.slack = float(slack)
        self.use_order = use_order
        self.use_megakernel = PL._megakernel_default() \
            if use_megakernel is None else use_megakernel
        self.cache = cache if cache is not None else PL.executable_cache()
        self.stages = PL.lower_phys(plan)
        self._sem = PL._Interned((
            PL.semantic_key(plan.node), PL._order_sig(self.stages), self.p,
            self.overlap_slices, self.use_megakernel, self.slack,
            self.use_order))

    # -- binding ---------------------------------------------------------
    def bind(self, bindings: Mapping[str, RecordBatch]) -> dict:
        """Host-bind a request to global mesh batches (reusable across
        `run_device` calls)."""
        with span("bind_device"):
            return bind_global(self.plan.node, bindings, self.p)

    def _source_sig(self, staged: Mapping[str, M.MaskedBatch]) -> tuple:
        return tuple(
            (n, staged[n].capacity,
             tuple((f, str(v.dtype))
                   for f, v in staged[n].columns.items()))
            for n in sorted(staged))

    # -- execution -------------------------------------------------------
    def _executable(self, staged: Mapping[str, M.MaskedBatch],
                    observe: bool):
        key = (self._sem, self._source_sig(staged), observe)
        fn = self.cache.get(key)
        if fn is not None:
            return fn
        names = sorted(staged)
        in_specs = tuple(jax.tree.map(lambda _: P(self.axis), staged[n])
                         for n in names)
        out_specs = P(self.axis) if not observe else (P(self.axis), P())
        plan, p, axis, cache = self.plan, self.p, self.axis, self.cache
        stages = self.stages
        slack = self.slack
        use_order, use_megakernel = self.use_order, self.use_megakernel
        overlap = self.overlap_slices

        @functools.partial(
            jax.shard_map, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)
        def run(*shards):
            cache.traces += 1  # trace-time side effect (CacheStats.traces)
            local = dict(zip(names, shards))
            obs_acc: Optional[list] = [] if observe else None
            if not stages:
                out = local[plan.node.name]
            else:
                out = _exec_stages(stages, local, axis, p, {}, slack,
                                   plan.node, use_order, obs_acc,
                                   use_megakernel, overlap)
            if not observe:
                return out
            with scope("wire"):
                src = {n: jax.lax.psum(jnp.sum(b.valid.astype(jnp.int32)),
                                       axis)
                       for n, b in local.items()}
            return out, {"src": src,
                         "out": tuple(o[0] for o in (obs_acc or ())),
                         "aux": tuple(o[1] for o in (obs_acc or ()))}

        fn = jax.jit(run)
        self.cache.put(key, fn)
        return fn

    def run_device(self, staged: Mapping[str, M.MaskedBatch],
                   stats_store=None) -> M.MaskedBatch:
        """Execute on already-bound global batches; returns the global
        output batch (device-resident — chain into further mesh steps)."""
        from . import pipeline as PL

        with span("run_device"):
            with span("lookup"):
                fn = self._executable(staged, stats_store is not None)
                args = [staged[n] for n in sorted(staged)]
            with span("dispatch"):
                res = fn(*args)
            if stats_store is None:
                return res
            out, obs = res
            obs = jax.device_get(obs)
            PL.record_batch_obs(stats_store, self.stages, obs["src"],
                                obs["out"], obs["aux"])
            return out

    def run(self, bindings: Mapping[str, RecordBatch],
            stats_store=None) -> RecordBatch:
        """Host-bind + execute + fetch: the one-call serving step."""
        out = self.run_device(self.bind(bindings), stats_store=stats_store)
        return out.to_record_batch()

    def cache_stats(self):
        return self.cache.stats()


def compile_distributed(plan, **kwargs) -> DistributedPlan:
    """Build a `DistributedPlan` from a PhysPlan / RankedPlan / OptResult
    (see `DistributedPlan` for the kwargs)."""
    return DistributedPlan(plan, **kwargs)
