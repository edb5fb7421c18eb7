"""UDF-facing record API + black-box UDF property model.

UDFs are ordinary Python functions written against a tiny record API, exactly
mirroring the paper's 3-address record API (Sec. 5):

    getField        -> view.get("name")
    OutputRecord(ir) -> ir.copy()            (Implicit Copy)
    OutputRecord()   -> empty()              (Implicit Projection)
    OutputRecord(i1,i2) -> left.concat(right) (binary implicit copy)
    setField        -> builder.set("name", value)
    explicit proj.  -> builder.drop("name")
    emit            -> out.emit(builder[, where=mask])

UDFs are *vectorized*: `get` returns the whole column, and data-dependent
control flow ("if (a < 0) skip") is expressed as the `where=` emission mask.
This keeps them executable eagerly (numpy), under jit (masked), and traceable
for the jaxpr analyzer — while remaining black boxes to the optimizer, which
only ever sees the derived `UdfProperties`.

Key-at-a-time (Reduce/CoGroup) UDFs receive a `GroupView` with per-group
aggregation methods and may either emit one record per group (`out.emit`) or
pass through the group's records (`out.emit_records`), optionally filtered by
a per-group mask — the clickstream "filter buy sessions" pattern.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, Mapping, Optional, Sequence

import numpy as np


# ---------------------------------------------------------------------------
# Emission cardinality classes (drive the KGP condition, Def. 5)
# ---------------------------------------------------------------------------
class Card(enum.Enum):
    ONE = "one"                  # |f(r)| = 1 for every record
    AT_MOST_ONE = "at_most_one"  # |f(r)| <= 1 (a filter)
    MANY = "many"                # anything else


class KatEmit(enum.Enum):
    PER_GROUP = "per_group"            # exactly one record per key group
    PER_GROUP_FILTER = "per_group_filter"  # <=1 record per key group
    PASSTHROUGH = "passthrough"        # all records of group, one-for-one
    PASSTHROUGH_FILTER = "passthrough_filter"  # whole groups kept or dropped
    MANY = "many"


# ---------------------------------------------------------------------------
# Decomposable aggregation (SOFA-style aggregation splitting)
# ---------------------------------------------------------------------------
# Aggregate kinds whose per-group results compose across a partition of the
# group's records: kind(kind(part_1), ..., kind(part_k)) == kind(whole) for
# sum/min/max, count via sum-of-counts, and mean via the sum+count rewrite.
DECOMPOSABLE_AGGS = ("sum", "min", "max", "count", "mean")


@dataclasses.dataclass(frozen=True)
class CombineRecipe:
    """How to split a PER_GROUP Reduce UDF into a local pre-aggregation
    (combiner) plus a final merge.

    `sites` lists the UDF's GroupView aggregate call sites in (deterministic)
    call order — one of `DECOMPOSABLE_AGGS` each.  The combiner re-runs the
    UDF per partition, capturing each site's partial value(s) as extra
    columns (`partial_fields`); the merge re-runs the UDF with every site
    answered by merge-reducing those partials instead of touching records.
    `columns` maps each emitted output column to how it is rebuilt at merge
    time: 'key' (group-constant key attribute), one of the aggregate kinds
    (the column IS site i's untouched result), or 'expr' (an arithmetic
    composition of aggregate results, replayed by re-running the UDF).

    A recipe is only attached to `UdfProperties.combine` after the split has
    been verified against an eager differential run (sca.decompose.verify) —
    analyzers may propose, the eager run disposes.
    """

    sites: tuple = ()        # aggregate kind per call site, in call order
    columns: tuple = ()      # (output_field, 'key'|kind|'expr') pairs

    def partial_fields(self, prefix: str = "_pt") -> tuple:
        """Names of the partial columns the combiner emits, site-ordered.
        `mean` decomposes into two partials (sum + count)."""
        out = []
        for i, kind in enumerate(self.sites):
            if kind == "mean":
                out.append(f"{prefix}{i}s")
                out.append(f"{prefix}{i}c")
            else:
                out.append(f"{prefix}{i}")
        return tuple(out)


@dataclasses.dataclass(frozen=True)
class UdfProperties:
    """The handful of properties the optimizer needs (Defs. 2-5)."""

    reads: frozenset            # R_f over global attribute names
    writes: frozenset           # W_f: modified + newly-created attributes
    adds: frozenset             # newly created attributes (subset of writes)
    drops: frozenset            # explicitly projected-out attributes
    implicit_copy: bool         # copy-constructor vs projection semantics
    card: Card                  # RAT emission cardinality
    filter_fields: frozenset    # attrs the emission mask may depend on
    kat_emit: Optional[KatEmit] = None  # set for Reduce/CoGroup UDFs
    copies: frozenset = frozenset()  # explicit unmodified copies (schema only,
                                     # NOT writes — paper's explicit-copy case)
    source: str = "manual"      # 'manual' | 'bytecode-sca' | 'jaxpr-sca'
    # True when the UDF enumerates its input schema (`view.fields`): its
    # behaviour then depends on the ambient schema, so rewrites that change
    # the input schema are blocked.  The paper's record API accesses fields
    # by static positions, which corresponds to schema_dependent=False;
    # first()/record_builder() are safe built-ins (group-constant/identity
    # extension semantics) and do NOT set this flag.
    schema_dependent: bool = False
    # Set (by the SCA analyzers, after eager verification) when the KAT UDF's
    # emissions are built only from decomposable per-group aggregates, so a
    # Reduce over it may be split into combiner + merge (reorder.split_reduce).
    combine: Optional[CombineRecipe] = None

    def satisfies_kgp(self, key_fields: frozenset) -> bool:
        """Key Group Preservation (Def. 5) w.r.t. `key_fields`.

        RAT: |f(r)|=1 always qualifies; a filter qualifies iff its decision
        depends only on a subset of the key.  KAT: one-for-one passthrough
        qualifies; group-filtered passthrough qualifies iff the filter fields
        are within the key.  Aggregating emission changes group cardinality
        and never qualifies (conservative).
        """
        key_fields = frozenset(key_fields)
        if self.kat_emit is None:
            if self.card is Card.ONE:
                return True
            if self.card is Card.AT_MOST_ONE:
                return self.filter_fields <= key_fields
            return False
        if self.kat_emit is KatEmit.PASSTHROUGH:
            return True
        if self.kat_emit is KatEmit.PASSTHROUGH_FILTER:
            return self.filter_fields <= key_fields
        return False

    def is_superset_of(self, other: "UdfProperties") -> bool:
        """Safety check: conservative estimates must be supersets (Sec. 5)."""
        return (self.reads >= other.reads and self.writes >= other.writes
                and self.adds >= other.adds)


# ---------------------------------------------------------------------------
# Views handed to UDFs
# ---------------------------------------------------------------------------
class InputView:
    """Read-only view of a record batch (one column per attribute)."""

    def __init__(self, columns: Mapping[str, object]):
        self._columns = dict(columns)

    def get(self, name: str):
        if name not in self._columns:
            raise KeyError(f"UDF read of unknown attribute {name!r}")
        return self._columns[name]

    @property
    def fields(self) -> tuple:
        return tuple(self._columns)

    def copy(self) -> "OutputBuilder":
        """Paper's `new OutputRecord($ir)` — Implicit Copy."""
        return OutputBuilder(base=dict(self._columns), implicit_copy=True)

    def concat(self, other: "InputView") -> "OutputBuilder":
        """Paper's `new OutputRecord($i1,$i2)` — binary implicit copy."""
        base = dict(self._columns)
        for k, v in other._columns.items():
            if k in base:
                raise KeyError(f"concat collision on attribute {k!r}")
            base[k] = v
        return OutputBuilder(base=base, implicit_copy=True)


def empty() -> "OutputBuilder":
    """Paper's `new OutputRecord()` — Implicit Projection."""
    return OutputBuilder(base={}, implicit_copy=False)


class OutputBuilder:
    """Mutable output record under construction (vectorized)."""

    def __init__(self, base: dict, implicit_copy: bool, first_fields=()):
        self._cols = dict(base)
        self.implicit_copy = implicit_copy
        self.set_fields: set = set()
        self.dropped: set = set()
        # fields populated by GroupView.first(): identity for key attributes
        self.first_fields: set = set(first_fields)

    def set(self, name: str, value) -> "OutputBuilder":
        self._cols[name] = value
        self.set_fields.add(name)
        self.dropped.discard(name)
        return self

    def drop(self, name: str) -> "OutputBuilder":
        self._cols.pop(name, None)
        self.dropped.add(name)
        self.set_fields.discard(name)
        return self

    def columns(self) -> dict:
        return dict(self._cols)


@dataclasses.dataclass
class Emission:
    builder: OutputBuilder
    where: Optional[object] = None        # per-record mask (RAT) or None
    records: bool = False                 # KAT passthrough emission
    group_where: Optional[object] = None  # per-group mask for passthrough


class Collector:
    """The `out` argument of every UDF."""

    def __init__(self):
        self.emissions: list[Emission] = []

    def emit(self, builder: OutputBuilder, where=None):
        self.emissions.append(Emission(builder, where=where))

    def emit_records(self, builder: Optional[OutputBuilder] = None, where=None):
        """KAT passthrough: emit all records of each group (optionally only
        for groups where the per-group mask holds). `builder`, if given, is a
        per-record builder carrying modified columns."""
        self.emissions.append(Emission(builder, records=True, group_where=where))


# ---------------------------------------------------------------------------
# Group view for key-at-a-time UDFs (Reduce / CoGroup)
# ---------------------------------------------------------------------------
class SegmentOps:
    """Backend for per-segment reductions over a key-sorted batch."""

    def sum(self, values):  # pragma: no cover - interface
        raise NotImplementedError

    def max(self, values):
        raise NotImplementedError

    def min(self, values):
        raise NotImplementedError

    def count(self):
        raise NotImplementedError

    def first(self, values):
        raise NotImplementedError

    def any(self, mask):
        raise NotImplementedError

    def all(self, mask):
        raise NotImplementedError

    def broadcast(self, per_group):
        raise NotImplementedError


class EagerSegmentOps(SegmentOps):
    """numpy reduceat-based segment reductions (host pipeline mode)."""

    def __init__(self, starts: np.ndarray, n: int, segment_ids: np.ndarray):
        self.starts = starts
        self.n = n
        self.segment_ids = segment_ids

    def _reduceat(self, ufunc, values):
        values = np.asarray(values)
        if len(self.starts) == 0:
            return values[:0]
        return ufunc.reduceat(values, self.starts)

    def sum(self, values):
        return self._reduceat(np.add, values)

    def max(self, values):
        return self._reduceat(np.maximum, values)

    def min(self, values):
        return self._reduceat(np.minimum, values)

    def count(self):
        return np.diff(np.append(self.starts, self.n))

    def mean(self, values):
        return self.sum(values) / np.maximum(self.count(), 1)

    def first(self, values):
        return np.asarray(values)[self.starts]

    def any(self, mask):
        return self.sum(np.asarray(mask).astype(np.int64)) > 0

    def all(self, mask):
        return self.sum(np.asarray(mask).astype(np.int64)) == self.count()

    def broadcast(self, per_group):
        return np.asarray(per_group)[self.segment_ids]


class DomainSegmentOps(SegmentOps):
    """Segment reductions over a *fixed key domain* of `num_segments` groups,
    some of which may be empty (CoGroup aligns both inputs on the union key
    domain).  Input arrays are key-sorted; `segment_ids` maps each record to
    its dense domain code."""

    def __init__(self, segment_ids: np.ndarray, num_segments: int):
        self.segment_ids = np.asarray(segment_ids)
        self.num_segments = int(num_segments)

    def sum(self, values):
        v = np.asarray(values)
        out = np.bincount(self.segment_ids, weights=v.astype(np.float64),
                          minlength=self.num_segments)
        if np.issubdtype(v.dtype, np.integer) or v.dtype == bool:
            return out.astype(np.int64)
        return out.astype(v.dtype)

    def max(self, values):
        v = np.asarray(values)
        fill = (np.finfo(v.dtype).min if np.issubdtype(v.dtype, np.floating)
                else np.iinfo(v.dtype).min)
        out = np.full(self.num_segments, fill, dtype=v.dtype)
        np.maximum.at(out, self.segment_ids, v)
        return out

    def min(self, values):
        v = np.asarray(values)
        fill = (np.finfo(v.dtype).max if np.issubdtype(v.dtype, np.floating)
                else np.iinfo(v.dtype).max)
        out = np.full(self.num_segments, fill, dtype=v.dtype)
        np.minimum.at(out, self.segment_ids, v)
        return out

    def count(self):
        return np.bincount(self.segment_ids, minlength=self.num_segments).astype(np.int64)

    def mean(self, values):
        return self.sum(values) / np.maximum(self.count(), 1)

    def first(self, values):
        v = np.asarray(values)
        out = np.zeros(self.num_segments, dtype=v.dtype)
        # reversed scatter: the first occurrence wins
        out[self.segment_ids[::-1]] = v[::-1]
        return out

    def any(self, mask):
        return self.sum(np.asarray(mask).astype(np.int64)) > 0

    def all(self, mask):
        c = self.count()
        return (self.sum(np.asarray(mask).astype(np.int64)) == c) & (c > 0)

    def broadcast(self, per_group):
        return np.asarray(per_group)[self.segment_ids]


class JitSegmentOps(SegmentOps):
    """Segment reductions with a static segment count.

    Two regimes:

    * `is_start` given (the masked Reduce path): segment ids are sorted AND
      densely numbered in row order, with `is_start` marking the first VALID
      row of each segment.  Aggregates then run scatter-free: `first` is a
      gather at segment starts, integer sums/counts difference a blocked
      prefix sum (exact), float sums and max/min run a log-depth segmented
      scan gathered at segment ends (`repro.core.scans`) — an order of
      magnitude cheaper than `jax.ops.segment_*`'s element-wise scatters.
    * no `is_start` (CoGroup sides, external callers): the original
      `jax.ops.segment_*` path, which tolerates segment ids that skip
      numbers on one side.  `first()` infers starts from id transitions —
      only sound when valid rows are contiguous, which that path guarantees.
    """

    def __init__(self, segment_ids, num_segments: int, record_valid=None,
                 is_start=None):
        import jax

        self._jax = jax
        self.segment_ids = segment_ids
        self.num_segments = num_segments
        self.record_valid = record_valid
        self.is_start = is_start
        self._pos = None  # lazy (starts, ends, ngroups), shared across calls

    def _masked(self, values, fill):
        import jax.numpy as jnp

        values = jnp.asarray(values)
        if self.record_valid is None:
            return values
        return jnp.where(self.record_valid, values, jnp.asarray(fill, values.dtype))

    # -- sorted/dense fast path helpers -------------------------------------
    def _starts_ends(self):
        """Row positions of each segment's first and last slot (computed once
        per stage input, reused by every aggregate call site).  Positions for
        segments past the live group count are clamped garbage — their
        aggregates are masked by the executor's `group_valid` prefix."""
        if self._pos is None:
            import jax.numpy as jnp

            from . import scans

            n = self.is_start.shape[0]
            u = scans.select(self.is_start, self.num_segments + 1)
            starts = jnp.minimum(u[:-1], n - 1).astype(jnp.int32)
            ends = jnp.clip(u[1:] - 1, 0, n - 1).astype(jnp.int32)
            self._pos = (starts, ends,
                         jnp.sum(self.is_start, dtype=jnp.int32))
        return self._pos

    def _prefix_diff(self, vm):
        """Per-segment totals by differencing a blocked prefix sum — exact
        for integer/bool values, so counts and integer sums skip the scan."""
        from . import scans

        starts, ends, _ = self._starts_ends()
        cv = scans.cumsum(vm)
        return cv[ends] - (cv[starts] - vm[starts])

    # below this many rows a single fused scatter beats the log-depth scan's
    # ~40 dispatch-bound elementwise ops (XLA CPU scatter costs ~60ns/row,
    # so the crossover sits around 2k rows)
    _SCAN_MIN_ROWS = 2048

    def _seg_reduce(self, vm, op):
        from ..obs import count
        from . import scans

        if vm.shape[0] < self._SCAN_MIN_ROWS:
            seg_fn = {"add": self._jax.ops.segment_sum,
                      "max": self._jax.ops.segment_max,
                      "min": self._jax.ops.segment_min}[op]
            return seg_fn(vm, self.segment_ids, self.num_segments)
        _, ends, _ = self._starts_ends()
        count("segment.scan", 1)
        return scans.segmented_scan(vm, self.is_start, op)[ends]

    # -- aggregates ----------------------------------------------------------
    def sum(self, values):
        import jax.numpy as jnp

        if self.is_start is not None:
            vm = self._masked(values, 0)
            if jnp.issubdtype(vm.dtype, jnp.floating):
                # the scan sums in tree order (no prefix differencing), so
                # float aggregates see no catastrophic cancellation
                return self._seg_reduce(vm, "add")
            return self._prefix_diff(vm)
        return self._jax.ops.segment_sum(
            self._masked(values, 0), self.segment_ids, self.num_segments)

    def max(self, values):
        import jax.numpy as jnp

        v = jnp.asarray(values)
        fill = jnp.finfo(v.dtype).min if jnp.issubdtype(v.dtype, jnp.floating) else jnp.iinfo(v.dtype).min
        if self.is_start is not None:
            return self._seg_reduce(self._masked(v, fill), "max")
        return self._jax.ops.segment_max(self._masked(v, fill), self.segment_ids,
                                         self.num_segments)

    def min(self, values):
        import jax.numpy as jnp

        v = jnp.asarray(values)
        fill = jnp.finfo(v.dtype).max if jnp.issubdtype(v.dtype, jnp.floating) else jnp.iinfo(v.dtype).max
        if self.is_start is not None:
            return self._seg_reduce(self._masked(v, fill), "min")
        return self._jax.ops.segment_min(self._masked(v, fill), self.segment_ids,
                                         self.num_segments)

    def count(self):
        import jax.numpy as jnp

        if self.is_start is not None:
            ones = self._masked(jnp.ones_like(self.segment_ids), 0)
            return self._prefix_diff(ones)
        ones = jnp.ones_like(self.segment_ids)
        return self._jax.ops.segment_sum(self._masked(ones, 0), self.segment_ids,
                                         self.num_segments)

    def mean(self, values):
        import jax.numpy as jnp

        return self.sum(values) / jnp.maximum(self.count(), 1)

    def first(self, values):
        import jax.numpy as jnp

        v = jnp.asarray(values)
        sid = self.segment_ids
        if self.is_start is not None:
            starts, _, ngroups = self._starts_ends()
            k = jnp.arange(self.num_segments)
            # zero (not garbage) past the live groups, matching the legacy
            # segment_sum-of-contributions behaviour
            return jnp.where(k < ngroups, v[starts], jnp.zeros((), v.dtype))
        is_start = jnp.concatenate([jnp.ones((1,), bool),
                                    sid[1:] != sid[:-1]])
        if self.record_valid is not None:
            is_start = is_start & self.record_valid
        contrib = jnp.where(is_start, v, jnp.zeros((), v.dtype))
        return self._jax.ops.segment_sum(contrib, sid, self.num_segments)

    def any(self, mask):
        return self.sum(mask.astype(np.int32)) > 0

    def all(self, mask):
        return self.sum(mask.astype(np.int32)) == self.count()

    def broadcast(self, per_group):
        import jax.numpy as jnp

        return jnp.asarray(per_group)[self.segment_ids]


class GroupView:
    """View over all key groups of a KAT operator input, vectorized across
    groups: per-record accessors return full columns (key-sorted), aggregate
    methods return one value per group."""

    def __init__(self, columns: Mapping[str, object], segops: SegmentOps,
                 key_fields: Sequence[str]):
        self._columns = dict(columns)
        self._seg = segops
        self.key_fields = tuple(key_fields)

    # per-record access (key-sorted order)
    def get(self, name: str):
        if name not in self._columns:
            raise KeyError(f"UDF read of unknown attribute {name!r}")
        return self._columns[name]

    @property
    def fields(self) -> tuple:
        return tuple(self._columns)

    # per-group aggregates
    def sum(self, name_or_values):
        return self._seg.sum(self._resolve(name_or_values))

    def max(self, name_or_values):
        return self._seg.max(self._resolve(name_or_values))

    def min(self, name_or_values):
        return self._seg.min(self._resolve(name_or_values))

    def mean(self, name_or_values):
        return self._seg.mean(self._resolve(name_or_values))

    def count(self):
        return self._seg.count()

    def any(self, values):
        return self._seg.any(values)

    def all(self, values):
        return self._seg.all(values)

    def broadcast(self, per_group):
        """Per-group values -> per-record values (gather by segment id)."""
        return self._seg.broadcast(per_group)

    def first(self) -> OutputBuilder:
        """Representative record per group (implicit copy of group firsts).
        NOTE: non-key fields are order-dependent — data sets are unordered
        (Sec. 2.2), so order-insensitive UDFs should prefer `keys()`."""
        return OutputBuilder(
            base={k: self._seg.first(v) for k, v in self._columns.items()},
            implicit_copy=True, first_fields=tuple(self._columns))

    def first_of(self, name: str):
        """Per-group first value of one attribute (sound pass-through for
        attributes known to be group-constant)."""
        return self._seg.first(self._columns[name])

    def keys(self) -> OutputBuilder:
        """Per-group key values only (deterministic: keys are constant within
        a group).  Implicit projection of all non-key fields."""
        return OutputBuilder(
            base={k: self._seg.first(self._columns[k]) for k in self.key_fields},
            implicit_copy=False, first_fields=tuple(self.key_fields))

    def record_builder(self) -> OutputBuilder:
        """Per-record builder for modified passthrough emission."""
        return OutputBuilder(base=dict(self._columns), implicit_copy=True)

    def _resolve(self, name_or_values):
        if isinstance(name_or_values, str):
            return self._columns[name_or_values]
        return name_or_values


UdfFn = Callable  # (views..., Collector) -> None
