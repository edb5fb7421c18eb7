"""jaxpr-based UDF analysis — the JAX-native "opening of the black box".

The UDF is traced with one abstract array per input attribute; the resulting
jaxpr is a purely-functional 3-address code (the exact analogue of the
paper's Sec. 5 IR).  Dependence analysis over it yields:

* read set  R_f — attributes whose input var (transitively) reaches any
  emitted column of a *different* attribute, or any emission mask (Def. 3:
  an identity pass-through of attribute n to attribute n does NOT put n in R).
* write set W_f — emitted columns that are not the identity of the same-named
  input var, plus newly-created attributes (Def. 2).
* filter_fields — attributes reaching a `where=` / group-filter mask, giving
  the exact KGP precondition (Def. 5 case 2).

Compared to the paper's conservative bytecode analysis this is exact on the
traced path (vectorized UDFs have a single path — control flow is data, not
branches), so it strictly enlarges the set of valid reorderings.  Safety is
preserved: conservatism is only needed where tracing fails, in which case the
caller falls back to the bytecode analyzer.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence

import numpy as np

import jax

from jax.extend import core as jcore

from ..udf import Card, Collector, KatEmit, UdfProperties
from .. import invoke


# ---------------------------------------------------------------------------
# Dependence analysis over a (closed) jaxpr
# ---------------------------------------------------------------------------
def _var_key(v):
    return id(v)


def _jaxpr_input_deps(jaxpr) -> dict:
    """Map every var (by id) -> set of invar positions it depends on.
    Conservative inside equations: every output depends on every input."""
    dep: dict = {}
    for i, v in enumerate(jaxpr.invars):
        dep[_var_key(v)] = {i}
    for eqn in jaxpr.eqns:
        s: set = set()
        for iv in eqn.invars:
            if not isinstance(iv, jcore.Literal):
                s |= dep.get(_var_key(iv), set())
        for ov in eqn.outvars:
            dep[_var_key(ov)] = set(s)
    return dep


class _TraceResult:
    def __init__(self, fields, emissions_meta, out_deps, out_identity):
        self.fields = fields
        self.emissions_meta = emissions_meta  # list of dicts describing emissions
        self.out_deps = out_deps              # per-output set of input field names
        self.out_identity = out_identity      # per-output: field name if identity else None


def _trace(udf_runner, in_fields: Sequence[str], dummy_arrays: Sequence) -> _TraceResult:
    """Trace `udf_runner(*arrays) -> flat outputs` and analyze dependence."""
    meta: dict = {}

    def fn(*arrays):
        col = udf_runner(*arrays)
        flat = []
        spec = []
        for ei, em in enumerate(col.emissions):
            cols = em.builder.columns() if em.builder is not None else {}
            for f, v in cols.items():
                spec.append(("col", ei, f))
                flat.append(v)
            if em.where is not None:
                spec.append(("where", ei, None))
                flat.append(em.where)
            if em.group_where is not None:
                spec.append(("gwhere", ei, None))
                flat.append(em.group_where)
        meta["spec"] = spec
        meta["emissions"] = [
            dict(records=em.records,
                 has_where=em.where is not None,
                 has_gwhere=em.group_where is not None,
                 implicit_copy=(em.builder.implicit_copy if em.builder is not None else None),
                 set_fields=frozenset(em.builder.set_fields) if em.builder is not None else frozenset(),
                 dropped=frozenset(em.builder.dropped) if em.builder is not None else frozenset(),
                 first_fields=frozenset(em.builder.first_fields) if em.builder is not None else frozenset(),
                 out_fields=tuple(em.builder.columns()) if em.builder is not None else ())
            for em in col.emissions
        ]
        # Non-array python scalars must still appear as outputs for dtype info.
        import jax.numpy as jnp

        return [jnp.asarray(v) for v in flat]

    closed = jax.make_jaxpr(fn)(*dummy_arrays)
    jaxpr = closed.jaxpr
    dep = _jaxpr_input_deps(jaxpr)
    invar_by_pos = {i: v for i, v in enumerate(jaxpr.invars)}
    invar_id_to_field = {_var_key(v): in_fields[i] for i, v in invar_by_pos.items()}

    out_deps, out_identity = [], []
    for ov in jaxpr.outvars:
        if isinstance(ov, jcore.Literal):
            out_deps.append(set())
            out_identity.append(None)
            continue
        positions = dep.get(_var_key(ov), set())
        out_deps.append({in_fields[p] for p in positions})
        out_identity.append(invar_id_to_field.get(_var_key(ov)))
    return _TraceResult(list(in_fields), meta["emissions"],
                        dict(spec=meta["spec"], deps=out_deps, identity=out_identity),
                        None)


def _properties_from_trace(tr: _TraceResult, in_fields: Sequence[str],
                           kat: bool, key_fields: Sequence[str] = (),
                           kat_value_identity_ok: bool = False) -> UdfProperties:
    spec = tr.out_deps["spec"]
    deps = tr.out_deps["deps"]
    identity = tr.out_deps["identity"]
    in_set = frozenset(in_fields)
    key_set = frozenset(key_fields)

    reads: set = set()
    writes: set = set()
    adds: set = set()
    drops: set = set()
    copies: set = set()
    filter_fields: set = set()

    for (tag, ei, f), d, ident in zip(spec, deps, identity):
        if tag in ("where", "gwhere"):
            reads |= d
            filter_fields |= d
            continue
        em = tr.emissions_meta[ei]
        is_passthrough_like = (not kat) or em["records"] or kat_value_identity_ok
        is_key_first = (kat and f in key_set and f in em["first_fields"]
                        and f not in em["set_fields"])
        if f not in in_set:
            adds.add(f)
            writes.add(f)
            reads |= d
        elif ident == f and is_passthrough_like:
            copies.add(f)  # identity pass-through: not read/written (Defs. 2/3)
        elif is_key_first:
            copies.add(f)  # per-group first() of a key attribute is the key itself
        else:
            writes.add(f)
            reads |= {x for x in d if x != f} | ({f} if f in d and ident != f else set())
            if ident is not None and ident != f:
                reads.add(ident)
            # a computed value of field f from field f alone still reads f
            if f in d and ident != f:
                reads.add(f)

    implicit_copy = any(em["implicit_copy"] for em in tr.emissions_meta
                        if em["implicit_copy"] is not None) or \
        any(em["records"] for em in tr.emissions_meta)
    for em in tr.emissions_meta:
        drops |= em["dropped"]

    # Every input field no emission carries is projected away — this covers
    # implicit projection (empty()), AND implicit copies whose base only
    # spans part of the input (e.g. CoGroup UDFs emitting one side's first()).
    if tr.emissions_meta:
        emitted = set()
        for em in tr.emissions_meta:
            if em["records"] and not em["out_fields"]:
                emitted |= in_set  # bare passthrough carries everything
            else:
                emitted |= set(em["out_fields"])
        drops |= in_set - emitted
    writes |= drops  # projecting an attribute away conflicts with readers

    # Cardinality classification
    n_emits = len(tr.emissions_meta)
    rat_card = Card.MANY
    kat_emit: Optional[KatEmit] = None
    if kat:
        recs = [em for em in tr.emissions_meta if em["records"]]
        groups = [em for em in tr.emissions_meta if not em["records"]]
        if n_emits == 1 and recs:
            kat_emit = (KatEmit.PASSTHROUGH_FILTER if recs[0]["has_gwhere"]
                        else KatEmit.PASSTHROUGH)
        elif n_emits == 1 and groups:
            kat_emit = (KatEmit.PER_GROUP_FILTER if groups[0]["has_where"] or groups[0]["has_gwhere"]
                        else KatEmit.PER_GROUP)
        else:
            kat_emit = KatEmit.MANY
        rat_card = Card.MANY
        reads |= key_set  # key attributes always belong to the read set
    else:
        if n_emits == 1:
            rat_card = Card.AT_MOST_ONE if tr.emissions_meta[0]["has_where"] else Card.ONE
        elif n_emits == 0:
            rat_card = Card.AT_MOST_ONE
        else:
            rat_card = Card.MANY

    return UdfProperties(
        reads=frozenset(reads), writes=frozenset(writes), adds=frozenset(adds),
        drops=frozenset(drops), implicit_copy=implicit_copy, card=rat_card,
        filter_fields=frozenset(filter_fields), kat_emit=kat_emit,
        copies=frozenset(copies - writes), source="jaxpr-sca")


# ---------------------------------------------------------------------------
# Entry points per operator kind
# ---------------------------------------------------------------------------
def _dummy(dtype, n=4):
    dt = np.dtype(dtype)
    if np.issubdtype(dt, np.floating):
        return np.linspace(1.0, 2.0, n).astype(dt)
    return (np.arange(n) % 3).astype(dt)


def analyze_map(udf, in_schema) -> UdfProperties:
    fields = list(in_schema.fields)
    arrays = [_dummy(in_schema.dtypes[f]) for f in fields]

    def runner(*arrs):
        return invoke.run_map_udf(udf, dict(zip(fields, arrs)))

    tr = _trace(runner, fields, arrays)
    return _properties_from_trace(tr, fields, kat=False)


def analyze_reduce(udf, in_schema, key: Sequence[str]) -> UdfProperties:
    from ..udf import JitSegmentOps

    fields = list(in_schema.fields)
    arrays = [_dummy(in_schema.dtypes[f]) for f in fields]
    seg_ids = np.array([0, 0, 1, 1], dtype=np.int32)

    def runner(*arrs):
        segops = JitSegmentOps(seg_ids, 2)
        return invoke.run_kat_udf(udf, dict(zip(fields, arrs)), segops, key)

    tr = _trace(runner, fields, arrays)
    props = _properties_from_trace(tr, fields, kat=True, key_fields=key)
    # Decomposability (aggregation splitting): probe the UDF's aggregate call
    # sites and verify the split differentially before recording the recipe.
    from . import decompose

    recipe = decompose.detect(udf, in_schema, key, props)
    if recipe is not None:
        import dataclasses

        props = dataclasses.replace(props, combine=recipe)
    return props


def analyze_pair(udf, left_schema, right_schema,
                 left_key: Sequence[str] = (), right_key: Sequence[str] = ()) -> UdfProperties:
    lf, rf = list(left_schema.fields), list(right_schema.fields)
    arrays = [_dummy(left_schema.dtypes[f]) for f in lf] + \
             [_dummy(right_schema.dtypes[f]) for f in rf]

    def runner(*arrs):
        lcols = dict(zip(lf, arrs[:len(lf)]))
        rcols = dict(zip(rf, arrs[len(lf):]))
        return invoke.run_pair_udf(udf, lcols, rcols)

    tr = _trace(runner, lf + rf, arrays)
    props = _properties_from_trace(tr, lf + rf, kat=False)
    # Match keys behave like reads of the conceptual f' (Sec. 4.3.1)
    if left_key or right_key:
        import dataclasses

        props = dataclasses.replace(
            props, reads=props.reads | frozenset(left_key) | frozenset(right_key))
    return props


def analyze_cogroup(udf, left_schema, right_schema, left_key, right_key) -> UdfProperties:
    from ..udf import JitSegmentOps

    lf, rf = list(left_schema.fields), list(right_schema.fields)
    arrays = [_dummy(left_schema.dtypes[f]) for f in lf] + \
             [_dummy(right_schema.dtypes[f]) for f in rf]
    seg_ids = np.array([0, 0, 1, 1], dtype=np.int32)

    def runner(*arrs):
        lcols = dict(zip(lf, arrs[:len(lf)]))
        rcols = dict(zip(rf, arrs[len(lf):]))
        return invoke.run_cogroup_udf(udf, lcols, JitSegmentOps(seg_ids, 2),
                                      rcols, JitSegmentOps(seg_ids, 2),
                                      left_key, right_key)

    tr = _trace(runner, lf + rf, arrays)
    return _properties_from_trace(tr, lf + rf, kat=True,
                                  key_fields=tuple(left_key) + tuple(right_key))
