"""End-to-end data-flow optimizer (paper Sec. 6-7 pipeline).

    optimize(flow) =
        SCA properties (already attached at flow construction)
        -> the memo of logical groups (`enumeration.GroupMemo`)
        -> a large space: the group search prices the groups (`_GroupSearch`)
           a small one: each flow of the rewrite closure is priced
           IMMEDIATELY through the shared Volcano memo, and flows whose
           admissible lower bound (`physical.cost_lower_bound`) already
           exceeds the best cost seen so far are skipped (branch-and-bound)
        -> rank priced flows by estimated cost, return the best

Enumeration and costing share hash-consed subtrees (`operators.struct_id`),
so the (often heavily overlapping) enumerated flows are priced with shared
work — the integration of enumeration and costing sketched in the paper's
Sec. 6, plus the Cascades-style bound pruning from the Volcano line of work.

Pruning only skips flows that provably cannot beat the incumbent, and the
group search is exact (DESIGN.md §4.2), so `best` is identical (same flow
order, same cost) to exhaustively pricing every enumerated flow — `optimize_two_phase` keeps the original enumerate-then-cost
pipeline precisely so tests and benchmarks can verify that equivalence.
Benchmarks that need the full cost spectrum (the paper's Figs. 5-7 rank
plots) pass `prune=False`.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Optional

from .. import obs
from .cost import estimate
from .enumeration import GroupMemo, closure, enumerate_plans
from .operators import MapOp, Node, Source, replace_child
from .physical import (Ctx, PhysPlan, _expand, best_physical,
                       cost_lower_bound, default_mesh_shards, dop_ladder)
from .reorder import commute


@dataclasses.dataclass(frozen=True)
class RankedPlan:
    flow: Node
    plan: PhysPlan
    cost: float

    def order(self) -> str:
        return "->".join(reversed(self.flow.op_names()))

    def compile(self, compact_slack: float = 2.0, cache=None,
                use_order: bool = True, adaptive=None, stats=None):
        """Lower this plan into a ready-to-run `pipeline.CompiledPlan`.

        Lowers the PHYSICAL plan, so the shipping strategies and order
        properties (`Props.sort`) the costing relied on thread into the
        stages — presorted inputs actually elide their sorts at runtime.
        `adaptive`/`stats` enable observed-cardinality feedback serving
        (`pipeline.AdaptiveConfig`, DESIGN.md §9)."""
        from .pipeline import compile_plan

        return compile_plan(self.plan, compact_slack=compact_slack,
                            cache=cache, use_order=use_order,
                            adaptive=adaptive, stats=stats)


@dataclasses.dataclass(frozen=True)
class OptResult:
    best: RankedPlan
    ranked: tuple            # all PRICED plans, ascending cost
    enumeration_s: float
    costing_s: float
    num_enumerated: int = 0  # flows discovered by the closure
    num_pruned: int = 0      # flows skipped by the lower-bound test

    @property
    def num_plans(self) -> int:
        """Size of the explored plan space.  With branch-and-bound pruning
        `ranked` holds only the flows that were actually priced; the space
        the search covered is `num_enumerated`."""
        return self.num_enumerated or len(self.ranked)

    def compile(self, compact_slack: float = 2.0, cache=None,
                use_order: bool = True, adaptive=None, stats=None):
        """Compile the best plan: `optimize(flow).compile().run(bindings)`.

        Repeated optimize+compile of equal-shaped flows returns handles that
        share one warm executable through the plan-executable cache."""
        return self.best.compile(compact_slack=compact_slack, cache=cache,
                                 use_order=use_order, adaptive=adaptive,
                                 stats=stats)

    def pick_rank_intervals(self, k: int = 10) -> list[RankedPlan]:
        """K plans at regular rank intervals (the paper's Figs. 5-7 method)."""
        n = len(self.ranked)
        if n <= k:
            return list(self.ranked)
        idx = [round(i * (n - 1) / (k - 1)) for i in range(k)]
        return [self.ranked[i] for i in idx]

    def summary(self) -> str:
        lines = [f"{len(self.ranked)} plans priced "
                 f"({self.num_enumerated} enumerated, "
                 f"{self.num_pruned} pruned by bound) in "
                 f"{(self.enumeration_s + self.costing_s) * 1e3:.1f} ms "
                 f"(enum {self.enumeration_s * 1e3:.1f} / "
                 f"cost {self.costing_s * 1e3:.1f})"]
        best, worst = self.ranked[0], self.ranked[-1]
        lines.append(f"best : {best.cost:.3e}s  {best.order()}")
        lines.append(f"worst: {worst.cost:.3e}s  {worst.order()}  "
                     f"({worst.cost / max(best.cost, 1e-30):.1f}x)")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Memoized group search (DESIGN.md §4.2)
#
# Algorithm 1's memo insight — all orders of one operator set over the same
# inputs share one alternative set — carried to trees: the search runs over
# the logical groups of `enumeration.GroupMemo` (connected sub-flows, O(2^n)
# of them) instead of materialized flows (the closure holds a Catalan-times-
# factorial number of them).  Costing is interleaved per group: each group
# keeps, per (output stats, physical props) key, the cheapest physical
# sub-plan over any of its members.  Keying by output stats keeps the
# search exact under the order-SENSITIVE cardinality estimator: two members
# only share a memo slot when every enclosing operator would be priced
# identically on top of them.
# ---------------------------------------------------------------------------
class _GroupSearch:
    """Volcano costing over the groups of an explored `GroupMemo`.

    Ties go as in the closure, whose stable sort keeps the first flow it
    yielded: every kept plan carries its tree's place in the closure's
    order — the class's (`GroupMemo.trees`: expression index, then the
    inputs' classes) and, within the class, the orientation's (`orbit`:
    the inputs' orientations, then the operator's) — and the least
    (cost, class place, orientation place) wins each memo slot.  Both
    places compare input by input, so the least for an operator is built
    from the least of each input's slot."""

    def __init__(self, memo: GroupMemo, ctx: Ctx, include_commutes: bool):
        self.memo = memo
        self.ctx = ctx
        self.commutes = include_commutes
        self.stats_memo: dict = {}
        self.priced = 0                    # physical alternatives built
        self._cands: dict = {}
        self._place: dict = {}             # id(kept plan) -> its places

    def _slot(self, node: Node) -> tuple:
        """What an enclosing operator's pricing reads of a sub-plan besides
        its props: the output stats (same dop as `_expand`, so the
        struct_id-keyed stats memo is shared) and whether its root is a Map
        (the fused-span candidates ask)."""
        st = estimate(node, self.stats_memo, self.ctx.dop)
        return (st.rows, st.width, st.distinct, isinstance(node, MapOp))

    def _price(self, idx: int, e: Node, out: dict, tag: tuple = ()) -> None:
        """Price expression `e` (number `idx` of its group), in each
        orientation, over every slot of its child groups into `out`:
        {slot: {Props: PhysPlan}}."""
        orients = [(e, False)]
        if self.commutes and e.is_binary:
            c = commute(e)
            if c is not None:
                orients.append((c, True))
        for v, flipped in orients:
            slots = [list(self.cands(self.memo.key(c)).values())
                     for c in v.children]
            for maps in itertools.product(*slots):
                n = v
                for i, pmap in enumerate(maps):
                    rep = next(iter(pmap.values())).node
                    if n.children[i] is not rep:
                        n = replace_child(n, i, rep)
                plans = _expand(n, self.ctx, self.stats_memo, list(maps))
                self.priced += len(plans)
                bucket = out.setdefault(tag + self._slot(n), {})
                for p in plans:
                    ins = [self._place[id(i)] for i in p.inputs]
                    if flipped:
                        ins.reverse()
                    cls = (idx,) + tuple(c for c, _ in ins)
                    ori = tuple(o for _, o in ins)
                    if len(ins) == 2:
                        ori += (flipped,)
                    elif ins:
                        ori = ins[0][1]
                    rank = (p.exact_cost, cls, ori)
                    cur = bucket.get(p.props)
                    if cur is None or rank < self._rank(cur):
                        bucket[p.props] = p
                        self._place[id(p)] = (cls, ori)

    def _rank(self, p: PhysPlan) -> tuple:
        return (p.exact_cost,) + self._place[id(p)]

    def cands(self, key: tuple) -> dict:
        """{slot: {Props: PhysPlan}} of one group.  A plan's `node` is its
        operator over one member per child slot; `_materialize` rebuilds
        the tree its inputs actually hold."""
        hit = self._cands.get(key)
        if hit is None:
            hit = {}
            for idx, e in enumerate(self.memo.exprs[key]):
                self._price(idx, e, hit)
            self._cands[key] = hit
        return hit

    def ranked(self, root: tuple) -> list[RankedPlan]:
        """The root group's plans — the cheapest per root expression, slot
        and props — in the closure's order: by cost, then place."""
        out: dict = {}
        for idx, e in enumerate(self.memo.exprs[root]):
            self._price(idx, e, out, (idx,))
        kept = sorted((p for pmap in out.values() for p in pmap.values()),
                      key=self._rank)
        built: dict = {}
        plans = [_materialize(p, built) for p in kept]
        return [RankedPlan(flow=p.node, plan=p, cost=p.cost)
                for p in plans]


def _materialize(plan: PhysPlan, built: dict) -> PhysPlan:
    """`plan` with every operator re-rooted over its inputs' own nodes, so
    `plan.node` is the tree the plan prices (costs carry over: the group
    search only pairs an operator with a member whose slot is the one the
    plan was priced on)."""
    hit = built.get(id(plan))
    if hit is None:
        inputs = tuple(_materialize(i, built) for i in plan.inputs)
        node = plan.node
        for i, p in enumerate(inputs):
            if node.children[i] is not p.node:
                node = replace_child(node, i, p.node)
        hit = built[id(plan)] = dataclasses.replace(plan, node=node,
                                                    inputs=inputs)
    return hit


# flows the closure enumerates above which the group search prices the
# memo instead (below it every flow is priced, so rank-spectrum consumers
# keep a full `ranked` list, and `max_plans` still bounds the space)
GROUP_SEARCH_THRESHOLD = 2000
# a flow of n operators has up to 2^n groups: past this many operators the
# closure and its max_plans guard take the flow
GROUP_SEARCH_MAX_OPS = 16


def optimize(flow: Node, ctx: Optional[Ctx] = None, max_plans: int = 20000,
             include_commutes: bool = True, prune: bool = True) -> OptResult:
    """Interleaved enumeration + costing with branch-and-bound.

    `prune=False` prices every enumerated flow (full ranked spectrum, as the
    paper's rank-interval figures need); the best plan is the same either
    way.  `include_commutes=False` prices one representative per
    side-order-insensitive plan class, exactly as the two-phase pipeline
    deduplicated before pricing.

    With `prune`, flows whose closure holds more than GROUP_SEARCH_THRESHOLD
    flows are searched group-wise (`_GroupSearch`): the memo of logical
    groups is priced instead of each flow, so e.g. TPC-H Q7's six-relation
    join (221 056 flows with commutes) costs a few hundred group
    expansions.  `max_plans` caps MATERIALIZED plans (the closure paths and
    `enumerate_plans` raise `PlanSpaceExceeded` past it); the group search
    never materializes flows, so the cap does not apply there.

    Counters (`repro.obs`): `optimize.groups`, the memo groups built, and
    `optimize.priced`, the physical alternatives priced."""
    with obs.span("optimize"):
        return _optimize(flow, ctx, max_plans, include_commutes, prune)


def _optimize(flow: Node, ctx: Optional[Ctx], max_plans: int,
              include_commutes: bool, prune: bool) -> OptResult:
    ctx = ctx or Ctx()
    n_ops = sum(not isinstance(n, Source) for n in flow.iter_nodes())
    if prune and n_ops <= GROUP_SEARCH_MAX_OPS:
        t0 = time.perf_counter()
        memo = GroupMemo()
        root = memo.explore(flow)
        obs.count("optimize.groups", len(memo.exprs))
        total = memo.count(root, include_commutes)
        if total > GROUP_SEARCH_THRESHOLD:
            t1 = time.perf_counter()
            search = _GroupSearch(memo, ctx, include_commutes)
            ranked = search.ranked(root)
            obs.count("optimize.priced", search.priced)
            t2 = time.perf_counter()
            return OptResult(best=ranked[0], ranked=tuple(ranked),
                             enumeration_s=t1 - t0, costing_s=t2 - t1,
                             num_enumerated=total,
                             num_pruned=total - len(ranked))
    else:
        memo = None
    plans: dict = {}
    stats_memo: dict = {}
    bound_memo: dict = {}
    ranked: list[RankedPlan] = []
    upper = float("inf")
    num_enumerated = 0
    num_pruned = 0
    costing_s = 0.0

    t0 = time.perf_counter()
    for f in closure(flow, max_plans=max_plans,
                     include_commutes=include_commutes, memo=memo):
        num_enumerated += 1
        tc = time.perf_counter()
        if prune and ranked:
            lb = cost_lower_bound(f, ctx, stats_memo, bound_memo)
            # conservative margin: the bound and the plan cost sum the same
            # terms in different association orders, so a mathematically
            # equal pair can differ by 1 ULP either way — requiring the
            # bound to strictly clear the incumbent keeps a tied-or-better
            # plan from ever being pruned (the same-best-plan contract)
            if lb >= upper * (1.0 + 1e-12):
                num_pruned += 1
                costing_s += time.perf_counter() - tc
                continue
        plan = best_physical(f, ctx, plans, stats_memo)
        cost = plan.cost
        ranked.append(RankedPlan(flow=f, plan=plan, cost=cost))
        if cost < upper:
            upper = cost
        costing_s += time.perf_counter() - tc
    total_s = time.perf_counter() - t0

    ranked.sort(key=lambda r: r.plan.exact_cost)  # stable: order breaks ties
    return OptResult(best=ranked[0], ranked=tuple(ranked),
                     enumeration_s=total_s - costing_s, costing_s=costing_s,
                     num_enumerated=num_enumerated, num_pruned=num_pruned)


@dataclasses.dataclass(frozen=True)
class LayoutResult:
    """Outcome of the sharding-aware layout sweep (`optimize_layout`).

    `result` is the full `OptResult` at the winning degree of parallelism
    `dop`; `per_dop` records `(dop, best_cost)` for every ladder rung, so
    benches and tests can see WHY a layout won (latency-bound small batches
    collapse to dop=1; bandwidth/compute-bound deployments spread to the
    full mesh)."""

    result: OptResult
    dop: int
    per_dop: tuple

    @property
    def best(self) -> RankedPlan:
        return self.result.best


def optimize_layout(flow: Node, mesh_shards: Optional[int] = None,
                    ctx: Optional[Ctx] = None, max_plans: int = 20000,
                    include_commutes: bool = True,
                    prune: bool = True) -> LayoutResult:
    """Sharding-aware optimization: sweep dop over `dop_ladder(mesh)`.

    Every rung reruns the full interleaved search under a context whose
    `dop` changes the net terms (shuffle shares, collective launch latency),
    the per-worker mem/cpu division, AND the combiner output estimates
    (`min(rows, groups*dop)`) — so the shard layout is chosen by the same
    §7.1 cost model as every other physical property, not taken as an
    input.  `mesh_shards` defaults to `REPRO_MESH_SHARDS` (8)."""
    base = ctx or Ctx()
    mesh = mesh_shards if mesh_shards is not None else default_mesh_shards()
    per: list[tuple[int, float]] = []
    best: Optional[tuple[int, OptResult]] = None
    for d in dop_ladder(mesh):
        res = optimize(flow, dataclasses.replace(base, dop=d),
                       max_plans=max_plans,
                       include_commutes=include_commutes, prune=prune)
        per.append((d, res.best.cost))
        if best is None or res.best.cost < best[1].best.cost:
            best = (d, res)
    assert best is not None
    return LayoutResult(result=best[1], dop=best[0], per_dop=tuple(per))


def optimize_two_phase(flow: Node, ctx: Optional[Ctx] = None,
                       max_plans: int = 20000,
                       include_commutes: bool = True) -> OptResult:
    """The original enumerate-everything-then-cost-everything pipeline.

    Kept as the reference implementation: `optimize` must return the same
    best plan (same flow order, same total cost) on every flow — see
    tests/test_optimizer.py and bench_enumeration's speedup column."""
    ctx = ctx or Ctx()
    t0 = time.perf_counter()
    flows = enumerate_plans(flow, max_plans=max_plans,
                            include_commutes=include_commutes)
    t1 = time.perf_counter()
    memo: dict = {}
    stats_memo: dict = {}
    ranked = []
    for f in flows:
        plan = best_physical(f, ctx, memo, stats_memo)
        ranked.append(RankedPlan(flow=f, plan=plan,
                                 cost=plan.cost))
    t2 = time.perf_counter()
    ranked.sort(key=lambda r: r.plan.exact_cost)
    return OptResult(best=ranked[0], ranked=tuple(ranked),
                     enumeration_s=t1 - t0, costing_s=t2 - t1,
                     num_enumerated=len(flows), num_pruned=0)
