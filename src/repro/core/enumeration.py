"""Plan enumeration (paper Sec. 6).

Two enumerators are provided:

* `enum_alternatives_alg1` — a faithful implementation of the paper's
  Algorithm 1 for unary-operator flows: recursive descent, exchange of
  neighbouring operators via `reorderable(r, s)`, candidate roots visited
  once, memo table keyed on the flow's operator multiset + source.

* `enumerate_plans` — the production enumerator for tree-shaped flows with
  binary operators: the closure of the flow under all valid single-step
  rewrites (unary swaps, pushes into/out of binary operators, rotations,
  commutations).  On purely unary flows it returns exactly the Algorithm-1
  space (tested); on trees it realizes the paper's "easily extended to
  non-unary operators" claim, including bushy join orders.

Both return logical plans only; the physical optimizer prices each.

The closure is computed as a memo of logical groups (`GroupMemo`, DESIGN.md
§2.1): the rewrite rules run once per group expression, not once per plan,
and the plans are then composed from the groups.  The optimizer's group
search (DESIGN.md §4.2) prices the same memo without composing the plans.
"""

from __future__ import annotations

from typing import Iterable, Optional

from .operators import (CrossOp, MapOp, Node, ReduceOp, Source,
                        commute_ordered, replace_child, struct_id)
from .reorder import RULES, commute, is_commuted, reorderable


class PlanSpaceExceeded(RuntimeError):
    """The rewrite closure grew past `max_plans` plans.

    Carries the configured limit and the number of distinct plans discovered
    before bailing out, so callers can report partial progress or retry with
    a larger budget."""

    def __init__(self, limit: int, count: int):
        super().__init__(f"plan space exceeds {limit} "
                         f"({count} plans discovered)")
        self.limit = limit
        self.count = count


# ---------------------------------------------------------------------------
# Algorithm 1 (unary flows) — faithful port of the paper's pseudocode
# ---------------------------------------------------------------------------
def _mtab_key(flow: Node) -> tuple:
    """Memo key: the *set* of operators plus the source — Algorithm 1 memoizes
    sub-flows regardless of their current order (all orders of the same ops
    over the same input enumerate the same alternatives)."""
    names = tuple(sorted(n.name for n in flow.iter_nodes()))
    return names


def enum_alternatives_alg1(flow: Node,
                           mtab: Optional[dict] = None) -> list[Node]:
    """Paper Algorithm 1 (lines 1-29) for single-input operator flows."""
    if mtab is None:
        mtab = {}
    key = _mtab_key(flow)
    if key in mtab:  # line 4-6
        return mtab[key]

    r = flow  # getRoot: the tree root IS the last operator          (line 7)
    if isinstance(r, Source):  # line 8-9
        alts = [r]
        mtab[key] = alts
        return alts
    if not isinstance(r, (MapOp, ReduceOp)):
        raise ValueError("Algorithm 1 handles unary flows only; "
                         "use enumerate_plans for trees")

    cand: set = set()  # line 16
    d_minus_r = r.children[0]  # rmRoot                               (line 17)
    alts_minus_r = enum_alternatives_alg1(d_minus_r, mtab)  # line 18
    alts: list[Node] = []
    seen: set = set()

    def add(tree: Node):
        s = struct_id(tree)
        if s not in seen:
            seen.add(s)
            alts.append(tree)

    for a_minus_r in alts_minus_r:  # line 19
        s = a_minus_r  # getRoot(A_-r)                                (line 20)
        add(r.with_children(a_minus_r))  # addRoot                    (line 21)
        if isinstance(s, Source):
            continue
        if s.name not in cand and reorderable(r, s):  # line 22
            cand.add(s.name)  # line 23
            # setRoot(A_-r, r): replace s with r                      (line 24)
            d_minus_s = r.with_children(s.children[0])
            for a_minus_s in enum_alternatives_alg1(d_minus_s, mtab):  # 25-26
                add(s.with_children(a_minus_s))  # line 27

    mtab[key] = alts  # line 28
    return alts


# ---------------------------------------------------------------------------
# The closure as a memo of logical groups (DESIGN.md §2.1)
# ---------------------------------------------------------------------------
def _first_name(tree: Node) -> str:
    return min(n.name for n in tree.iter_nodes())


def as_built(tree: Node, memo: dict) -> Node:
    """`tree` in the one orientation of its commute class that does not
    depend on the path a search took to it: every keyed binary operator
    (Match, CoGroup) with its inputs in the order it was built with
    (`reorder.commute` undone), every Cross with the input holding the
    least operator name first (rotations regroup a Cross's inputs freely).
    Memoized per structural id in `memo`."""
    sid = struct_id(tree)
    hit = memo.get(sid)
    if hit is not None:
        return hit
    out = tree
    for i, c in enumerate(tree.children):
        b = as_built(c, memo)
        if b is not c:
            out = replace_child(out, i, b)
    if isinstance(out, CrossOp):
        flip = _first_name(out.right) < _first_name(out.left)
    else:
        flip = is_commuted(out)
    if flip:
        out = commute(out)
    memo[sid] = memo[struct_id(out)] = out
    return out


class GroupMemo:
    """The rewrite closure of a flow as a memo of logical groups.

    A group is a logical equivalence class of sub-flows: the same operators
    over the same base relations with the same output attributes — the
    `_mtab_key` of Algorithm 1 carried to trees, where a split Reduce's
    merge and combiner (`<name>.merge`, `<name>.pre`) count as the Reduce
    `<name>` they came from.  An expression is one operator over child
    groups, kept as a tree whose children are members of those groups, in
    its built orientation (`as_built`); expressions are told apart by the
    operator's name and the child groups, and keep the order in which they
    were found, the flow's own first.

    `explore` applies the rules of `reorder.RULES` (all but `commute`,
    which orientation covers) at the root of every expression, under every
    binding of one child to each expression of its group — below a Reduce,
    of a grandchild too, which the combiner rules read — until no rule adds
    an expression.  Rules read only a node, its children and grandchildren,
    and beyond them only what every member of a group shares (attribute
    sets, whether it is a Source), so the trees the groups compose are the
    closure's commute classes.  `count` sizes the closure without composing
    it; `trees` composes it."""

    def __init__(self, split_reduces: bool = True):
        self.exprs: dict[tuple, list[Node]] = {}
        self._ekeys: set = set()
        self._keys: dict[int, tuple] = {}      # struct_id -> group key
        self._added: set = set()               # struct_ids registered
        self._bound: set = set()               # bindings rewritten
        self._built: dict[int, Node] = {}
        self.root: Optional[tuple] = None
        self._rules = [r for r in RULES if r.in_engine
                       and (split_reduces or not r.needs_split)]

    def key(self, tree: Node) -> tuple:
        """Group key of `tree`: (operator names, attributes)."""
        sid = struct_id(tree)
        k = self._keys.get(sid)
        if k is None:
            names: set = set()
            for c in tree.children:
                names |= self.key(c)[0]
            udf = getattr(tree, "udf", None)
            while hasattr(udf, "__reduce_extension__"):
                udf = udf.__reduce_extension__[0]
            split = getattr(udf, "__combine_split__", None)
            if split is not None:
                names.discard(split[0] + ".pre")
                names.add(split[0])
            else:
                names.add(tree.name)
            k = self._keys[sid] = (frozenset(names), tree.attrs())
        return k

    def add(self, tree: Node) -> tuple:
        """Register `tree` (built orientation) and every subtree of it as
        expressions of their groups; returns its group key."""
        k = self.key(tree)
        sid = struct_id(tree)
        if sid in self._added:
            return k
        self._added.add(sid)
        ek = (tree.name, tuple(self.add(c) for c in tree.children))
        if ek not in self._ekeys:
            self._ekeys.add(ek)
            self.exprs.setdefault(k, []).append(tree)
        return k

    def local(self, node: Node) -> list[Node]:
        """Every tree one rule builds at `node`'s root, in built
        orientation — the registry walk `explore` runs per binding."""
        out = []
        for rule in self._rules:
            for ctx in rule.pattern(node):
                if rule.guard(node, ctx):
                    t = rule.apply(node, ctx)
                    if t is not None:
                        out.append(as_built(t, self._built))
        return out

    def _bindings(self, e: Node):
        """`e` with one child bound to each expression of its group, and
        below a Reduce also one grandchild — each binding once per memo."""
        for i, c in enumerate(e.children):
            for x in self.exprs[self.key(c)]:
                bid = (id(e), i, id(x))
                if bid not in self._bound:
                    self._bound.add(bid)
                    yield e if x is c else replace_child(e, i, x)
                if not isinstance(e, ReduceOp):
                    continue
                for j, g in enumerate(x.children):
                    for y in self.exprs[self.key(g)]:
                        bid = (id(e), i, id(x), j, id(y))
                        if y is not g and bid not in self._bound:
                            self._bound.add(bid)
                            yield replace_child(
                                e, i, replace_child(x, j, y))

    def explore(self, flow: Node, max_plans: Optional[int] = None) -> tuple:
        """Grow the memo from `flow` to its fixed point; returns the root
        group's key (also kept as `root`).  With `max_plans`, raises
        `PlanSpaceExceeded` once the classes composed so far — a lower bound
        on the closure — exceed it (checked whenever the expressions
        double past it, so a huge lattice is never built out)."""
        self.root = self.add(as_built(flow, self._built))
        check = max_plans
        grown = True
        while grown:
            grown = False
            for k in list(self.exprs):
                for e in list(self.exprs[k]):
                    for b in self._bindings(e):
                        n = len(self._ekeys)
                        for t in self.local(b):
                            self.add(t)
                        grown |= len(self._ekeys) > n
                        if check is not None and len(self._ekeys) > check:
                            check *= 2
                            if self.count(self.root, False) > max_plans:
                                raise PlanSpaceExceeded(max_plans, max_plans)
        return self.root

    def count(self, key: tuple, include_commutes: bool = True,
              memo: Optional[dict] = None) -> int:
        """Flows the closure enumerates for the group `key`: one per class,
        times its orientation orbit with `include_commutes`."""
        memo = {} if memo is None else memo
        hit = memo.get(key)
        if hit is None:
            hit = 0
            for e in self.exprs[key]:
                n = 2 if include_commutes and len(e.children) == 2 \
                    and not commute_ordered(e) else 1
                for c in e.children:
                    n *= self.count(self.key(c), include_commutes, memo)
                hit += n
            memo[key] = hit
        return hit

    def trees(self, key: tuple) -> Iterable[Node]:
        """Every class of group `key`, lazily, in the memo's order: by
        expression, then the first child's classes before the second's."""
        for e in self.exprs[key]:
            yield from self._over(e, 0)

    def _over(self, node: Node, i: int) -> Iterable[Node]:
        if i == len(node.children):
            yield node
            return
        c = node.children[i]
        for t in self.trees(self.key(c)):
            yield from self._over(node if t is c else
                                  replace_child(node, i, t), i + 1)


def orbit(tree: Node) -> Iterable[Node]:
    """Every orientation of `tree`'s binary operators, `tree` first: the
    first input's orientations, then the second's, then the operator's own
    (anti joins keep theirs)."""
    kids = tree.children
    if not kids:
        yield tree
    elif len(kids) == 1:
        for v in orbit(kids[0]):
            yield tree if v is kids[0] else replace_child(tree, 0, v)
    else:
        for lv in orbit(kids[0]):
            base = tree if lv is kids[0] else replace_child(tree, 0, lv)
            for rv in orbit(kids[1]):
                t = base if rv is kids[1] else replace_child(base, 1, rv)
                yield t
                c = commute(t)
                if c is not None:
                    yield c


def closure(flow: Node, max_plans: int = 20000,
            include_commutes: bool = True, split_reduces: bool = True,
            memo: Optional[GroupMemo] = None) -> Iterable[Node]:
    """Lazily yield every flow reachable from `flow` by valid rewrites: one
    tree per commute class in the memo's order (`GroupMemo.trees`; the
    first is `flow` itself, in built orientation), each followed by its
    orientation orbit with `include_commutes=True`.  `memo` is the memo of
    `flow`, already explored, to reuse.

    The interleaved optimizer consumes this generator directly so costing
    overlaps enumeration.  Raises `PlanSpaceExceeded` when more than
    `max_plans` plans would be yielded."""
    if memo is None:
        memo = GroupMemo(split_reduces=split_reduces)
        memo.explore(flow, max_plans=max_plans)
    count = 0
    for rep in memo.trees(memo.root):
        for m in orbit(rep) if include_commutes else (rep,):
            if count >= max_plans:
                raise PlanSpaceExceeded(max_plans, count)
            count += 1
            yield m


def enumerate_plans(flow: Node, max_plans: int = 20000,
                    include_commutes: bool = True,
                    split_reduces: bool = True) -> list[Node]:
    """All data flows reachable from `flow` by valid pairwise reorderings.

    `include_commutes=False` collapses Match/Cross argument order to one
    representative per side-order-insensitive class, matching the paper's
    notion of distinct operator orders.  (The search itself always runs
    class-wise; commuted variants are materialized only on request.)
    `split_reduces=False` restricts the space to pure reorderings (no
    combiner/merge splits of decomposable Reduces).
    """
    return list(closure(flow, max_plans=max_plans,
                        include_commutes=include_commutes,
                        split_reduces=split_reduces))

