"""Per-rule contract tests for the declarative rewrite registry
(DESIGN.md §13).

Every registered `Rule` must be exercised here with at least one POSITIVE
application (pattern matches, guard passes, apply builds a tree) and one
GUARD-REJECTION case (pattern matches, guard refuses) — a rule whose guard
is never falsified by any test is a rule whose safety conditions are
untested.  `test_zz_every_registered_rule_exercised` (last in the file)
asserts completeness against the live registry, so registering a new rule
without tests fails CI.

The file also pins the satellite fix of this PR's issue: `local_rewrites`
historically never generated CONJUGATE rotations even though the
enumeration engine's commute-class closure is conjugate-completed, so the
one-step neighbourhood disagreed with the enumerator's expansion on 3-join
trees whose rotation is only reachable through the commuted child.
`test_local_rewrites_matches_engine_expansion_on_three_join` compares the
two surfaces class-by-class.
"""

import numpy as np
import pytest

from repro.core import executor, flow as F
from repro.core.enumeration import GroupMemo
from repro.core.operators import (Hints, LimitOp, MapOp, MatchOp, ReduceOp,
                                  commute_id)
from repro.core.record import Schema, batch_from_dict
from repro.core.reorder import (RULES, RULES_BY_NAME, Rule, local_rewrites,
                                register_rule, rotate, split_reduce)

S_AB = Schema.of(A=np.int64, B=np.int64)

# rule name -> {"apply", "reject"} marks recorded by the helpers below;
# the completeness test at the bottom audits it against the live registry
EXERCISED: dict[str, set] = {}


def _fire(rule: Rule, node):
    """Trees produced by `rule` at `node`'s root (guard-passing ctxs only)."""
    out = []
    for ctx in rule.pattern(node):
        if rule.guard(node, ctx):
            t = rule.apply(node, ctx)
            if t is not None:
                out.append(t)
    return out


def assert_fires(name: str, node, expect_type=None):
    rule = RULES_BY_NAME[name]
    trees = _fire(rule, node)
    assert trees, f"rule {name!r} did not fire on\n{node.pretty()}"
    if expect_type is not None:
        assert any(isinstance(t, expect_type) for t in trees), \
            f"rule {name!r} produced no {expect_type.__name__} root"
    EXERCISED.setdefault(name, set()).add("apply")
    return trees


def assert_guard_rejects(name: str, node):
    """The pattern matches at least one position but EVERY context is
    refused by the guard (not merely by apply)."""
    rule = RULES_BY_NAME[name]
    ctxs = list(rule.pattern(node))
    assert ctxs, f"rule {name!r}: pattern did not even match\n{node.pretty()}"
    assert not any(rule.guard(node, c) for c in ctxs), \
        f"rule {name!r}: guard admitted a context on\n{node.pretty()}"
    EXERCISED.setdefault(name, set()).add("reject")


# -- shared builders ---------------------------------------------------------
def _abs_b(ir, out):
    out.emit(ir.copy().set("B", abs(ir.get("B"))))


def _filter_a(ir, out):
    out.emit(ir.copy(), where=ir.get("A") >= 0)


def _read_b(ir, out):
    out.emit(ir.copy().set("A", ir.get("A") + ir.get("B")))


def _inc_b(ir, out):
    out.emit(ir.copy().set("B", ir.get("B") + 1))


def _sum_b(g, out):
    out.emit(g.keys().set("s", g.sum("B")))


def _passthrough(g, out):
    out.emit_records(where=g.any(g.get("B") > 0))


def _three_join(parent_key: str):
    a = F.source("A", Schema.of(k1=np.int64, x=np.int64))
    b = F.source("B", Schema.of(k1b=np.int64, k2=np.int64))
    c = F.source("C", Schema.of(kc=np.int64, z=np.int64))
    j1 = F.match(a, b, ["k1"], ["k1b"], name="J1")
    return F.match(j1, c, [parent_key], ["kc"], name="J2")


# -- swap-unary --------------------------------------------------------------
def test_swap_unary_rule():
    src = F.source("I", S_AB)
    m1 = F.map_(src, _abs_b, name="M1")
    ok = F.map_(m1, _filter_a, name="M2")      # reads A, M1 writes B: ROC ok
    bad = F.map_(m1, _read_b, name="M3")       # reads B that M1 writes
    assert_fires("swap-unary", ok)
    assert_guard_rejects("swap-unary", bad)


# -- push-unary / pull-unary -------------------------------------------------
def test_push_unary_rule():
    l = F.source("L", Schema.of(a=np.int64, k=np.int64))
    r = F.source("R", Schema.of(b=np.int64, j=np.int64))
    j = F.match(l, r, ["k"], ["j"], name="J")

    def left_only(ir, out):
        out.emit(ir.copy(), where=ir.get("a") > 0)

    def both_sides(ir, out):
        out.emit(ir.copy(), where=ir.get("a") > ir.get("b"))

    assert_fires("push-unary", F.map_(j, left_only, name="ML"))
    assert_guard_rejects("push-unary", F.map_(j, both_sides, name="MB"))


def test_pull_unary_rule():
    li = F.source("L", Schema.of(k=np.int64, v=np.int64))
    su = F.source("S", Schema.of(sk=np.int64, nm=np.int64), num_records=10)

    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")))

    red = F.reduce_(li, ["k"], agg, name="R")
    ok = F.match(red, su, ["k"], ["sk"], name="J",
                 hints=Hints(pk_side="right"))
    assert_fires("pull-unary", ok, expect_type=ReduceOp)
    # an anti join's right side never hoists: its rows are consumed by the
    # existence test only and must stay below
    r2 = F.source("R", Schema.of(j=np.int64, w=np.int64))
    anti = F.match(li, F.map_(r2, lambda ir, out: out.emit(
        ir.copy(), where=ir.get("w") > 0), name="MR"),
        ["k"], ["j"], anti=True, name="ANTI")
    assert_guard_rejects("pull-unary", anti)


# -- split / unsplit reduce --------------------------------------------------
def test_split_reduce_rule():
    src = F.source("I", S_AB)
    ok = F.reduce_(src, ["A"], _sum_b, name="R")
    bad = F.reduce_(src, ["A"], _passthrough, name="RP")  # not decomposable
    assert_fires("split-reduce", ok)
    assert_guard_rejects("split-reduce", bad)


def test_unsplit_reduce_rule():
    src = F.source("I", S_AB)
    red = F.reduce_(src, ["A"], _sum_b, name="R")
    split = split_reduce(red)
    assert split is not None
    assert_fires("unsplit-reduce", split)
    # the unsplit original has no split markers to collapse
    assert_guard_rejects("unsplit-reduce", red)


# -- combiner push / pull ----------------------------------------------------
def _split_over_match():
    l = F.source("L", Schema.of(k=np.int64, B=np.int64))
    r = F.source("R", Schema.of(j=np.int64, w=np.int64), num_records=10)
    j = F.match(l, r, ["k"], ["j"], name="J", hints=Hints(pk_side="right"))
    red = F.reduce_(j, ["k"], _sum_b, name="R")
    split = split_reduce(red)
    assert split is not None
    return split


def test_push_combiner_rule():
    split = _split_over_match()
    assert_fires("push-combiner", split)
    # guard-rejection: the combiner sits over a Source, not a Match
    src = F.source("I", S_AB)
    split_plain = split_reduce(F.reduce_(src, ["A"], _sum_b, name="R"))
    assert_guard_rejects("push-combiner", split_plain)


def test_pull_combiner_rule():
    split = _split_over_match()
    pushed = assert_fires("push-combiner", split)[0]
    assert_fires("pull-combiner", pushed)
    # guard-rejection: a merge whose child is not a Match at all (the
    # pattern still offers both sides; the guard refuses each)
    src = F.source("I", S_AB)
    split_plain = split_reduce(F.reduce_(src, ["A"], _sum_b, name="R"))
    assert_guard_rejects("pull-combiner", split_plain)


# -- rotate / commute --------------------------------------------------------
def test_rotate_rule():
    ok = _three_join("k2")     # parent key lives in B: plain rotation
    assert_fires("rotate", ok)
    # guard-rejection: an anti child never rotates, whatever the keys
    l = F.source("L", Schema.of(k=np.int64, v=np.int64))
    r = F.source("R", Schema.of(j=np.int64,))
    anti = F.match(l, r, ["k"], ["j"], anti=True, name="ANTI")
    top = F.match(anti, F.source("S", Schema.of(sk=np.int64)),
                  ["k"], ["sk"], name="TOP")
    assert_guard_rejects("rotate", top)
    assert rotate(top, 0) is None and rotate(top, 0, conjugate=True) is None


def test_commute_rule():
    l = F.source("L", Schema.of(a=np.int64, k=np.int64))
    r = F.source("R", Schema.of(b=np.int64, j=np.int64))
    assert_fires("commute", F.match(l, r, ["k"], ["j"], name="J"),
                 expect_type=MatchOp)
    # anti is orientation-sensitive: sides must never swap
    assert_guard_rejects("commute",
                         F.match(l, r, ["k"], ["j"], anti=True, name="A"))


# -- limit pushdown ----------------------------------------------------------
def test_push_limit_rule():
    src = F.source("I", S_AB)
    inc = F.map_(src, _inc_b, name="INC")          # 1:1, writes B only
    ok = F.limit_(inc, k=5, key=("A",), name="LIM")
    assert_fires("push-limit", ok, expect_type=MapOp)
    # guard-rejection 1: the map is a filter (card AT_MOST_ONE, not 1:1)
    filt = F.map_(src, _filter_a, name="FILT")
    assert_guard_rejects("push-limit", F.limit_(filt, k=5, key=("A",)))
    # guard-rejection 2: the map writes the limit's sort key
    assert_guard_rejects("push-limit", F.limit_(inc, k=5, key=("B",)))


def test_pull_limit_rule():
    src = F.source("I", S_AB)
    lim = F.limit_(src, k=5, key=("A",), name="LIM")
    ok = F.map_(lim, _inc_b, name="INC")
    assert_fires("pull-limit", ok, expect_type=LimitOp)
    bad = F.map_(F.limit_(src, k=5, key=("B",), name="LB"), _inc_b,
                 name="INCB")                      # map writes the key
    assert_guard_rejects("pull-limit", bad)


# -- the one-step neighbourhood pin (satellite: conjugate rotations) ---------
@pytest.mark.parametrize("parent_key", ["k2", "x"])
def test_local_rewrites_matches_engine_expansion_on_three_join(parent_key):
    """On a 3-join tree, `local_rewrites`' root-level neighbourhood —
    projected onto commute classes — must equal the group memo's local
    expansion of the root's class.  `parent_key="x"` (the key living on
    J1's LEFT grandchild) is the regression: its only rotation is the
    CONJUGATE one, which `local_rewrites` historically never generated."""
    root = _three_join(parent_key)
    cids = {commute_id(t) for t in GroupMemo().local(root)}
    mine = {commute_id(t) for t in local_rewrites(root)}
    # the commute rule's result is the root's own class (classes are
    # side-order-insensitive); the engine never emits it
    mine.discard(commute_id(root))
    assert mine == cids, (root.pretty(), len(mine), len(cids))
    if parent_key == "x":   # the conjugate-only case really rotates
        assert rotate(root, 0) is None
        assert rotate(root, 0, conjugate=True) is not None
        assert cids, "conjugate rotation missing from the engine expansion"


def test_registered_rules_semantics_on_data():
    """Every tree a rule builds at the root is bit-identical to its input
    on concrete data (spot check on flows the rules above fire on)."""
    rng = np.random.default_rng(5)
    src = F.source("I", S_AB)
    inc = F.map_(src, _inc_b, name="INC")
    lim = F.limit_(inc, k=4, key=("A",), name="LIM")
    data = {"I": batch_from_dict({
        "A": rng.integers(-5, 6, 32), "B": rng.integers(-5, 6, 32)})}
    ref = executor.execute(lim, data)
    for t in local_rewrites(lim):
        assert executor.execute(t, data).equivalent(ref), t.pretty()


# -- registration API and completeness (keep these last) ---------------------
def test_register_rule_rejects_duplicates_and_inserts_before():
    dummy = Rule("dummy-rule", lambda n: iter(()), lambda n, c: False,
                 lambda n, c: None)
    register_rule(dummy, before="commute")
    try:
        names = [r.name for r in RULES]
        assert names.index("dummy-rule") == names.index("commute") - 1
        with pytest.raises(ValueError):
            register_rule(dummy)
    finally:
        RULES.remove(dummy)
        del RULES_BY_NAME["dummy-rule"]


def test_zz_every_registered_rule_exercised():
    """Registry completeness: every registered rule must have BOTH a
    positive application and a guard-rejection case in this file."""
    missing = {}
    for rule in RULES:
        got = EXERCISED.get(rule.name, set())
        if got != {"apply", "reject"}:
            missing[rule.name] = sorted({"apply", "reject"} - got)
    assert not missing, f"unexercised rules: {missing}"
