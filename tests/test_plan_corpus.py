"""Plan-choice corpus: the plan the optimizer picks for a fixed set of flows.

Each case pins what `optimize(flow)` decides and `compile()` lowers: the
best plan's operator order, its exact integer cost (`PhysPlan.exact_cost`),
and the compiled stage list as `(stage kind, last fused operator)` pairs.
A change to the rewrite closure, the cost model, tie-breaking or lowering
that alters any plan here fails loudly instead of silently changing what a
cell runs.  A change that means to move a plan updates its row, and says why.

Flows: the four `configs.flows.FLOWS`, `chain_join(5)`, `star_join(5)` and
the first twelve `tests/flowgen.py` seeds that hold a binary operator.
Every row is identical under `PYTHONHASHSEED` 0, 1 and 12345.  Flow-builder
names carry a process-wide `#<n>` suffix (`core.flow`), which depends on
how many operators were built before; it is stripped before comparing.
Costs are written as `m << s`: each is a sum of multiples of a large power
of two, so that form is exact and short.
"""

import re

import pytest

from repro.configs import flows
from repro.core.optimizer import optimize
from repro.core.pipeline import ExecutableCache

from flowgen import random_flow
from test_optimizer import _joined_flowgen_seeds

EXPECTED = {
    'q7': (
        "customer->supplier->orders->lineitem->FilterShipdate->JoinOrders->"
        "JoinSupplier->JoinCustomer->FilterNationPair->AggRevenue.pre->"
        "AggRevenue.merge",
        269675833947031272577 << 992,
        [('chain', 'FilterShipdate'), ('match', 'JoinOrders'),
         ('match', 'JoinSupplier'), ('match', 'JoinCustomer'),
         ('chain', 'FilterNationPair'), ('reduce', 'AggRevenue.pre'),
         ('reduce', 'AggRevenue.merge')]),
    'q15': (
        "supplier->lineitem->FilterShipdate->AggRevenue.pre->"
        "AggRevenue.merge->JoinSupplier",
        18173948353920220171 << 995,
        [('chain', 'FilterShipdate'), ('reduce', 'AggRevenue.pre'),
         ('reduce', 'AggRevenue.merge'), ('match', 'JoinSupplier')]),
    'clickstream': (
        "users->logins->clicks->FilterLoggedIn->FilterBuySessions->"
        "CondenseSessions->AppendUserInfo",
        51403001067812445055 << 1002,
        [('match', 'FilterLoggedIn'), ('reduce', 'FilterBuySessions'),
         ('reduce', 'CondenseSessions'), ('match', 'AppendUserInfo')]),
    'textmining': (
        "docs->Preprocess->Extract[drug_m]->Extract[dis_m]->Extract[gene_m]->"
        "Extract[mut_m]->ExtractRelations",
        439559902655698745 << 1001,
        [('chain', 'ExtractRelations')]),
    'chain_join-5': (
        'R4->R3->R2->R1->R0->J1->J2->J3->J4',
        12726075158761762697 << 996,
        [('match', 'J1'), ('match', 'J2'), ('match', 'J3'), ('match', 'J4')]),
    'star_join-5': (
        'dim3->dim2->dim1->dim0->fact->J0->J1->J2->J3',
        82289875638395283161 << 996,
        [('match', 'J0'), ('match', 'J1'), ('match', 'J2'), ('match', 'J3')]),
    'flowgen-0': (
        "S11->S5->S1->mod_k2->_default_join_udf->agg_a8.pre->agg_a8.merge->"
        "anti14",
        4679251262615701730557 << 986,
        [('chain', 'mod_k2'), ('cross', '_default_join_udf'),
         ('reduce', 'agg_a8.pre'), ('reduce', 'agg_a8.merge'),
         ('match', 'anti14')]),
    'flowgen-1': (
        'S8->S4->S1->mod_f3->anti7->mod_f3->add_g11->_default_join_udf',
        781021715758382871457 << 988,
        [('chain', 'mod_f3'), ('match', 'anti7'), ('chain', 'add_g11'),
         ('match', '_default_join_udf')]),
    'flowgen-2': (
        'S9->S5->S1->anti8->_default_join_udf',
        390073939198127959347 << 989,
        [('match', 'anti8'), ('match', '_default_join_udf')]),
    'flowgen-3': (
        'S5->S1->anti8->add_g9',
        194546721944041580301 << 989,
        [('match', 'anti8'), ('chain', 'add_g9')]),
    'flowgen-4': (
        'S15->S10->S5->S1->cg_a8->keep_a8->anti13->lim14->cg_a18',
        1163060247790483206543 << 989,
        [('cogroup', 'cg_a8'), ('reduce', 'keep_a8'), ('match', 'anti13'),
         ('limit', 'lim14'), ('cogroup', 'cg_a18')]),
    'flowgen-6': (
        'S5->S1->add_g4->cg_a8->keep_k2->mod_k2',
        1162068222752184879745 << 988,
        [('chain', 'add_g4'), ('cogroup', 'cg_a8'), ('reduce', 'keep_k2'),
         ('chain', 'mod_k2')]),
    'flowgen-7': (
        'S8->S5->filt_f7->S1->filt_f3->_default_join_udf->anti11',
        1554984914510189813677 << 987,
        [('chain', 'filt_f3'), ('chain', 'filt_f7'),
         ('match', '_default_join_udf'), ('match', 'anti11')]),
    'flowgen-9': (
        'S7->S4->S1->keep_f3->_default_join_udf->anti10->add_g11->mod_k2',
        4688923442062714487357 << 986,
        [('reduce', 'keep_f3'), ('cross', '_default_join_udf'),
         ('match', 'anti10'), ('chain', 'mod_k2')]),
    'flowgen-10': (
        'S5->S1->anti8->mod_f3->add_g9',
        194635485252440517085 << 989,
        [('match', 'anti8'), ('chain', 'add_g9')]),
    'flowgen-12': (
        'S5->S1->anti8->mod_f4->filt_f4_f3->mod_f4->lim9',
        49077451264124278879 << 992,
        [('match', 'anti8'), ('chain', 'mod_f4'), ('limit', 'lim9')]),
    'flowgen-13': (
        'S9->S5->S1->anti8->cg_a12->mod_a13->mod_a13->lim14',
        1550942156257773607661 << 988,
        [('match', 'anti8'), ('cogroup', 'cg_a12'), ('chain', 'mod_a13'),
         ('limit', 'lim14')]),
    'flowgen-14': (
        'S4->S1->_default_join_udf->keep_k5->lim7->mod_k2',
        601199770826824489269 << 989,
        [('match', '_default_join_udf'), ('reduce', 'keep_k5'),
         ('limit', 'lim7'), ('chain', 'mod_k2')]),
}

_SEEDS = _joined_flowgen_seeds(12)


def _flow(case: str):
    if case in flows.FLOWS:
        return flows.FLOWS[case]()[0]
    name, n = case.rsplit("-", 1)
    if name == "flowgen":
        return random_flow(int(n))[0]
    return getattr(flows, name)(int(n))


def _norm(name: str) -> str:
    return re.sub(r"#\d+", "", name)


def test_corpus_covers_the_seeds():
    assert [f"flowgen-{s}" for s in _SEEDS] == \
        [c for c in EXPECTED if c.startswith("flowgen-")]


@pytest.mark.parametrize("case", list(EXPECTED))
def test_plan_choice(case):
    order, cost, stages = EXPECTED[case]
    res = optimize(_flow(case))
    assert _norm(res.best.order()) == order
    assert res.best.plan.exact_cost == cost
    cp = res.compile(cache=ExecutableCache())
    assert [(st.kind, _norm(st.ops[-1].name)) for st in cp.stages] == stages
