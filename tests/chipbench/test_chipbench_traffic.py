"""The deck deals exact proportions for any seed; the seed changes order and
drawn values only. A case for each cell whose traffic file has the `deck`
order, whatever the cell is called."""

import collections

import pytest

from chipbench_helpers import DECK_CELLS, SESSIONS_CELL, manifest_of
from lib.traffic import Plan, deal

SEEDS = [0, 1, 7, 2**31 + 5, 2147500606]


def plan_of(cell: str, seed: int, **kwargs) -> Plan:
    manifest = manifest_of(cell)
    traffic = manifest.cell(cell)["traffic"]
    return Plan(traffic, manifest.payloads_of(traffic), seed, rehearse=True, **kwargs)


def test_deal_is_exact_and_even():
    deck = deal({"a": 50, "b": 25, "c": 25})
    assert collections.Counter(deck) == {"a": 2, "b": 1, "c": 1}
    assert deck[0] != deck[1]  # a's two cards are not neighbours
    assert len(deal({"hello": 25, "w": 10, "r": 5, "fib": 15, "ls": 5, "e": 5, "c": 5, "sumsq": 20, "mm": 10})) == 20


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", DECK_CELLS)
def test_any_seed_deals_the_mix_in_exact_proportion(cell, seed):
    plan = plan_of(cell, seed)
    mix = plan.traffic["mix"]
    size = len(plan.deck)
    dealt = collections.Counter(plan.stateless(i)["payload"] for i in range(3 * size))
    assert {k: v * sum(mix.values()) for k, v in dealt.items()} == {k: w * 3 * size for k, w in mix.items()}


@pytest.mark.parametrize("cell", DECK_CELLS)
def test_the_seed_changes_order_and_drawn_values_never_the_work(cell):
    a, b = plan_of(cell, 11), plan_of(cell, 12)
    assert a.deck == b.deck
    size = len(a.deck)
    turns_a = [a.stateless(i) for i in range(size)]
    turns_b = [b.stateless(i) for i in range(size)]
    strip = lambda turns: sorted(  # noqa: E731
        (t["payload"], tuple(sorted((k, v) for k, v in t["params"].items()
                                    if k not in a.payloads[t["payload"]].get("draw", {})))) for t in turns)
    assert strip(turns_a) == strip(turns_b)
    assert [t["source"] for t in turns_a] != [t["source"] for t in turns_b]
    again = plan_of(cell, 11)
    assert [again.stateless(i)["source"] for i in range(size)] == [t["source"] for t in turns_a]


@pytest.mark.parametrize("seed", SEEDS)
def test_sessions_hold_each_variant_once_per_block(seed):
    plan = plan_of(SESSIONS_CELL, seed)
    variants = len(plan.session["variants"])
    for block in range(3):
        chains = {plan.session_turns(block * variants + i)[1][0]["chain"] for i in range(variants)}
        assert len(chains) == variants
    ids = {plan.session_turns(n)[0] for n in range(12)}
    assert len(ids) == 12
    turns = plan.session_turns(0)[1]
    assert [t["params"]["T"] for t in turns] == list(range(1, plan.session["turns"] + 1))
    assert turns[0]["inputs"] and not turns[1]["inputs"]  # only turn 1 uploads


@pytest.mark.parametrize("cell", DECK_CELLS)
def test_control_changes_what_is_sent_not_what_the_reference_runs(cell):
    sound, control = plan_of(cell, 3), plan_of(cell, 3, control=True)
    for i in range(len(sound.deck)):
        s, c = sound.stateless(i), control.stateless(i)
        assert s["reference_source"] == c["reference_source"] == s["source"]
        has_control = "control" in sound.payloads[s["payload"]]
        assert (c["source"] != s["source"]) is has_control


@pytest.mark.parametrize("cell", DECK_CELLS)
def test_traced_runs_profile_only_payloads_that_state_a_floor(cell):
    plan = plan_of(cell, 3, trace=True)
    every = plan.traffic["trace"]["profile_every"]
    turns = [plan.stateless(i) for i in range(max(4, every) * len(plan.deck))]
    profiled = [t for t in turns if t["profile"]]
    # every cell drives the device path: some payload of its mix states a floor
    assert profiled and all("floor" in plan.payloads[t["payload"]] for t in profiled)
    for name in {t["payload"] for t in profiled}:
        flags = [t["profile"] for t in turns if t["payload"] == name]
        assert flags == [i % every == 0 for i in range(len(flags))]
    assert not any(t["profile"] for t in (plan_of(cell, 3).stateless(i) for i in range(20)))
