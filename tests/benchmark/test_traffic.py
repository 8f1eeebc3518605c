"""The stratified, de-phased generator."""
import json
import os

import pytest

from perfbench.harness import traffic

from bench_util import ROOT

with open(os.path.join(ROOT, "perfbench", "traffic",
                       "closed-p64-256-o48-96.json")) as f:
    MIX = dict(json.load(f), rounds=3)


def test_grid_is_evenly_spaced_with_both_ends():
    assert traffic.grid(64, 256, 8) == [64, 91, 119, 146, 174, 201, 229, 256]
    assert traffic.grid(48, 96, 8)[0] == 48 and traffic.grid(48, 96, 8)[-1] == 96
    assert traffic.grid(10, 20, 1) == [15]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_every_seed_holds_the_same_multiset_of_lengths(seed):
    base = traffic.plan_lengths(traffic.closed_loop_plan(MIX, 50257, 1))
    plan = traffic.closed_loop_plan(MIX, 50257, seed)
    assert traffic.plan_lengths(plan) == base
    assert all(0 <= t < 50257 for c in plan for q in c for t in q["prompt"])


def test_seeds_differ_in_order_and_tokens():
    a = traffic.closed_loop_plan(MIX, 50257, 1)
    b = traffic.closed_loop_plan(MIX, 50257, 2)
    order = lambda p: [(len(q["prompt"]), q["max_new_tokens"])
                       for c in p for q in c]
    assert order(a) != order(b)
    assert a[0][0]["prompt"] != b[0][0]["prompt"]
    assert a == traffic.closed_loop_plan(MIX, 50257, 1)  # same seed, same plan


def test_first_round_answers_are_dephased():
    plan = traffic.closed_loop_plan(MIX, 50257, 5)
    assert [c[0]["max_new_tokens"] for c in plan] == \
        [12, 24, 36, 48, 60, 72, 84, 96]
    later = sorted(c[1]["max_new_tokens"] for c in plan)
    assert later == traffic.grid(48, 96, 8)


def test_without_dephasing_the_first_round_is_the_grid():
    plan = traffic.closed_loop_plan(dict(MIX, dephase=False), 50257, 5)
    assert sorted(c[0]["max_new_tokens"] for c in plan) == \
        traffic.grid(48, 96, 8)


def test_the_mix_file_states_ranges_grid_and_clients():
    for key in ("clients", "prompt_len", "answer_len", "grid", "dephase",
                "dephase_rule", "rounds", "driver"):
        assert key in MIX
    assert MIX["clients"] == 8 and MIX["prompt_len"] == {"lo": 64, "hi": 256}
    assert MIX["answer_len"] == {"lo": 48, "hi": 96}
