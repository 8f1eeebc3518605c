"""One general generator for every traffic mix: it reads the mix's data file
and makes, from the seed, the same amount of work in another order.

Closed-loop generation mixes (``clients``, ``prompt_len``, ``answer_len``,
``rounds``): in every round the clients' prompt lengths are a seeded
permutation of one evenly spaced grid over the stated range, and so are the
answer lengths, so every seed holds the same multiset of work per round under
other token ids.  In the first round the answers are ``answer_len.hi x
(i + 1) / clients`` for client i (``dephase``), so that completions, and the
prefills that follow them, are spread evenly from the first step instead of
arriving in a clump.
"""
import numpy as np


def grid(lo, hi, n):
    """``n`` whole numbers evenly spaced over [lo, hi], ends included."""
    if n == 1:
        return [int(round((lo + hi) / 2.0))]
    return [int(round(lo + (hi - lo) * i / (n - 1.0))) for i in range(n)]


def closed_loop_plan(mix, vocab, seed):
    """[client][round] -> {"prompt": [ids], "max_new_tokens": n}."""
    rng = np.random.default_rng(int(seed))
    clients, rounds = int(mix["clients"]), int(mix["rounds"])
    p_grid = grid(mix["prompt_len"]["lo"], mix["prompt_len"]["hi"], clients)
    a_grid = grid(mix["answer_len"]["lo"], mix["answer_len"]["hi"], clients)
    plan = [[] for _ in range(clients)]
    for r in range(rounds):
        p_perm = rng.permutation(clients)
        a_perm = rng.permutation(clients)
        for i in range(clients):
            answer = a_grid[a_perm[i]]
            if r == 0 and mix.get("dephase", False):
                answer = max(1, int(round(mix["answer_len"]["hi"]
                                          * (i + 1) / float(clients))))
            n = p_grid[p_perm[i]]
            plan[i].append({
                "prompt": [int(t) for t in rng.integers(0, vocab, size=n)],
                "max_new_tokens": int(answer)})
    return plan


def plan_lengths(plan):
    """The multiset of work of a plan, whatever the seed ordered it into:
    per round the sorted prompt lengths and the sorted answer lengths."""
    rounds = len(plan[0])
    return [(sorted(len(c[r]["prompt"]) for c in plan),
             sorted(c[r]["max_new_tokens"] for c in plan))
            for r in range(rounds)]
