"""Closed-loop single-turn chat: ``users`` callers, each with its own
list of unshared prompts, each sending its next request the moment the
last one ended.

Traffic file keys: ``users``, ``rounds`` (requests per user),
``prompt_tokens`` and ``output_tokens`` (distributions, see lib/grids),
``first_round_output_tokens`` (the budget that user i's first request
takes the share (i + 1) / users of, which spreads the slots' phases from
the start) and ``lead_in_s``.

Every round hands the same ``users`` prompt lengths and the same
``users`` output budgets to the users in an order drawn from the seed,
so every round — and the whole schedule — has the same token totals for
every seed.
"""

from __future__ import annotations

import random
from typing import Any, Dict

from benchmark.lib import grids
from benchmark.lib.closed_loop import drive  # noqa: F401  (the load loop)


def generate(traffic: Dict[str, Any], seed: int, vocab: int) -> Dict[str, Any]:
    users, rounds = traffic["users"], traffic["rounds"]
    rng = random.Random(seed)
    p_grid = grids.grid(traffic["prompt_tokens"], users)
    o_grid = grids.grid(traffic["output_tokens"], users)
    first = traffic["first_round_output_tokens"]
    per_user = [[] for _ in range(users)]
    for r in range(rounds):
        plens = grids.permuted(p_grid, rng)
        budgets = grids.permuted(o_grid, rng)
        for u in range(users):
            budget = budgets[u]
            if r == 0:
                budget = max(1, round(first * (u + 1) / users))
            per_user[u].append({
                "prompt": grids.token_ids(rng, plens[u], vocab),
                "max_new_tokens": budget, "tag": "chat"})
    return {"users": users, "per_user": per_user, "shared": None,
            "prime": [], "lead_in_s": traffic["lead_in_s"]}
