"""Closed-loop document QA: ``users`` workers pull from ONE seeded list
of asks; each document is asked ``asks_per_doc`` times, each ask being
the document followed by a fresh short question.

The list is a rolling schedule. With A = asks_per_doc and
``lag_groups`` = g, position p = A*m + r holds ask r of document
m - g*r: a new document enters every A-th ask, and the asks of one
document lie A*g + 1 positions apart. So an earlier ask has ended (and
its pages are registered) before the next arrives, and (A-1)/A of the
asks find their document cached, at every point of the list. Documents
with a negative index are the ones a long-running deployment would
already hold: the benchmark primes them during set-up (one-token asks,
in index order), so the first position already sees steady state.

Traffic file keys: ``users``, ``groups`` (list length / A),
``asks_per_doc``, ``lag_groups``, ``doc_tokens``, ``question_tokens``,
``answer_tokens`` (distributions, see lib/grids), ``doc_grid`` (distinct
document lengths per round; a round is doc_grid groups),
``first_round_output_tokens`` (the first ``users`` asks take the shares
(i + 1) / users of it as budgets) and ``lead_in_s``.

Every round holds the same document, question and answer lengths in an
order drawn from the seed: equal totals for every seed.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

from benchmark.lib import grids
from benchmark.lib.closed_loop import drive  # noqa: F401  (the load loop)


def generate(traffic: Dict[str, Any], seed: int, vocab: int) -> Dict[str, Any]:
    users, groups = traffic["users"], traffic["groups"]
    A, g, G = traffic["asks_per_doc"], traffic["lag_groups"], traffic["doc_grid"]
    if groups % G:
        raise ValueError(f"groups={groups} must be a multiple of "
                         f"doc_grid={G}")
    rng = random.Random(seed)
    n_prime = g * (A - 1)            # documents -n_prime .. -1
    doc_grid = grids.grid(traffic["doc_tokens"], G)
    q_grid = grids.grid(traffic["question_tokens"], G * A)
    a_grid = grids.grid(traffic["answer_tokens"], G * A)

    docs: Dict[int, List[int]] = {}
    # primed documents (and, below, the list's last round) are asked
    # fewer than A times inside the list, by their index: their lengths
    # keep the grid's order so that the totals do not depend on the seed
    lens = grids.grid(traffic["doc_tokens"], n_prime)
    for j, n in zip(range(-n_prime, 0), lens):
        docs[j] = grids.token_ids(rng, n, vocab)
    first = traffic["first_round_output_tokens"]
    stagger = [max(1, round(first * (u + 1) / users)) for u in range(users)]
    asks = []
    for base in range(0, groups, G):
        dl = (grids.permuted(doc_grid, rng)
              if base + G <= groups - n_prime else list(doc_grid))
        ql = grids.permuted(q_grid, rng)
        if base == 0:      # the first asks take the staggered budgets in
            # place of the grid's lowest values, whatever the seed
            al = stagger + grids.permuted(sorted(a_grid)[users:], rng)
        else:
            al = grids.permuted(a_grid, rng)
        for i in range(G):
            docs[base + i] = grids.token_ids(rng, dl[i], vocab)
        for i in range(G):
            m = base + i
            for r in range(A):
                p = A * i + r
                asks.append({
                    "prompt": docs[m - g * r]
                    + grids.token_ids(rng, ql[p], vocab),
                    "max_new_tokens": al[p], "doc": m - g * r,
                    "tag": "miss" if r == 0 else "hit"})
    prime = [{"prompt": docs[j], "max_new_tokens": 1, "doc": j,
              "tag": "prime"} for j in range(-n_prime, 0)]
    return {"users": users, "per_user": None, "shared": asks,
            "prime": prime, "lead_in_s": traffic["lead_in_s"]}
