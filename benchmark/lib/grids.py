"""Length grids: the same values for every seed, in another order.

A traffic file states a distribution ("loguniform" or "uniform" between
``lo`` and ``hi``). ``grid`` turns it into ``n`` fixed values, the
distribution's quantiles at (i + 0.5) / n, and ``permuted`` hands them
out in an order drawn from the seed. The totals of a round are therefore
identical for every seed; only the order (and the token ids) differ.
"""

from __future__ import annotations

import math
import random
from typing import Dict, List


def grid(dist: Dict, n: int) -> List[int]:
    lo, hi = dist["lo"], dist["hi"]
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "loguniform":
        vals = [math.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                for q in qs]
    elif dist["dist"] == "uniform":
        vals = [lo + q * (hi - lo) for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r} "
                         f"(loguniform | uniform)")
    return [int(round(v)) for v in vals]


def permuted(values: List[int], rng: random.Random) -> List[int]:
    out = list(values)
    rng.shuffle(out)
    return out


def token_ids(rng: random.Random, n: int, vocab: int) -> List[int]:
    # id 0 is kept out: engines commonly pad with it
    return [rng.randrange(1, vocab) for _ in range(n)]
