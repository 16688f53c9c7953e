"""The generators: the same work for every seed, in another order."""

import json
import os
from collections import defaultdict

import pytest

from benchmark.generators import closed_chat, closed_docqa
from benchmark.lib import grids, spec

VOCAB = 151936


def _traffic(name):
    with open(os.path.join(spec.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_grid_is_the_distributions_quantiles():
    g = grids.grid({"dist": "loguniform", "lo": 128, "hi": 1024}, 32)
    assert len(g) == 32 and g == sorted(g)
    assert 128 <= g[0] and g[-1] <= 1024
    assert 340 <= g[16] <= 390          # median of log-uniform 128-1024 ~ 362
    u = grids.grid({"dist": "uniform", "lo": 3072, "hi": 6144}, 16)
    assert abs(sum(u) / 16 - 4608) < 1
    with pytest.raises(ValueError):
        grids.grid({"dist": "zipf", "lo": 1, "hi": 2}, 4)


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 31 + 11)])
def test_chat_totals_equal_across_seeds(seeds):
    tr = _traffic("chat-closed")
    a, b = (closed_chat.generate(tr, s, VOCAB) for s in seeds)

    def totals(s, rounds=None):
        reqs = [r for u in s["per_user"] for r in u[:rounds]]
        return (sum(len(r["prompt"]) for r in reqs),
                sum(r["max_new_tokens"] for r in reqs))

    assert totals(a) == totals(b)
    for n in (1, 2, 5):                    # and round by round
        assert totals(a, n) == totals(b, n)
    assert a["per_user"][0][1]["prompt"] != b["per_user"][0][1]["prompt"]
    ids = [t for r in a["per_user"][3] for t in r["prompt"]]
    assert min(ids) >= 1 and max(ids) < VOCAB


def test_chat_first_budgets_are_staggered():
    tr = _traffic("chat-closed")
    s = closed_chat.generate(tr, 3, VOCAB)
    first = [u[0]["max_new_tokens"] for u in s["per_user"]]
    C = tr["users"]
    assert first == [max(1, round(tr["first_round_output_tokens"]
                                  * (i + 1) / C)) for i in range(C)]
    assert len(set(first)) == C
    assert tr["users"] == tr["engine"]["num_slots"]


@pytest.mark.parametrize("seeds", [(1, 5)])
def test_docqa_totals_equal_across_seeds(seeds):
    tr = _traffic("docqa-closed")
    a, b = (closed_docqa.generate(tr, s, VOCAB) for s in seeds)

    def totals(s, n=None):
        reqs = s["shared"][:n]
        return (sum(len(r["prompt"]) for r in reqs),
                sum(r["max_new_tokens"] for r in reqs))

    assert totals(a) == totals(b)
    per_round = tr["doc_grid"] * tr["asks_per_doc"]
    C = tr["users"]                     # the staggered first budgets
    first = [r["max_new_tokens"] for r in a["shared"][:C]]
    assert first == [r["max_new_tokens"] for r in b["shared"][:C]]
    assert first == [max(1, round(tr["first_round_output_tokens"]
                                  * (i + 1) / C)) for i in range(C)]
    # answers and questions: equal round by round; documents: equal once
    # every document of a round has had all its asks (lag rounds later)
    for k in (1, 2, 3):
        assert totals(a, k * per_round)[1] == totals(b, k * per_round)[1]
    assert sum(len(r["prompt"]) for r in a["prime"]) == \
        sum(len(r["prompt"]) for r in b["prime"])


def test_docqa_spacing_and_hit_schedule():
    tr = _traffic("docqa-closed")
    s = closed_docqa.generate(tr, 9, VOCAB)
    A, g, C = tr["asks_per_doc"], tr["lag_groups"], tr["users"]
    assert C == tr["engine"]["num_slots"]
    pos = defaultdict(list)
    for i, r in enumerate(s["shared"]):
        pos[r["doc"]].append(i)
    gap = A * g + 1
    assert C < gap <= 4 * C
    for doc, ps in pos.items():
        if 0 <= doc < tr["groups"] - g * (A - 1):
            assert len(ps) == A
            assert [q - p for p, q in zip(ps, ps[1:])] == [gap] * (A - 1)
    # 75% of asks hit, at every point: any 4 consecutive asks hold 1 miss
    tags = [r["tag"] for r in s["shared"]]
    for i in range(0, len(tags) - A, A):
        assert tags[i:i + A].count("miss") == 1
    # an ask's prompt starts with its document: the prefix the cache holds
    docs = {r["doc"]: r["prompt"] for r in s["prime"]}
    first_ask = {}
    for r in s["shared"]:
        if r["tag"] == "miss":
            first_ask[r["doc"]] = r["prompt"]
    for r in s["shared"][:200]:
        if r["tag"] == "hit":
            base = docs.get(r["doc"]) or first_ask[r["doc"]]
            n = len(docs[r["doc"]]) if r["doc"] in docs else None
            if n is not None:
                assert r["prompt"][:n] == base
            else:
                common = 0
                for x, y in zip(r["prompt"], base):
                    if x != y:
                        break
                    common += 1
                assert common >= 3072
    # the primed documents are exactly those asked before their first ask
    assert sorted(docs) == list(range(-g * (A - 1), 0))
    # lengths and budgets within the stated ranges
    for r in s["shared"][C:400]:
        assert 16 <= r["max_new_tokens"] <= 49
        assert 3072 + 32 <= len(r["prompt"]) <= 6144 + 128
        assert len(r["prompt"]) + r["max_new_tokens"] < tr["engine"]["max_len"]


def test_pool_arithmetic_holds_the_working_set():
    tr = _traffic("docqa-closed")
    page = tr["engine"]["page_size"]
    mean_doc_pages = (tr["doc_tokens"]["lo"] + tr["doc_tokens"]["hi"]) / 2 / page
    between = tr["asks_per_doc"] * tr["lag_groups"]           # 32 positions
    stack = between * mean_doc_pages
    in_flight = tr["users"] / tr["asks_per_doc"] * mean_doc_pages + \
        tr["users"] * 3
    assert stack + in_flight < tr["engine"]["num_pages"]
