"""Paged-KV engine: token parity with the dense engine, prefix caching,
chunked prefill of long prompts, pool pressure, and the Pallas
page-gather kernel's numerics (interpret mode).

Reference parity anchor: the dense engine is itself pinned token-exact
to the non-cached reference model (test_serve.py::test_llm_engine_e2e),
so paged == dense ⇒ paged == reference.
"""

import time

import numpy as np
import pytest


from tests.engines import PAGE, TINY, drain, private_engine, tokens


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 250, n)] for n in lens]


def test_paged_matches_dense_greedy(dense_engine, paged_engine):
    """Greedy generations are token-identical to the dense engine for a
    mixed batch, including a prompt long enough to take multiple prefill
    chunks (23 tokens over 16-token chunks)."""
    reqs = [(f"match{i}", p, {})
            for i, p in enumerate(_prompts(7, (3, 23, 9, 40)))]
    want = tokens(drain(dense_engine, reqs))
    assert len(want) == len(reqs)
    assert tokens(drain(paged_engine, reqs)) == want


def test_prefix_cache_reuses_pages(dense_engine, paged_engine):
    """A repeated prompt prefix skips prefill for its full cached pages:
    the second request computes only the tail, and its output is
    unchanged."""
    (shared,) = _prompts(3, (32,))  # 4 full pages
    p1 = shared + [11, 12, 13]
    p2 = shared + [99, 98]

    eng = paged_engine
    before = eng.stats()
    out1 = drain(eng, [("prefix-a", p1, {})])
    first = eng.stats()
    assert first["prefix_hit_tokens"] == before["prefix_hit_tokens"]
    out2 = drain(eng, [("prefix-b", p2, {})])
    second = eng.stats()
    # 32 shared tokens = 4 pages cached by request a; b prefills only
    # its 2-token tail (padded to one 16-token chunk)
    assert second["prefix_hit_tokens"] - first["prefix_hit_tokens"] == 32
    assert (second["prefill_tokens_computed"]
            - first["prefill_tokens_computed"]) <= 16
    assert len(out1["prefix-a"]["tokens"]) == 8
    assert len(out2["prefix-b"]["tokens"]) == 8

    # the same prompts where nothing is shared (the dense engine has no
    # page cache) give identical tokens — sharing changed the work, not
    # the math
    cold = tokens(drain(dense_engine, [("prefix-a", p1, {}),
                                       ("prefix-b", p2, {})]))
    assert cold == tokens({**out1, **out2})


def test_long_prompt_chunked_prefill(dense_engine, paged_engine):
    """A prompt far longer than the prefill bucket (and longer than the
    dense engine could admit per its slot reservation economics) runs
    through chunked prefill and still matches the dense engine given the
    same max_len window."""
    req = [("long", _prompts(5, (70,))[0], {})]
    want = tokens(drain(dense_engine, req))
    computed = paged_engine.stats()["prefill_tokens_computed"]
    got = tokens(drain(paged_engine, req))
    # 70 tokens / 16-token chunks = 5 chunks
    assert paged_engine.stats()["prefill_tokens_computed"] - computed == 70
    assert got == want and len(got["long"]) == 8


@pytest.fixture(scope="module")
def small_pool_engine():
    """Pool exhaustion is the point: 8 pages where the shared engine
    has slots x max_len worth."""
    from ray_tpu.serve.paged_engine import PagedLLMEngine

    with private_engine(PagedLLMEngine, page_size=PAGE, num_pages=8,
                        **TINY) as eng:
        yield eng


def test_small_pool_requeues_until_pages_free(small_pool_engine):
    """With a pool far smaller than slots × max_len, admission defers
    when pages run out and every request still completes."""
    # each request needs ceil(17/8)+1 ≈ 4 pages; pool of 8 forces
    # serialized admission across the 6 requests
    reqs = [(f"q{i}", p, {}) for i, p in enumerate(_prompts(9, (17,) * 6))]
    out = drain(small_pool_engine, reqs, timeout_s=180)
    assert sorted(out) == sorted(r[0] for r in reqs)
    assert all(len(v["tokens"]) == 8 for v in out.values())


def test_paged_sampling_and_stop_ids(paged_engine):
    """Sampled slots diverge while greedy slots in the same batch stay
    deterministic; per-request stop tokens end generation early."""
    prompt = [5, 3, 7]
    toks = tokens(drain(paged_engine, [
        ("samp-g", prompt, {}),
        ("samp-s1", prompt, {"temperature": 1.0}),
        ("samp-s2", prompt, {"temperature": 1.0})]))
    assert all(len(t) == 8 for t in toks.values())
    full = toks["samp-g"]
    assert toks["samp-s1"] != full or toks["samp-s2"] != full

    stop_tok = full[3]
    out = drain(paged_engine, [("samp-b", prompt, {"stop_ids": [stop_tok]})])
    assert out["samp-b"]["tokens"] == full[:full.index(stop_tok) + 1]


def test_paged_attention_kernel_interpret():
    """Pallas page-gather kernel vs the XLA gather reference, including
    ragged contexts, page-table clamping, and an empty slot."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    S, KVH, G, hd, page, MAXP, P = 4, 2, 2, 128, 8, 6, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (S, KVH, G, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (P, KVH, page, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (P, KVH, page, hd), jnp.float32)
    bt = jax.random.randint(ks[3], (S, MAXP), 0, P)
    ctx = jnp.array([0, 5, 17, 48], jnp.int32)
    with jax.default_matmul_precision("highest"):
        o_ref, m_ref, l_ref = paged_attention_reference(q, kp, vp, bt, ctx)
        o, m, l = paged_attention(q, kp, vp, bt, ctx, interpret=True)
    live = np.asarray(ctx) > 0
    n_ref = np.asarray(o_ref)[live] / np.asarray(l_ref)[live][..., None]
    n_ker = np.asarray(o)[live] / np.asarray(l)[live][..., None]
    assert np.max(np.abs(n_ker - n_ref)) < 2e-5
    assert np.max(np.abs(np.asarray(m - m_ref)[live])) < 2e-5
    # empty slot: zero accumulator and denominator
    assert float(jnp.max(jnp.abs(o[0]))) == 0.0
    assert float(jnp.max(l[0])) == 0.0


def test_paged_engine_cancel_releases_pages(paged_engine):
    """Cancelling a generating request frees its slot AND its pages."""
    eng = paged_engine

    def pages_at_rest():  # free or cached: not held by a slot
        st = eng.stats()
        return st["free_pages"] + st["cached_prefix_pages"]

    def wait_for(cond, what):
        deadline = time.monotonic() + 60
        while not cond():
            assert time.monotonic() < deadline, what
            time.sleep(0.005)

    free0 = pages_at_rest()
    # a budget the max_len window cuts: ~90 tokens of decode to cancel in
    eng.submit("victim", [1, 2, 3, 4, 5], max_new_tokens=3000)
    wait_for(lambda: "victim" in eng.peek(), "request never admitted")
    eng.cancel("victim")
    wait_for(lambda: eng.stats()["active"] == 0,
             "slot not freed after cancel")
    # pages return to free/cached; no result is delivered
    wait_for(lambda: pages_at_rest() == free0, "pages not released")
    assert eng.collect(["victim"]) == {}


def test_oversized_prompt_rejected_not_livelocked(small_pool_engine):
    """A prompt needing more pages than the POOL HAS can never admit;
    it must fail fast with RuntimeError instead of requeueing forever —
    and must not wedge admission for satisfiable requests behind it."""
    # 70 tokens -> 9 pages > the 8-page pool
    huge, ok = _prompts(13, (70, 9))
    out = drain(small_pool_engine, [("huge", huge, {}), ("ok", ok, {})])
    assert isinstance(out.get("huge"), RuntimeError)
    assert "pages" in str(out["huge"])
    assert len(out["ok"]["tokens"]) == 8


def test_pool_exhausted_retry_is_head_of_line(small_pool_engine):
    """A pool-exhausted request parks and retries BEFORE newer arrivals:
    the big request admits as soon as pages free, instead of being
    overtaken indefinitely by a stream of small admits."""
    eng = small_pool_engine
    s0, big, *smalls = _prompts(17, (9, 49, 9, 9, 9))
    # 49 tokens in all: s0 decodes, holding its pages, while big arrives
    eng.submit("s0", s0, max_new_tokens=40)
    deadline = time.monotonic() + 60
    while "s0" not in eng.peek():   # admitted: s0 holds its pages
        assert time.monotonic() < deadline
        time.sleep(0.002)
    # 49 tokens -> 7 pages: satisfiable alone, parked while s0 runs
    eng.submit("big", big)
    for i, p in enumerate(smalls, 1):
        eng.submit(f"s{i}", p)
    order = []
    deadline = time.monotonic() + 180
    while len(order) < 5 and time.monotonic() < deadline:
        order.extend(eng.collect())
        time.sleep(0.01)
    assert sorted(order) == ["big", "s0", "s1", "s2", "s3"]
    # head-of-line: big admitted at s0's page release, ahead of the
    # smalls submitted after it
    assert order.index("big") < order.index("s1")


def test_chain_hash_stable_across_processes():
    """Chain hashes must be process-invariant: they cross process
    boundaries in residency digests (serve/affinity.py) and disagg
    handoffs, so a PYTHONHASHSEED-salted builtin hash() would silently
    zero the router-side match rate. Two interpreters with different
    hash seeds must agree."""
    import os
    import subprocess
    import sys

    prog = ("from ray_tpu.serve.paged_engine import _PageAllocator as A;"
            "print(A.chain_hash(0, tuple(range(8))),"
            " A.chain_hash(12345, (7, 8, 9)))")
    outs = []
    for seed in ("0", "424242"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        outs.append(subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, check=True, timeout=120).stdout.strip())
    assert outs[0] == outs[1]
    assert outs[0].split()[0] != "0"  # hashes are real values
