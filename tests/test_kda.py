"""The delta rule whose decay is a vector over the key's channels
(``ops/delta.gated_delta_rule`` at ``g [b, s, H, K]``, Kimi Delta
Attention): XLA's walk against the recurrence one position after another, the bounded gate's worst case, the
scalar rule it reduces to, its gradients, and the router's choice under a
group limit with a selection bias (``ops/moe.route``)."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import delta, moe  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402


def _recurrence(q, k, v, g, beta):
    """S_t = S_{t-1} Diag(exp g_t) (I - beta_t k_t k_t^T) + beta_t v_t
    k_t^T, o_t = S_t q_t: q, k, g [b, s, H, K], v [b, s, H, V], beta [b, s,
    H] -> (o, the last state [b, H, V, K])."""
    b, s, H, K = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        S = S * jnp.exp(g_t)[:, :, None, :]
        seen = jnp.einsum("bhvk,bhk->bhv", S, k_t)
        S = S + (beta_t[..., None] * (v_t - seen))[..., None] \
            * k_t[:, :, None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((b, H, v.shape[-1], K), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _inputs(b=2, s=70, H=3, K=16, V=8, seed=0, lower=-5.0):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(b, s, H, K))
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        rng.normal(size=(b, s, H, K)) * K ** -0.5,
        k / np.linalg.norm(k, axis=-1, keepdims=True),
        rng.normal(size=(b, s, H, V)),
        lower * rng.uniform(size=(b, s, H, K)),
        rng.uniform(size=(b, s, H))))


def _scalar(fn):
    def f(*a):
        o, S = fn(*a)
        return (jnp.sin(o) * o).sum() + (S * S).sum()
    return f


@functools.lru_cache(maxsize=None)
def _walk(chunk):
    return jax.jit(functools.partial(delta.gated_delta_rule, chunk=chunk))


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
@pytest.mark.parametrize("s", [64, 70], ids=["whole-chunks", "ragged"])
def test_the_walk_is_the_recurrence(chunk, s):
    """Outputs and the last state at several chunks (one sub-block, and
    sub-blocks of 16 in chunks of 32 and 64), at a sequence that is whole
    chunks and at one that is not: the result does not depend on the
    chunk."""
    args = _inputs(s=s)
    with jax.default_matmul_precision("highest"):
        (o, S), (want, want_S) = _walk(chunk)(*args), jax.jit(
            _recurrence)(*args)
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)
    assert delta._sub_block(chunk) == min(chunk, 16)


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_lower_bound_at_every_position_and_channel_is_finite(chunk):
    """``g = -5`` everywhere over several chunks: a chunk of 64 reaches
    ``exp(-320)`` from its start and a sub-block ``exp(75)`` from its
    reference row, inside float32; outputs, state and gradients are finite
    and the recurrence's."""
    q, k, v, g, beta = _inputs(s=192, seed=1)
    g = jnp.full_like(g, -5.0)
    with jax.default_matmul_precision("highest"):
        o, S = _walk(chunk)(q, k, v, g, beta)
        want, want_S = jax.jit(_recurrence)(q, k, v, g, beta)
        grads = jax.jit(jax.grad(_scalar(functools.partial(
            delta.gated_delta_rule, chunk=chunk)), argnums=(0, 1, 2, 3, 4)))(
                q, k, v, g, beta)
        want_g = jax.jit(jax.grad(_scalar(_recurrence),
                                  argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(S)).all()
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)
    # (a position's own term aside, what a gradient sums is a product of a
    # factor near exp(75) and one near exp(-75): float32's 1e-7 of each)
    for got, w in zip(grads, want_g):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, w, rtol=1e-4,
                                   atol=2e-3 * float(jnp.abs(w).max()))


def test_equal_channels_give_the_scalar_rule():
    """A decay that is one number a head, spread over the channels, is
    ``gated_delta_rule`` at that scalar: the two forms of one recurrence."""
    q, k, v, g, beta = _inputs(s=64, seed=2, lower=-1.0)
    one = g[..., :1]
    with jax.default_matmul_precision("highest"):
        o, S = _walk(16)(q, k, v, jnp.broadcast_to(one, g.shape), beta)
        want, want_S = _walk(16)(q, k, v, one[..., 0], 2 * beta / 2)
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunk", [8, 32])
def test_gradients_of_q_k_v_g_and_beta_are_the_recurrences(chunk):
    """``dg`` is a vector a position, through the running sums."""
    args = _inputs(s=40, seed=3)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(_scalar(functools.partial(
            delta.gated_delta_rule, chunk=chunk)), argnums=(0, 1, 2, 3, 4)))(
                *args)
        want = jax.jit(jax.grad(_scalar(_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    assert got[3].shape == args[3].shape
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(w).max()))


def test_the_form_is_read_from_gs_rank_and_the_plan_says_so(monkeypatch):
    """On a TPU backend a decay a head is the kernels' and a decay a
    channel the walk's; the span says ``decay``, ``form`` and the
    sub-block; what a channel's walk puts in HBM is more than a head's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    head = delta.rule_plan(1, 32768, 32, 128, 128, 64)
    channel = delta.rule_plan(1, 32768, 32, 128, 128, 64, decay="channel")
    assert (head["form"], head["decay"]) == ("pallas", "head")
    assert (channel["form"], channel["decay"]) == ("xla_walk", "channel")
    assert channel["walk"] * channel["steps"] == 512
    assert channel["float32_bytes_in_hbm"] <= delta.WALK_BYTES
    monkeypatch.undo()
    assert delta.rule_plan(1, 32768, 32, 128, 128, 64)["walk"] > channel[
        "walk"]
    here = tracing.since()
    q, k, v, g, beta = _inputs(b=1, s=24, H=2)
    jax.eval_shape(functools.partial(delta.gated_delta_rule, chunk=8),
                   q, k, v, g, beta)
    said = [e["args"] for e in here.events()
            if e["name"] == "rtpu.gdn.rule_plan"]
    assert [(a["decay"], a["form"], a["sub_block"]) for a in said] == [
        ("channel", "xla_walk", 8)]
    with pytest.raises(ValueError, match="a decay a channel wants"):
        delta.gated_delta_rule(q[:, :, :1], k, v, g, beta)


def _choose_a_token(scores, bias, top_k, n_group, keep):
    """The rule a token at a time, in plain Python."""
    out = []
    for row in np.asarray(scores + bias, np.float64):
        groups = row.reshape(n_group, -1)
        kept = np.argsort(-np.sort(groups, -1)[:, -2:].sum(-1),
                          kind="stable")[:keep]
        allowed = [e for e in range(row.size)
                   if e // groups.shape[1] in kept]
        out.append(sorted(allowed, key=lambda e: -row[e])[:top_k])
    return np.asarray(out)


@pytest.mark.parametrize("seed", [0, 1])
def test_route_with_a_group_limit_and_a_bias_against_a_loop_a_token(seed):
    """Sigmoid scores, a selection bias, 2 of 4 groups kept by the sum of
    their two largest ``score + bias``, the 3 largest inside them; the
    weights are the scores without the bias, renormalised and scaled; the
    bias gets no gradient."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(50, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)) / 32 ** 0.5, jnp.float32)
    bias = jnp.asarray(0.2 * rng.normal(size=(16,)), jnp.float32)
    logits, top_w, top_e = moe.route(
        x, w, 3, renormalize=True, scale=2.5, score="sigmoid",
        select_bias=bias, renorm_eps=1e-20, groups=(4, 2),
        group_score="top2")
    scores = np.asarray(jax.nn.sigmoid(logits))
    want = _choose_a_token(scores, np.asarray(bias), 3, 4, 2)
    assert (np.sort(np.asarray(top_e), -1) == np.sort(want, -1)).all()
    picked = np.take_along_axis(scores, np.asarray(top_e), -1)
    np.testing.assert_allclose(
        top_w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # the limit and the bias both moved some choice
    plain = np.argsort(-scores, -1)[:, :3]
    assert (np.sort(plain, -1) != np.sort(want, -1)).any()
    by_max = moe.route(x, w, 3, score="sigmoid", select_bias=bias,
                       groups=(4, 2))[2]
    assert (np.sort(np.asarray(by_max), -1) != np.sort(want, -1)).any()
    grad = jax.grad(lambda b: moe.route(
        x, w, 3, score="sigmoid", select_bias=b, groups=(4, 2),
        group_score="top2")[1].sum())(bias)
    assert not np.asarray(grad).any()
    with pytest.raises(ValueError, match="unknown group score"):
        moe.route(x, w, 3, groups=(4, 2), group_score="mean")


def test_the_accepted_group_limit_is_left_as_it_was():
    """DeepSeek-V2's caller (no bias, a group's largest score) traces the
    operations it traced before: ``route_choice`` is not reached."""
    x = jnp.ones((8, 16))
    w = jnp.asarray(np.random.default_rng(0).normal(size=(16, 8)),
                    jnp.float32)
    honest = moe.route_choice
    try:
        moe.route_choice = None
        _, top_w, top_e = moe.route(x, w, 2, groups=(4, 2))
    finally:
        moe.route_choice = honest
    assert top_e.shape == (8, 2) and float(top_w.min()) > 0
