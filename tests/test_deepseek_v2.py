"""DeepSeek-V2 (``models/deepseek_v2.py``): its row of the conformance
suite (``tests/model_suite.py``: the program at ``tiny()`` against
``benchmark/references/deepseek_v2_ref.py``, whole and at 2 of 4 heads with
experts 4..11; the head shares' and the expert shares' parts add up to the
whole layer's), and what only DeepSeek-V2 has: the flash kernels at unequal
key and value widths, the group-limited router against a written-out loop,
and the latents the remat ladder keeps."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402
from ray_tpu.models import deepseek_v2  # noqa: E402
from ray_tpu.ops import attention  # noqa: E402
from ray_tpu.ops.attention import (attention_reference,  # noqa: E402
                                   flash_attention, with_shared_key)
from ray_tpu.ops.moe import route  # noqa: E402

ROWS = ("deepseek_v2",)
globals().update(model_suite.tests_of(ROWS))


@pytest.mark.parametrize("case", model_suite.cases(ROWS)[1:], indirect=True)
def test_remat_levels_keep_the_latents_and_change_no_value(case):
    """Under the ladder's first rung a layer keeps its two latents beside
    the kernels' outputs; no level changes a gradient (the suite's, of the
    program without remat)."""
    from dataclasses import replace

    _, _, cfg, params, tokens = case

    def grad(**kw):
        return jax.jit(jax.grad(lambda p: deepseek_v2.loss_fn(
            replace(cfg, **kw), p, {"tokens": tokens})))(params)

    plain = case.gradients[0]
    for level in ("full", "level1", "level4"):
        got = grad(remat=True, remat_policy=level)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(plain)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)
    from ray_tpu.models import llama
    assert {"q_latent", "kv_latent"} <= set(llama.remat_names("level1"))
    shape = {n: a.shape[1:] for n, a in params["layers"]["mla_moe"].items()}
    keeps = deepseek_v2.LAYER_KINDS["mla_moe"][0].keeps(cfg, shape, 64, None)
    # out [T, 2 x 12] and the latents [T, 32 + 32] in float32, lse [T, 2]
    assert keeps["rungs"][0] == 64 * (24 * 4 + 2 * 4 + 64 * 4)
    assert keeps["rungs"][1] == 64 * (2 * 24 + 2 * 28 + 8) * 4


def _qkv(shared, kv_heads, seq=256):
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    d_m, d_s, d_v = 32, 16, 24
    q = jax.random.normal(keys[0], (2, seq, 4, d_m + d_s))
    k = jax.random.normal(keys[1], (2, seq, kv_heads,
                                    d_m if shared else d_m + d_s))
    v = jax.random.normal(keys[2], (2, seq, kv_heads, d_v))
    kx = jax.random.normal(keys[3], (2, seq, d_s)) if shared else None
    w = jax.random.normal(keys[4], (2, seq, 4, d_v))
    return q, k, v, kx, w


@pytest.mark.parametrize("shared,kv_heads,window", [
    (True, 4, None), (True, 2, None), (False, 4, None), (False, 1, None),
    (True, 4, 100)])
def test_flash_kernels_at_unequal_widths(shared, kv_heads, window):
    """Forward, dQ, dK, dV (and the shared key's gradient) of the
    ``flash_kv_*`` kernels in ``interpret`` mode against
    ``attention_reference`` on whole keys, causal."""
    q, k, v, kx, w = _qkv(shared, kv_heads)

    def kernel(q, k, v, kx):
        out = flash_attention(q, k, v, use_pallas=True, interpret=True,
                              block_q=64, block_k=128, k_shared=kx,
                              window=window, sm_scale=0.2)
        return (out * w).sum(), out

    def plain(q, k, v, kx):
        whole = with_shared_key(k, kx) if shared else k
        out = attention_reference(q, whole, v, sm_scale=0.2, window=window)
        return (out * w).sum(), out

    wrt = (0, 1, 2, 3) if shared else (0, 1, 2)
    (_, got), got_g = jax.value_and_grad(kernel, wrt, has_aux=True)(
        q, k, v, kx)
    (_, want), want_g = jax.value_and_grad(plain, wrt, has_aux=True)(
        q, k, v, kx)
    assert got.shape == q.shape[:3] + (v.shape[-1],)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(got_g, want_g):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_flash_kernels_refuse_what_they_do_not_divide_or_meet():
    q, k, v, kx, _ = _qkv(True, 4, seq=200)
    with pytest.raises(ValueError, match="divisible by blocks"):
        flash_attention(q, k, v, use_pallas=True, interpret=True,
                        block_q=64, block_k=64, k_shared=kx)
    with pytest.raises(ValueError, match="do not meet keys"):
        flash_attention(q[..., :40], k, v, use_pallas=True, interpret=True,
                        k_shared=kx)
    # the reference path takes the shared part too
    out = flash_attention(q, k, v, use_pallas=False, k_shared=kx)
    np.testing.assert_allclose(out, attention_reference(
        q, with_shared_key(k, kx), v), rtol=1e-6)


def test_equal_widths_run_the_kernels_they_always_did(monkeypatch):
    """A call whose keys and values are one width never reaches the
    ``flash_kv_*`` kernels: its three calls carry the old names."""
    monkeypatch.setattr(attention, "_flash_kv", lambda *a, **k: 1 / 0)
    q = jax.ShapeDtypeStruct((1, 256, 4, 32), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 256, 2, 32), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True, interpret=True).sum(), (0, 1, 2)))(q, k, k))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"name={name}" in text or name in text
    assert "flash_kv" not in text


def _loop_route(scores, top_k, n_group, keep):
    """One token's choice, written out."""
    per = len(scores) // n_group
    best = sorted(range(n_group), key=lambda g: -max(
        scores[g * per:(g + 1) * per]))[:keep]
    inside = [e for e in range(len(scores)) if e // per in best]
    return sorted(sorted(inside, key=lambda e: -scores[e])[:top_k])


def test_route_limits_the_choice_to_the_best_groups():
    """160 experts in 8 groups, 6 a token from 3 groups, against the loop;
    one token's six largest scores lie in four groups, so its choice is
    not the plain top 6."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64, 160)).astype(np.float32)
    # token 0: the six largest in groups 0, 0, 1, 2, 3, 3; the group limit
    # drops group 2's (the weakest best) and takes the next inside 0, 1, 3
    logits[0] = rng.normal(size=160) * 0.1
    for e, value in ((0, 9.0), (5, 8.0), (21, 7.0), (41, 4.0), (60, 6.0),
                     (61, 5.0), (25, 3.0)):
        logits[0, e] = value
    x = jnp.asarray(logits)         # the router the identity: logits = x
    got_logits, top_w, top_e = route(x, jnp.eye(160), 6, scale=16.0,
                                     groups=(8, 3))
    scores = np.asarray(jax.nn.softmax(got_logits, -1))
    for t in range(64):
        assert sorted(np.asarray(top_e[t]).tolist()) == _loop_route(
            scores[t].tolist(), 6, 8, 3), t
    assert sorted(np.asarray(top_e[0]).tolist()) == [0, 5, 21, 25, 60, 61]
    assert sorted(np.argsort(-logits[0])[:6].tolist()) == [0, 5, 21, 41, 60,
                                                           61]
    # the weights are the scores themselves, times the scale
    np.testing.assert_allclose(
        np.asarray(top_w), 16.0 * np.take_along_axis(
            scores, np.asarray(top_e), -1), rtol=1e-6)
    # and renormalised where asked
    _, renorm, _ = route(x, jnp.eye(160), 6, renormalize=True, groups=(8, 3))
    np.testing.assert_allclose(np.asarray(renorm).sum(-1), 1.0, rtol=1e-6)
    # a selection bias under the limit (PR 60; it raised before): zeros
    # change no choice, and the weights are the scores without it
    _, biased_w, biased_e = route(x, jnp.eye(160), 6, scale=16.0,
                                  groups=(8, 3), select_bias=jnp.zeros(160))
    assert (np.sort(np.asarray(biased_e), -1)
            == np.sort(np.asarray(top_e), -1)).all()
    np.testing.assert_allclose(np.sort(np.asarray(biased_w), -1),
                               np.sort(np.asarray(top_w), -1), rtol=1e-6)
