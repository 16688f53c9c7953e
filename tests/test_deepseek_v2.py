"""DeepSeek-V2 (``models/deepseek_v2.py``) at ``tiny()`` on seeded weights:
the program against its plain reference, the flash kernels at unequal key
and value widths, the group-limited router against a written-out loop,
and the share test: the head shares' and the expert shares' parts add up
to the whole layer's."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.references import deepseek_v2_ref as ref  # noqa: E402
from ray_tpu.models import deepseek_v2  # noqa: E402
from ray_tpu.models.deepseek_v2 import DeepseekV2Config  # noqa: E402
from ray_tpu.ops import attention, mla  # noqa: E402
from ray_tpu.ops.attention import (attention_reference,  # noqa: E402
                                   flash_attention, with_shared_key)
from ray_tpu.ops.layers import Ctx  # noqa: E402
from ray_tpu.ops.moe import route  # noqa: E402

_MOVED = ("attn_norm", "q_a_norm", "kv_a_norm", "mlp_norm")
_SHARES = [pytest.param((None, None), id="whole"),
           pytest.param((2, (4, 8)), id="2-of-4-heads-experts-4..11")]


@pytest.fixture(scope="module")
def setup(request):
    """(config, parameters, tokens [2, 33]) of ``tiny()`` in float32: three
    layers (dense, two routed), 4 heads with keys of 16 + 8 and values of
    12, 16 experts in 4 groups, 3 a token from 2 groups, two sequences.
    The norms are moved off 1: one applied twice or dropped would go
    unseen."""
    heads, experts = request.param
    cfg = DeepseekV2Config.tiny(
        attn_impl="reference", experts_held=experts,
        **({"num_heads": heads, "heads_of": 4} if heads else {}))
    params = deepseek_v2.init_params(cfg, jax.random.PRNGKey(0))
    for n, kind in enumerate(params["layers"]):
        for i, name in enumerate(_MOVED):
            w = params["layers"][kind][name]
            params["layers"][kind][name] = w + 0.3 * jax.random.normal(
                jax.random.PRNGKey(10 * n + i), w.shape)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 33))
    return cfg, params, tokens


@pytest.mark.parametrize("setup", _SHARES, indirect=True)
def test_forward_and_loss_match_the_reference(setup):
    cfg, params, tokens = setup
    assert cfg.pattern == ("mla_dense", "mla_moe", "mla_moe")
    H = cfg.num_heads
    assert params["layers"]["mla_moe"]["wq_b"].shape == (2, 32, H * 24)
    assert params["layers"]["mla_moe"]["wkv_b"].shape == (2, 24, H * 28)
    assert params["layers"]["mla_moe"]["wo"].shape == (2, H * 12, 64)
    assert params["layers"]["mla_moe"]["wkv_a"].shape == (2, 64, 24 + 8)
    assert params["layers"]["mla_moe"]["e_gate"].shape[1] == (
        8 if cfg.experts_held else 16)
    with jax.default_matmul_precision("highest"):
        logits, router = jax.jit(lambda p, t: deepseek_v2.forward(
            cfg, p, t, keep_router_logits=True))(params, tokens[:, :-1])
        loss, terms = jax.jit(lambda p, t: deepseek_v2.loss_terms(
            cfg, p, {"tokens": t}))(params, tokens)
    np.testing.assert_allclose(np.asarray(logits),
                               ref.logits(cfg, params, tokens[:, :-1]),
                               rtol=1e-5, atol=2e-5)
    want = ref.token_nll(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(router["logits"]),
                               want["router_logits"], rtol=1e-5, atol=1e-5)
    chosen = np.asarray(router["chosen"])                # route's own
    assert chosen.shape == (2, 64, cfg.top_k)
    assert (np.sort(chosen, -1) == np.sort(want["chosen"], -1)).all()
    # the group limit moved some choice away from the largest scores, and
    # no token's choices span more than ``topk_group`` groups
    plain = np.argsort(-want["router_logits"], -1)[..., :cfg.top_k]
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()
    per_group = cfg.num_experts // cfg.n_group
    assert max(len(set(row // per_group)) for row in chosen.reshape(
        -1, cfg.top_k)) == cfg.topk_group
    counts = np.stack([np.bincount(c.ravel(), minlength=cfg.num_experts)
                       for c in want["chosen"]])
    assert (np.asarray(terms["expert_counts"]) == counts).all()
    first, count = cfg.experts_held or (0, cfg.num_experts)
    assert int(deepseek_v2.rows_held(cfg, terms["expert_counts"])) == int(
        counts[:, first:first + count].sum())
    for name in ("cross_entropy", "load_balance"):
        assert abs(float(terms[name]) - want["terms"][name]) < 1e-5, name
    assert abs(float(loss) - want["terms"]["loss"]) < 1e-5
    # two routed layers, each a sequence's sum_e f_e P_e near 1 at a
    # near-uniform router
    assert 1.8 < want["terms"]["load_balance"] < 4.0


@pytest.mark.parametrize("setup", _SHARES, indirect=True)
def test_gradients_match_the_reference(setup):
    """Every leaf's gradient of the whole loss, router term and all."""
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: deepseek_v2.loss_fn(
            cfg, p, {"tokens": tokens})))(params)
        want = jax.grad(lambda p: ref.loss(cfg, p, tokens))(params)
    gaps = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max()),
        got, want)
    flat = jax.tree_util.tree_leaves_with_path(gaps)
    assert len(flat) == 3 + 12 + 16
    worst = max(flat, key=lambda kv: kv[1])
    assert worst[1] < 2e-4, worst


@pytest.mark.parametrize("setup", _SHARES[1:], indirect=True)
def test_remat_levels_keep_the_latents_and_change_no_value(setup):
    """Under the ladder's first rung a layer keeps its two latents beside
    the kernels' outputs; no level changes a gradient."""
    from dataclasses import replace

    cfg, params, tokens = setup

    def grad(**kw):
        return jax.jit(jax.grad(lambda p: deepseek_v2.loss_fn(
            replace(cfg, **kw), p, {"tokens": tokens})))(params)

    plain = grad()
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


# ---- the flash kernels at unequal widths


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


# ---- the group-limited router


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
    with pytest.raises(ValueError, match="group limit"):
        route(x, jnp.eye(160), 6, groups=(8, 3), select_bias=jnp.zeros(160))


# ---- the share test


@pytest.mark.parametrize("setup", _SHARES[:1], indirect=True)
def test_head_shares_add_up_to_the_whole_layers_attention(setup):
    """Four chips with one head each: what each block adds to the residual
    stream sums to the whole layer's, program and reference alike."""
    from dataclasses import replace

    cfg, params, tokens = setup
    p = {k: v[0] for k, v in params["layers"]["mla_moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def block(cfg_, p_):
        part = mla.latent_attention_part()
        ctx = Ctx(None, {mla.rope_tables: mla.rope_tables(
            cfg_, tokens[:, :-1])})
        with jax.default_matmul_precision("highest"):
            return part.body(cfg_, x, p_, ctx)[0] - x

    whole = block(cfg, p)
    np.testing.assert_allclose(whole[0], ref.attention_layer(cfg, p, x[0]),
                               rtol=1e-4, atol=1e-5)
    parts, ref_parts = [], []
    for head in range(4):
        mine = {**p,
                "wq_b": p["wq_b"][:, head * (dn + dr):(head + 1) * (dn + dr)],
                "wkv_b": p["wkv_b"][:, head * (dn + dv):
                                    (head + 1) * (dn + dv)],
                "wo": p["wo"][head * dv:(head + 1) * dv]}
        held = replace(cfg, num_heads=1, heads_of=4)
        assert {n: leaf.shape for n, leaf in mla.latent_attention_part(
            ).leaves(held).items()} == {n: a.shape for n, a in mine.items()
                                        if n in ("attn_norm", "wq_a",
                                                 "q_a_norm", "wq_b", "wkv_a",
                                                 "kv_a_norm", "wkv_b", "wo")}
        parts.append(block(held, mine))
        ref_parts.append(ref.attention_layer(held, mine, x[0]))
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sum(ref_parts), whole[0], rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3


def test_expert_shares_add_up_to_the_uncut_layer():
    """Twenty chips with one of twenty experts each: their routed parts,
    plus the shared experts counted once, are the uncut reference's layer."""
    from dataclasses import replace

    from ray_tpu.ops.moe import routed_experts

    cfg = DeepseekV2Config.tiny(num_experts=20, n_group=4, topk_group=2,
                                top_k=3)
    params = deepseek_v2.init_params(cfg, jax.random.PRNGKey(2))
    p = {k: v[0] for k, v in params["layers"]["mla_moe"].items()}
    u = jax.random.normal(jax.random.PRNGKey(6), (48, 64))
    want = ref.routed_layer(cfg, p, u)
    shared = want - ref.routed_layer(cfg, p, u, shared=False)
    total = ref_total = shared
    for e in range(20):
        mine = {**p, **{n: p[n][e:e + 1]
                        for n in ("e_gate", "e_up", "e_down")}}
        held = replace(cfg, experts_held=(e, 1))
        with jax.default_matmul_precision("highest"):
            out, _, counts = routed_experts(
                u, mine["router"], mine["e_gate"], mine["e_up"],
                mine["e_down"], cfg.top_k, renormalize=False, held=(e, 1),
                scale=cfg.routed_scale, groups=(cfg.n_group, cfg.topk_group))
        assert int(counts.sum()) == 48 * 3
        total = total + out
        ref_total = ref_total + ref.routed_layer(held, mine, u, shared=False)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_total, want, rtol=1e-4, atol=1e-5)


def test_preset_counts_what_the_model_card_says():
    """The published sizes give 236 B parameters, 21 B of them active a
    token; the benchmark's cut (5 layers, 32 heads, 8 experts, 12,800
    rows) the 1,493,959,680 the configuration file states."""
    import math

    def count(cfg):
        shapes = jax.eval_shape(lambda k: deepseek_v2.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        return sum(math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(shapes))

    cfg = DeepseekV2Config.deepseek_v2()
    assert cfg.pattern == ("mla_dense",) + ("mla_moe",) * 59
    assert 235e9 < count(cfg) < 237e9
    assert abs(mla.softmax_scale(cfg) - 0.114721) < 1e-5
    cut = DeepseekV2Config.deepseek_v2(
        num_layers=5, vocab_size=12_800, num_heads=32, heads_of=128,
        experts_held=(0, 8))
    assert count(cut) == 1_493_959_680


def test_the_cells_flops_and_bytes_against_hand_counts():
    """``benchmark/lib/latent_flops.py`` at the configuration file's sizes
    against the issue's arithmetic: no roofline or MFU counts more than
    the mathematics needs."""
    import json
    import os

    from benchmark.lib import latent_flops, spec

    m = spec.model_sizes(json.load(open(os.path.join(
        spec.ROOT, "benchmark/configs/deepseek-v2-c1.json"))))
    proj = (5120 * 1536 + 1536 * 32 * 192 + 5120 * 576 + 512 * 32 * 256
            + 32 * 128 * 5120)
    assert proj == 45_416_448 == latent_flops.mla_proj_params(m)
    assert (latent_flops.layers(m), latent_flops.routed_layers(m)) == (5, 4)
    assert latent_flops.mlp_params(m) == 188_743_680 + 4 * 47_185_920
    assert latent_flops.head_params(m) == 65_536_000
    assert latent_flops.token_matmul_params(m) == (
        5 * proj + 188_743_680 + 4 * (47_185_920 + 819_200) + 65_536_000)
    pairs = 8192 * 8193 / 2
    fwd = 5 * 32 * (2 * 192 + 2 * 128) * pairs
    assert latent_flops.attention_flops_fwd(m, 1, 8192) == fwd
    flash = latent_flops.flash_flops_per_step(m, 1, 8192)
    assert flash == 5 * 32 * (3 * 2 * 192 + 2 * 2 * 128 + 2 * 192 + 2 * 128
                              ) * pairs
    assert abs(flash / fwd - 3.6) < 1e-9
    # q, k (the 64 shared dims once), v, o, dO, dq, dk, dv in bf16
    q, k, v = 32 * 192, 32 * 128 + 64, 32 * 128
    assert latent_flops.flash_bytes_per_step(m, 8192) == (
        5 * 8192 * 2 * (q + k + v + v + v + q + k + v))
    rows = 4 * 8192 * 6 * 8 / 160
    step = latent_flops.train_flops_per_step(m, 1, 8192, rows)
    # the issue's count: 701.5 M multiply-adds a token and 1.03e13 of
    # attention, 4.5e13 a step
    assert abs((step - 3 * fwd) / 6 / 8192 / 701.5e6 - 1) < 0.005
    assert abs(3 * fwd / 1.03e13 - 1) < 0.01 and 4.4e13 < step < 4.6e13
    # the flash kernels' floor is compute's: 15.7 ms of FLOPs a step
    # against 1.2 ms of bytes on a v5e
    assert flash / 197e12 > 10 * latent_flops.flash_bytes_per_step(
        m, 8192) / 819e9
