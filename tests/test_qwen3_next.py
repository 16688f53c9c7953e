"""Qwen3-Next's language model (``models/qwen3_next.py``) at ``tiny()`` on
seeded weights, in float32: the program against its plain reference
(logits, loss, every leaf's gradient, the linear layers' states), the rule
at grouped heads against the token-by-token recurrence, the held share (the
parts four chips give add up to the uncut layer), the remat plan's two kinds
and what the table reports."""

from dataclasses import replace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.references import qwen3_next_ref as ref  # noqa: E402
from ray_tpu.models import llama, qwen3_next  # noqa: E402
from ray_tpu.models.qwen3_next import Qwen3NextConfig  # noqa: E402
from ray_tpu.ops import delta  # noqa: E402
from ray_tpu.ops.layers import Ctx, rms_norm  # noqa: E402

_MOVED = ("attn_norm", "op_norm", "mlp_norm", "q_norm", "k_norm", "g_norm")
_SHARES = [pytest.param(None, id="all-experts"),
           pytest.param((4, 4), id="held-4..7")]


@pytest.fixture(scope="module")
def setup(request):
    """(config, parameters, tokens [2, 33]) of ``tiny()`` in float32: three
    delta-rule layers of 2 key heads under 4 value heads and a full layer
    of 4 heads of 16 over 2 with 4 rotated dims, 16 experts, 4 a token,
    beside a gated shared expert in every layer. The norms (drawn as
    zeros, the rule's own as ones) and the last norm are moved off their
    starts: a ``1 + w`` applied as ``w`` or twice would go unseen."""
    cfg = Qwen3NextConfig.tiny(attn_impl="reference",
                               experts_held=request.param)
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(0))
    for n, kind in enumerate(params["layers"]):
        for i, name in enumerate(_MOVED):
            if name in params["layers"][kind]:
                w = params["layers"][kind][name]
                params["layers"][kind][name] = w + 0.3 * jax.random.normal(
                    jax.random.PRNGKey(10 * n + i), w.shape)
    params["final_norm"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(77), params["final_norm"].shape)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 33))
    return cfg, params, tokens


@pytest.fixture(scope="module")
def both(setup):
    """The program's forward with the routers' logits kept, its own choices
    of experts, and the reference on those choices."""
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, said = jax.jit(lambda p, t: qwen3_next.forward(
            cfg, p, t, keep_router_logits=True))(params, tokens[:, :-1])
    chosen = jax.lax.top_k(jax.nn.softmax(said["router"]["logits"], -1),
                           cfg.top_k)[1]
    return logits, said, chosen, ref.token_nll(cfg, params, tokens,
                                               forced_topk=chosen)


@pytest.mark.parametrize("setup", _SHARES, indirect=True)
def test_forward_matches_the_reference(setup, both):
    cfg, params, tokens = setup
    assert cfg.pattern == ("linear", "linear", "linear", "full")
    lin, full = params["layers"]["linear"], params["layers"]["full"]
    # z 4 x 16 | q and k 2 x 16 each, v 4 x 16 | a and b 4 each
    assert lin["g_in"].shape == (3, 64, 64 + 128 + 8)
    assert lin["g_conv"].shape == (3, 128, 4)
    assert full["wq"].shape == (1, 64, 2 * 4 * 16)
    assert full["e_gate"].shape == (1, cfg.experts_here, 64, 32)
    assert full["s_sigmoid"].shape == (1, 64)
    assert not float(jnp.abs(qwen3_next.init_params(
        cfg, jax.random.PRNGKey(0))["final_norm"]).max())
    logits, said, chosen, want = both
    want_logits = jax.jit(lambda p: ref.logits(
        cfg, p, tokens[:, :-1], forced_topk=chosen))(params)
    # (5e-5 as Olmo-Hybrid's: three rule layers hand their rounding on)
    np.testing.assert_allclose(logits, want_logits, rtol=1e-5, atol=5e-5)
    # the reference, left to choose, chooses what the program chose
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(want["chosen"], -1))
    np.testing.assert_allclose(said["router"]["logits"],
                               want["router_logits"], rtol=1e-5, atol=5e-5)
    assert said["router"]["counts"].shape == (4, 16)
    assert int(said["router"]["counts"].sum()) == 4 * 2 * 32 * cfg.top_k


@pytest.mark.parametrize("setup", _SHARES[1:], indirect=True)
def test_states_and_loss_match_the_reference(setup, both):
    cfg, params, tokens = setup
    _, said, chosen, want = both
    assert said["gdn_state"].shape == (3, 2, 4, 16, 16)
    np.testing.assert_allclose(said["gdn_state"], want["last_states"],
                               rtol=1e-5, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        nll, again = qwen3_next.token_nll(cfg, params, jnp.asarray(tokens),
                                          head_block=16)
        loss, terms = qwen3_next.loss_terms(
            cfg, params, {"tokens": jnp.asarray(tokens)})
    np.testing.assert_allclose(nll, want["nll"], rtol=1e-5, atol=1e-5)
    assert set(again) == {"gdn_state", "router"}
    for name in ("cross_entropy", "load_balance"):
        np.testing.assert_allclose(terms[name], want["terms"][name],
                                   rtol=1e-5)
    np.testing.assert_allclose(loss, want["terms"]["loss"], rtol=1e-5)
    np.testing.assert_allclose(terms["gdn_state_abs_max"],
                               want["state_abs_max"], rtol=1e-5)
    np.testing.assert_array_equal(terms["expert_counts"],
                                  said["router"]["counts"])
    assert int(qwen3_next.rows_held(cfg, terms["expert_counts"])) == int(
        terms["expert_counts"][:, 4:8].sum())


@pytest.fixture(scope="module")
def row_gradient(setup, both):
    """The reference's gradient of the whole loss of the first row, on the
    program's choices, for every leaf."""
    cfg, params, tokens = setup
    return jax.jit(jax.grad(lambda p: ref.loss(
        cfg, p, tokens[:1], forced_topk=both[2][:, :32])))(params)


@pytest.mark.parametrize("setup", _SHARES[1:], indirect=True)
def test_gradients_match_the_reference(setup, row_gradient):
    """Every leaf's gradient of the loss, its router term with it, against
    the reference's at 5e-5 of the leaf's largest entry (Olmo-Hybrid's
    tolerance, ``tests/test_stack_models.py``)."""
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: qwen3_next.loss_fn(
            cfg, p, {"tokens": jnp.asarray(tokens[:1])})))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    assert len(flat) == 3 + 16 + 16
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(row_gradient)):
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path                       # it is reached
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=5e-5 * max(scale, 1e-2),
                                   err_msg=str(path))


@pytest.mark.parametrize("setup", _SHARES[1:], indirect=True)
def test_the_references_weighted_gradient_is_the_losss(setup, both,
                                                       row_gradient):
    """``token_nll(grad_weights=1 / n, router_term=True)`` of one row is
    the gradient of ``loss`` for the leaves it is asked for."""
    cfg, params, tokens = setup
    got = ref.token_nll(cfg, params, tokens[:1], forced_topk=both[2][:, :32],
                        grad_weights=np.full((1, 32), 1 / 32, np.float32),
                        router_term=True)["grads"]
    want = ref.first_layers(row_gradient)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))
    with pytest.raises(ValueError, match="a row alone"):
        ref.token_nll(cfg, params, tokens, router_term=True,
                      grad_weights=np.ones((2, 32), np.float32))


@pytest.mark.parametrize("chunk", [4, 16])
def test_the_rule_at_grouped_heads_is_the_recurrence(chunk):
    """2 key heads under 4 value heads: the mixer's chunked rule on q and k
    copied to the value heads against the reference's recurrence, which
    hands value head ``i`` key head ``i // 2`` a position at a time; the
    span says how the heads were joined."""
    cfg = Qwen3NextConfig.tiny(rule_chunk=chunk)
    p = {k: v[1] for k, v in qwen3_next.init_params(
        cfg, jax.random.PRNGKey(4))["layers"]["linear"].items()}
    p["g_norm"] = p["g_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), p["g_norm"].shape)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 64))
    with jax.default_matmul_precision("highest"):
        out, S = delta.gated_delta_mixer(
            u, p, heads=4, key_heads=2, key_dim=16, value_dim=16,
            chunk=chunk, beta_scale=1.0)
    want, want_S = ref.mixer(cfg, p, u[0])
    np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S[0], want_S, rtol=1e-5, atol=1e-5)
    plan = delta.rule_plan(1, 40, 4, 16, 16, chunk, key_heads=2)
    assert (plan["heads"], plan["key_heads"], plan["joined"]) == (
        4, 2, "repeat")
    assert delta.rule_plan(1, 40, 4, 16, 16, chunk)["joined"] is None
    # a head that read key head i mod 2 is another model
    with jax.default_matmul_precision("highest"):
        honest = delta._join_heads
        try:
            delta._join_heads = lambda x, heads: jnp.tile(
                x, (1, 1, heads // x.shape[2], 1))
            wrong, _ = delta.gated_delta_mixer(
                u, p, heads=4, key_heads=2, key_dim=16, value_dim=16,
                chunk=chunk, beta_scale=1.0)
        finally:
            delta._join_heads = honest
    assert float(jnp.abs(wrong[0] - want).max()) > 1e-3


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips with 4 of 16 experts each: what their layers add (the
    part's body less its input), with the gated shared expert that every
    chip computes alike counted once, is the uncut reference's layer; and
    ``experts_held=None`` is that sum."""
    cfg = Qwen3NextConfig.tiny()
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(2))
    p = {k: v[0] for k, v in params["layers"]["full"].items()}
    p["mlp_norm"] = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (64,))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 48, 64))
    u = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps, True)[0]
    want = ref.routed_layer(cfg, p, u)
    shared = want - ref.routed_layer(cfg, p, u, shared=False)
    assert float(jnp.abs(shared).max()) > 1e-3
    mlp = qwen3_next.LAYER_KINDS["full"][1]
    ctx = Ctx(None, {})
    total = ref_total = shared
    for first in range(0, 16, 4):
        mine = {**p, **{n: p[n][first:first + 4]
                        for n in ("e_gate", "e_up", "e_down")}}
        held = replace(cfg, experts_held=(first, 4))
        with jax.default_matmul_precision("highest"):
            out, said = mlp.body(held, x, mine, ctx)
        assert int(said["router"]["counts"].sum()) == 48 * cfg.top_k
        total = total + (out - x)[0] - shared
        ref_total = ref_total + ref.routed_layer(held, mine, u, shared=False)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_total, want, rtol=1e-4, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        whole, _ = mlp.body(cfg, x, p, ctx)
    np.testing.assert_allclose((whole - x)[0], want, rtol=1e-4, atol=1e-5)


def test_the_plan_knows_both_kinds():
    cfg = Qwen3NextConfig.tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                               experts_held=(0, 4))
    params = jax.eval_shape(lambda k: qwen3_next.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    stack = llama.describe_stack(cfg, qwen3_next.LAYER_KINDS,
                                 params["layers"], 64, pattern=cfg.pattern,
                                 head_tokens=64)
    assert stack["runs"] == (("linear", 3), ("full", 1))
    kinds = stack["kinds"]
    # a full layer keeps its flash output and log-sum-exp on the first rung
    # at its own 4 heads of 16, not at ``wq``'s width, which holds the gate
    assert kinds["full"]["rungs"][0] == 64 * (64 * 2 + 4 * 4)
    assert kinds["full"]["rungs"][1] == 64 * (64 + 2 * 32) * 2
    # a linear layer's one rung that keeps anything is the shared SwiGLU's
    assert kinds["linear"]["rungs"] == (0, 0, 2 * 64 * 32 * 2, 0)
    plan = llama.remat_plan(cfg, stack, 64, 10 ** 6, 10 ** 9, False)
    assert set(plan["level"]) == {"linear", "full"}


def test_the_configurations_headroom_widens_a_pass_and_changes_no_sum():
    """``held_headroom`` (the benchmark's configuration says 4) reaches the
    layer's passes and ``rows_passed`` alike: at 48 tokens, 4 of 16 experts
    a token, 4 held, a pass is one tile either way here, so the counter is
    read at the cell's own sizes from hand-made counts; the layer's result
    is the same under any headroom."""
    cfg = Qwen3NextConfig.tiny(experts_held=(4, 4))
    params = qwen3_next.init_params(cfg, jax.random.PRNGKey(2))
    p = {k: v[0] for k, v in params["layers"]["full"].items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 48, 64))
    mlp = qwen3_next.LAYER_KINDS["full"][1]
    with jax.default_matmul_precision("highest"):
        narrow, _ = mlp.body(cfg, x, p, Ctx(None, {}))
        wide, _ = mlp.body(replace(cfg, held_headroom=1), x, p, Ctx(None, {}))
    np.testing.assert_allclose(narrow, wide, rtol=1e-5, atol=1e-6)
    cell = Qwen3NextConfig.qwen3_next_80b_a3b(num_layers=4,
                                              experts_held=(0, 64))
    counts = np.zeros((4, 512), np.int64)
    counts[:, 0], counts[:, 64] = 48_000, 327_680 - 48_000
    assert qwen3_next.rows_passed(cell, counts) == 4 * 2 * 46_080
    assert qwen3_next.rows_passed(replace(cell, held_headroom=4),
                                  counts) == 4 * 51_200
    kept = [mlp.keeps(replace(cell, held_headroom=part),
                      {"e_gate": (64, 2048, 512), "router": (2048, 512),
                       "s_gate": (2048, 512)}, 32_768, None)["rows"]
            for part in (None, 4)]
    assert kept[1] - kept[0] == (51_200 - 46_080) * (2 * 2048 + 6 * 512) * 2
