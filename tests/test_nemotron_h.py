"""Nemotron-H through ``models/stack.py`` (``models/nemotron_h.py``: kinds of
one part, scans at grouped heads with a gated norm a group at a time,
attention without rotation, a mixture of two-matrix experts in a latent
beside a squared-ReLU shared expert, a prediction module that shares the
embedding and the head) against ``benchmark/references/nemotron_h_ref.py``,
float32 on the CPU at the tiny size."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.references import nemotron_h_ref as ref  # noqa: E402
from ray_tpu.models import llama, nemotron_h as model  # noqa: E402
from ray_tpu.ops.layers import Ctx, relu2_mlp, rms_norm  # noqa: E402

TOKENS = np.random.default_rng(0).integers(0, 256, (2, 34), np.int32)


def _seeded(held=None, **kw):
    """(config, parameters with router biases that take part in the
    choice)."""
    cfg = model.Nemotron_hConfig.tiny(experts_held=held, **kw)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 2))
    for layers in (params["layers"], params.get("mtp", {}).get("layers", {})):
        if "moe" in layers:
            b = layers["moe"]["router_bias"]
            layers["moe"]["router_bias"] = 0.05 * jax.random.normal(
                next(keys), b.shape, b.dtype)
    return cfg, params


@pytest.fixture(scope="module")
def whole():
    return _seeded()


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.sqrt(np.square(a - b).sum() / np.square(b).sum())


def test_config_is_the_published_one():
    cfg = model.Nemotron_hConfig.nemotron_3_super_120b_a12b()
    assert len(cfg.pattern) == 88
    assert (cfg.pattern.count("mamba"), cfg.pattern.count("moe"),
            cfg.pattern.count("attention")) == (40, 40, 8)
    assert cfg.pattern[26:37] == tuple(
        {"E": "moe", "M": "mamba", "*": "attention"}[c]
        for c in "EMEMEMEMEM*")
    assert cfg.mtp_pattern == ("attention", "moe")
    assert (cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_chunk, cfg.top_k,
            cfg.num_experts, cfg.moe_latent_size, cfg.routed_scale) == (
        128, 8, 128, 22, 512, 1024, 5.0)
    with pytest.raises(ValueError, match="a layer is M, E or"):
        model.Nemotron_hConfig.tiny(layer_pattern="MEMXE")
    with pytest.raises(ValueError, match="num_layers is 5"):
        model.Nemotron_hConfig.tiny(layer_pattern="ME")


def test_the_cells_parameter_count_is_the_configuration_files():
    cfg = model.Nemotron_hConfig.nemotron_3_super_120b_a12b(
        layer_pattern="EMEMEMEMEM*", vocab_size=16384, experts_held=(0, 8))
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 1_378_724_736
    assert "e_gate" not in shapes["layers"]["moe"]
    assert shapes["layers"]["moe"]["e_up"].shape == (5, 8, 1024, 2688)
    assert shapes["layers"]["mamba"]["m_in"].shape == (5, 4096, 18560)
    assert shapes["mtp"]["join"].shape == (8192, 4096)


def test_logits_match_the_reference(whole):
    cfg, params = whole
    got, said = jax.jit(lambda p, t: model.forward(cfg, p, t))(
        params, TOKENS[:, :32])
    want = ref.logits(cfg, params, TOKENS[:, :32])
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert said["ssm_state"].shape == (2, 2, 8, 16, 16)
    assert said["router"]["counts"].shape == (2, 16)


def _chosen(cfg, params):
    return np.asarray(jax.jit(lambda p: model.token_nlls(
        cfg, p, TOKENS, keep_router_logits=True))(params)[2]["router"][
            "chosen"])


def _reference_grads(cfg, params, chosen):
    """(cross entropy, the module's, the gradient of each with respect to
    every leaf) from one compiled function: the gradient of ``ce + w *
    more`` is linear in ``w``."""
    def weighted(p, w):
        _, (ce, more) = ref.loss(cfg, p, TOKENS, forced_topk=chosen)
        return ce + w * more, (ce, more)

    both = jax.jit(jax.value_and_grad(weighted, has_aux=True))
    (_, (ce, more)), g0 = both(params, 0.0)
    _, g1 = both(params, 1.0)
    return float(ce), float(more), g0, jax.tree_util.tree_map(
        jnp.subtract, g1, g0)


@pytest.fixture(scope="module")
def reference_grads(whole):
    return _reference_grads(*whole, _chosen(*whole))


def _program_grads(cfg, params, scale):
    import dataclasses

    c = dataclasses.replace(cfg, mtp_loss_scale=scale)
    return jax.jit(jax.value_and_grad(lambda t: model.loss_terms(
        c, model.with_trainable(params, t), {"tokens": TOKENS}),
        has_aux=True))(model.trainable(params))


def test_both_losses_and_every_gradient_match_the_reference(
        whole, reference_grads):
    cfg, params = whole
    ce, more, g_ce, g_more = reference_grads
    (loss, terms), grads = _program_grads(cfg, params, 0.1)
    assert _chosen(cfg, params).shape == (3, 64, 4)   # the module's last
    assert abs(float(terms["cross_entropy"]) - ce) < 1e-5
    assert abs(float(terms["mtp_cross_entropy"]) - more) < 1e-5
    assert abs(float(loss) - ce - 0.1 * more) < 1e-5
    assert terms["expert_counts"].shape == (3, 16)
    want = model.trainable(jax.tree_util.tree_map(
        lambda a, b: a + 0.1 * b, g_ce, g_more))
    gaps = jax.tree_util.tree_map(_rel, grads, want)
    assert max(jax.tree_util.tree_leaves(gaps)) < 2e-5, gaps
    # the reference's own choice is the program's
    own = ref.token_nll(cfg, params, TOKENS)
    assert (np.sort(own["chosen"], -1)
            == np.sort(_chosen(cfg, params), -1)).all()


def test_a_held_share_gives_the_references_losses():
    """Experts 4-7 of 16 held (the module's mixture the same indices): both
    losses against the reference's on the program's choices (the held
    passes' gradient at a latent's width is ``tests/test_ops.py``'s)."""
    cfg, params = _seeded((4, 4))
    loss, terms = jax.jit(lambda p: model.loss_terms(
        cfg, p, {"tokens": TOKENS}))(params)
    chosen = _chosen(cfg, params)
    want, (ce, more) = jax.jit(lambda p: ref.loss(
        cfg, p, TOKENS, forced_topk=chosen))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    assert abs(float(terms["cross_entropy"]) - float(ce)) < 1e-5
    assert abs(float(terms["mtp_cross_entropy"]) - float(more)) < 1e-5
    assert params["layers"]["moe"]["e_up"].shape == (2, 4, 32, 48)
    assert int(model.rows_held(cfg, terms["expert_counts"])) == int(
        terms["expert_counts"][:, 4:8].sum())


def test_the_modules_term_reaches_the_shared_embedding_and_head(
        whole, reference_grads):
    """The module has no embedding and no head of its own: the gradient of
    its cross entropy alone arrives at the model's, as the reference's."""
    cfg, params = whole
    assert set(params["mtp"]) == {"embed_norm", "hidden_norm", "join",
                                  "layers", "final_norm"}
    _, _, g_ce, g_more = reference_grads
    (_, terms), with_term = _program_grads(cfg, params, 1.0)
    _, without = _program_grads(cfg, params, 0.0)
    grads = jax.tree_util.tree_map(jnp.subtract, with_term, without)
    for name in ("embed", "lm_head"):
        assert float(jnp.abs(grads[name]).max()) > 0
        assert _rel(grads[name], g_more[name]) < 5e-5, name
        assert _rel(without[name], g_ce[name]) < 2e-5, name
    # the main model's last norm is not the module's
    assert float(jnp.abs(grads["final_norm"]).max()) < 1e-7
    assert float(jnp.abs(without["mtp"]["final_norm"]).max()) == 0.0
    assert float(jnp.abs(grads["mtp"]["final_norm"]).max()) > 0
    # a module reading another target is another function
    shifted = np.concatenate([TOKENS[:, :-1], TOKENS[:, :1]], axis=1)
    other = jax.jit(lambda p: model.loss_terms(cfg, p, {"tokens": shifted}))(
        params)[1]
    assert abs(float(other["cross_entropy"])
               - float(terms["cross_entropy"])) < 1e-6
    assert abs(float(other["mtp_cross_entropy"])
               - float(terms["mtp_cross_entropy"])) > 1e-3


def test_a_config_without_a_module_takes_seq_plus_one_ids():
    cfg, params = _seeded(mtp_layer_pattern="")
    assert "mtp" not in params and "mtp" not in model.logical_axes(cfg)
    loss, terms = jax.jit(lambda p: model.loss_terms(
        cfg, p, {"tokens": TOKENS[:, :33]}))(params)
    assert "mtp_cross_entropy" not in terms
    want, _ = ref.loss(cfg, params, TOKENS[:, :33])
    assert abs(float(loss) - float(want)) < 1e-5
    assert terms["expert_counts"].shape == (2, 16)


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """16 experts as 4 shares of 4: each share's layer gives ``x + shared(u)
    + (its experts' latent sums) W_up``; the routed parts of all shares,
    with the shared expert counted once, are the uncut reference's layer."""
    cfg, params = whole
    part = model.LAYER_KINDS["moe"][0]
    p = {k: v[0] for k, v in params["layers"]["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64), jnp.float32)
    u = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    shared = relu2_mlp(u, p["s_up"], p["s_down"])
    ctx = Ctx(None, {})
    routed = 0.0
    for first in range(0, 16, 4):
        share_cfg = model.Nemotron_hConfig.tiny(experts_held=(first, 4))
        share = {**p, "e_up": p["e_up"][first:first + 4],
                 "e_down": p["e_down"][first:first + 4]}
        out, said = jax.jit(lambda x_, p_: part.body(share_cfg, x_, p_, ctx)
                            )(x, share)
        assert int(said["router"]["counts"].sum()) == 2 * 32 * 4
        routed = routed + (out - x - shared)
    want = ref.mixture(cfg, p, u.reshape(64, 64)).reshape(2, 32, 64)
    np.testing.assert_allclose(routed + shared, want, atol=2e-5)
    whole_out, _ = part.body(cfg, x, p, ctx)
    np.testing.assert_allclose(whole_out - x, want, atol=2e-5)


def test_a_scan_layer_matches_the_reference_a_group_at_a_time(whole):
    """The gated norm runs over each group's channels on its own: the part
    against the reference's mixer, and against one norm over all channels,
    which is another function."""
    from ray_tpu.ops import ssm

    cfg, params = whole
    p = {k: v[0] for k, v in params["layers"]["mamba"].items()}
    p["m_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), p["m_norm"].shape)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64), jnp.float32)
    sizes = dict(heads=8, head_dim=16, state=16, groups=2, chunk=8)
    got, S = ssm.mamba2_mixer(u, p, norm_groups=2, **sizes)
    want, want_S = ref.mixer(cfg, p, u[0])
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    np.testing.assert_allclose(S[0], want_S, atol=2e-5)
    one_group, _ = ssm.mamba2_mixer(u, p, **sizes)
    assert float(jnp.abs(one_group - got).max()) > 1e-3


def test_kinds_of_one_part_get_leaves_axes_a_plan_and_the_bias_update(whole):
    cfg, params = whole
    axes = model.logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    assert axes["layers"]["moe"]["e_up"] == ("layer", "expert", "embed",
                                             "mlp")
    assert axes["mtp"]["join"] == ("mlp", "embed")
    assert set(params["layers"]["mamba"]) == set(
        model.LAYER_KINDS["mamba"][0].leaves(cfg))
    described = llama.describe_stack(
        cfg, model.LAYER_KINDS, params["layers"], 64,
        pattern=cfg.pattern + cfg.mtp_pattern)
    assert described["runs"] == (("mamba", 1), ("moe", 1), ("mamba", 1),
                                 ("attention", 1), ("moe", 1),
                                 ("attention", 1), ("moe", 1))
    plan = llama.remat_plan(cfg, described, 64, 10 ** 6, 10 ** 9, False)
    assert plan["layers"] == {"mamba": 2, "moe": 3, "attention": 2}
    assert set(plan["level"]) == {"mamba", "moe", "attention"}
    # the bias: the stack's two mixtures, then the module's
    counts = jnp.asarray(np.random.default_rng(1).integers(
        0, 9, (3, 16)), jnp.int32)
    moved = model.update_router_bias(cfg, params, counts)
    want = ref.updated_bias(cfg, ref.router_biases(cfg, params),
                            np.asarray(counts))
    np.testing.assert_array_equal(ref.router_biases(cfg, moved), want)
    assert float(model.router_bias_abs_max(moved)) == pytest.approx(
        np.abs(want).max())
    trained = model.trainable(params)
    assert "router_bias" not in trained["layers"]["moe"]
    assert "router_bias" not in trained["mtp"]["layers"]["moe"]
    back = model.with_trainable(params, trained)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(params)


def test_a_rematted_step_is_the_same_function(whole):
    cfg, params = whole
    import dataclasses

    remat = dataclasses.replace(cfg, remat=True, remat_policy="full")

    def grad_of(c):
        return jax.jit(jax.grad(lambda t: model.loss_terms(
            c, model.with_trainable(params, t), {"tokens": TOKENS})[0]))(
                model.trainable(params))

    a, b = grad_of(cfg), grad_of(remat)
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, b, a)))
    assert worst < 1e-5
