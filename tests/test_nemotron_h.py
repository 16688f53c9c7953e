"""Nemotron-H through ``models/stack.py`` (``models/nemotron_h.py``): its
row of the conformance suite (``tests/model_suite.py``: the program at
``tiny()`` against ``benchmark/references/nemotron_h_ref.py``, whole and
at experts 4..7; four shares of four experts add up to the uncut layer),
and what only Nemotron-H has: a prediction module that shares the
embedding and the head, kinds of one part, scans with a gated norm a group
at a time, and a config without the module."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402
from benchmark.references import nemotron_h_ref as ref  # noqa: E402
from ray_tpu.models import llama, nemotron_h as model  # noqa: E402
from tests.model_table import (ROWS as TABLE,  # noqa: E402
                               nemotron_h_program_grads,
                               nemotron_h_reference_grads)

ROWS = ("nemotron_h",)
globals().update(model_suite.tests_of(ROWS))
ROW = TABLE["nemotron_h"]
_WHOLE = model_suite.cases(ROWS)[:1]
TOKENS = np.random.default_rng(0).integers(0, 256, (2, 34), np.int32)  # its


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.sqrt(np.square(a - b).sum() / np.square(b).sum())


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_the_modules_term_reaches_the_shared_embedding_and_head(case):
    """The module has no embedding and no head of its own: the gradient of
    its cross entropy alone arrives at the model's, as the reference's
    (whose two gradients are the suite's)."""
    cfg, params = case.cfg, case.params
    assert set(params["mtp"]) == {"embed_norm", "hidden_norm", "join",
                                  "layers", "final_norm"}
    _, _, g_ce, g_more = nemotron_h_reference_grads(case)
    (_, terms), with_term = nemotron_h_program_grads(case, 1.0)
    _, without = nemotron_h_program_grads(case, 0.0)
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
    cfg = model.Nemotron_hConfig.tiny(mtp_layer_pattern="")
    params = ROW.also_moved(model.init_params(cfg, jax.random.PRNGKey(0)))
    assert "mtp" not in params and "mtp" not in model.logical_axes(cfg)
    loss, terms = jax.jit(lambda p: model.loss_terms(
        cfg, p, {"tokens": TOKENS[:, :33]}))(params)
    assert "mtp_cross_entropy" not in terms
    want, _ = ref.loss(cfg, params, TOKENS[:, :33])
    assert abs(float(loss) - float(want)) < 1e-5
    assert terms["expert_counts"].shape == (2, 16)


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_a_scan_layer_matches_the_reference_a_group_at_a_time(case):
    """The gated norm runs over each group's channels on its own: the part
    against the reference's mixer, and against one norm over all channels,
    which is another function."""
    from ray_tpu.ops import ssm

    cfg, params = case.cfg, case.params
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


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_kinds_of_one_part_get_leaves_axes_a_plan_and_the_bias_update(case):
    cfg, params = case.cfg, case.params
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


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_a_rematted_step_is_the_same_function(case):
    """(Against the suite's gradient of the program without remat.)"""
    import dataclasses

    cfg, params = case.cfg, case.params
    remat = dataclasses.replace(cfg, remat=True, remat_policy="full")
    a = model.trainable(case._loss_and_gradient[1])
    b = jax.jit(jax.grad(lambda t: model.loss_terms(
        remat, model.with_trainable(params, t), {"tokens": TOKENS})[0]))(
            model.trainable(params))
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(_rel, b, a)))
    assert worst < 1e-5
