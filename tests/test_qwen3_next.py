"""Qwen3-Next's language model (``models/qwen3_next.py``): its row of the
conformance suite (``tests/model_suite.py``: the program at ``tiny()``
against ``benchmark/references/qwen3_next_ref.py`` on the program's own
choices of experts, every expert here and at experts 4..7; the parts four
chips give add up to the uncut layer; the remat plan's two kinds), and what
only Qwen3-Next has: the rule at grouped heads against the token-by-token
recurrence, the reference's weighted gradient with its router term, and
the configuration's headroom."""

from dataclasses import replace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402
from benchmark.references import qwen3_next_ref as ref  # noqa: E402
from ray_tpu.models import qwen3_next  # noqa: E402
from ray_tpu.models.qwen3_next import Qwen3NextConfig  # noqa: E402
from ray_tpu.ops import delta  # noqa: E402
from ray_tpu.ops.layers import Ctx  # noqa: E402

ROWS = ("qwen3_next",)
globals().update(model_suite.tests_of(ROWS))


@pytest.mark.parametrize("case", model_suite.cases(ROWS)[1:], indirect=True)
def test_the_references_weighted_gradient_is_the_losss(case):
    """``token_nll(grad_weights=1 / n, router_term=True)`` of one row is
    the gradient of ``loss`` for the leaves it is asked for (the suite's:
    the reference's gradient of the first row's loss on the program's
    choices). Only this reference has a router term in its weighted
    gradient, and it is a row's."""
    _, ref, cfg, params, tokens = case
    chosen = case.forced["forced_topk"][:, :32]
    got = ref.token_nll(cfg, params, tokens[:1], forced_topk=chosen,
                        grad_weights=np.full((1, 32), 1 / 32, np.float32),
                        router_term=True)["grads"]
    want = ref.first_layers(case.gradients[1])
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
