"""LFM2 (``models/lfm2.py``): its row of the conformance suite
(``tests/model_suite.py``: the program at ``tiny()`` against
``benchmark/references/lfm2_ref.py``, every expert here and at a chip's
share), and what only LFM2 has: a router bias that takes part in the
choice, moves by a rule of its own and belongs to no optimizer, and what
its config refuses."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402

ROWS = ("lfm2",)
globals().update(model_suite.tests_of(ROWS))


@pytest.mark.parametrize("case", model_suite.cases(ROWS), indirect=True)
def test_update_router_bias_is_the_references_rule(case):
    lfm2, lfm2_ref, cfg, params, tokens = case
    counts = np.random.default_rng(3).integers(0, 40, (4, 8))
    counts[2] = 16                                  # a balanced layer: no move
    before = lfm2_ref.router_biases(cfg, params)
    after = lfm2.update_router_bias(cfg, params, jnp.asarray(counts))
    want = lfm2_ref.updated_bias(cfg, before, counts)
    np.testing.assert_array_equal(lfm2_ref.router_biases(cfg, after), want)
    assert (want[2] == before[2]).all() and (want[0] != before[0]).any()
    # routed layers 0 is the attention layer's, 1..3 the conv layers'
    np.testing.assert_array_equal(
        np.asarray(after["layers"]["attn_moe"]["router_bias"][0]), want[0])
    np.testing.assert_array_equal(
        np.asarray(after["layers"]["conv_moe"]["router_bias"]), want[1:])
    assert float(lfm2.router_bias_abs_max(after)) == float(
        np.abs(want).max())
    # nothing else moved
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(lfm2.trainable(after))[0],
            jax.tree_util.tree_leaves(lfm2.trainable(params))):
        assert a is b, path


def test_router_bias_balances_a_skewed_router():
    """200 steps of the bias update alone on a router that sends most
    rows to two experts: ``expert_load_max_over_mean`` falls."""
    from ray_tpu.ops.moe import route

    E, K, n = 8, 2, 512
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 16))
    w = (jax.random.normal(jax.random.PRNGKey(1), (16, E)) * 0.2)
    x = x.at[:, 0].set(3.0)
    w = w.at[0, :2].add(0.5)             # experts 0 and 1 favoured
    from ray_tpu.models import lfm2

    cfg = lfm2.Lfm2Config.tiny(num_layers=2, num_dense_layers=1,
                               attention_layers=(False, False),
                               bias_update_rate=0.01)
    params = {"layers": {"conv_moe": {"router_bias": jnp.zeros((1, E))}}}

    @jax.jit
    def step(params):
        top_e = route(x, w, K, True, score="sigmoid",
                      select_bias=params["layers"]["conv_moe"][
                          "router_bias"][0], renorm_eps=1e-6)[2]
        counts = (top_e.reshape(-1, 1) == jnp.arange(E)).sum(0)[None]
        return lfm2.update_router_bias(cfg, params, counts), counts[0]

    loads = []
    for _ in range(200):
        params, counts = step(params)
        loads.append(float(counts.max() / counts.mean()))
    assert loads[0] > 2.0
    assert loads[-1] < 1.3
    assert float(lfm2.router_bias_abs_max(params)) <= 200 * 0.01 + 1e-6


@pytest.mark.parametrize("case", model_suite.cases(ROWS), indirect=True)
def test_trainable_leaves_the_bias_out_of_adamws_state(case):
    """(The gradient is the suite's: the case's one compiled function.)"""
    import optax

    lfm2, _, cfg, params, tokens = case
    owned = lfm2.trainable(params)
    assert all("router_bias" not in leaves
               for leaves in owned["layers"].values())
    n_all = len(jax.tree_util.tree_leaves(params))
    assert len(jax.tree_util.tree_leaves(owned)) == n_all - 2
    tx = optax.adamw(1e-3)
    opt = tx.init(owned)
    assert len(jax.tree_util.tree_leaves(opt[0].mu)) == n_all - 2
    grads = lfm2.trainable(case.gradients[0])
    updates, _ = tx.update(grads, opt, owned)
    stepped = lfm2.with_trainable(params, optax.apply_updates(owned, updates))
    assert jax.tree_util.tree_structure(stepped) == \
        jax.tree_util.tree_structure(params)
    for kind in ("attn_moe", "conv_moe"):       # adamw's decay never saw b
        assert stepped["layers"][kind]["router_bias"] is \
            params["layers"][kind]["router_bias"]
    assert float(jnp.abs(stepped["embed"] - params["embed"]).max()) > 0


@pytest.mark.parametrize("how, says", [
    ({"tie_embeddings": False}, "the head is the embedding"),
    ({"attention_layers": (True, True, False, False, False)},
     "an attention layer with a dense MLP")])
def test_lfm2_refuses_what_it_has_no_parameters_for(how, says):
    from ray_tpu.models import lfm2

    with pytest.raises(ValueError, match=says):
        lfm2.Lfm2Config.tiny(**how)


@pytest.mark.parametrize("case", model_suite.cases(ROWS), indirect=True)
def test_the_cells_check_sees_a_route_that_leaves_the_bias_out(
        case, monkeypatch):
    """(f) of ``benchmark/cells/train_hybrid.py``: ``route``'s own choices
    held to the selection scores recomputed from the program's logits and
    the biases. With the bias dropped inside ``ops/moe.route`` the reading
    is of the biases' size (0.1 here); the honest program reads a
    rounding."""
    from benchmark.cells import train_hybrid
    from ray_tpu.ops import moe

    lfm2, lfm2_ref, cfg, params, tokens = case
    tokens = jnp.asarray(tokens, jnp.int32)

    def reading():
        train_hybrid._program.cache_clear()
        return train_hybrid.choices_under_bias(lfm2, lfm2_ref, cfg, params,
                                               tokens)

    assert reading() < 1e-6
    honest = moe.route
    monkeypatch.setattr(
        moe, "route", lambda *a, select_bias=None, **kw: honest(*a, **kw))
    assert reading() > 0.01
    monkeypatch.undo()
    train_hybrid._program.cache_clear()
