"""The models that are a table of layer kinds (``models/stack.py`` walks
them): Laguna, LFM2, Granite 4.0-H and Olmo-Hybrid against their plain
references, what each owns beside its table, and the seam itself: the
parameters a table gives are the ones its module gave before there was a
walker, and a table no shipped model has trains."""

import hashlib
from importlib import import_module

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402
from tests.test_models import _cell_config  # noqa: E402

# model -> the norms, skips and biases its fixture moves off their initial
# 1 and 0: one applied twice, or dropped, would go unseen
_MOVED = {
    "laguna": ("attn_norm", "mlp_norm"),
    "lfm2": ("attn_norm", "op_norm", "mlp_norm", "q_norm", "k_norm",
             "router_bias"),
    "granite": ("attn_norm", "op_norm", "mlp_norm", "m_norm", "D",
                "m_conv_bias"),
    "olmo_hybrid": ("attn_post_norm", "op_post_norm", "mlp_post_norm",
                    "g_norm", "q_norm", "k_norm")}
_CONFIG = {"laguna": "LagunaConfig", "lfm2": "Lfm2Config",
           "granite": "GraniteConfig", "olmo_hybrid": "OlmoHybridConfig"}
# the fixture's cases: every expert here or a chip's share of each routed
# layer's, where the model routes
_LAGUNA = [pytest.param(("laguna", None), id="laguna-all-experts"),
           pytest.param(("laguna", (4, 8)), id="laguna-held-4..11")]
_LFM2 = [pytest.param(("lfm2", None), id="lfm2-all-experts"),
         pytest.param(("lfm2", (0, 4)), id="lfm2-held-0..3")]
_GRANITE = [pytest.param(("granite", None), id="granite")]
_OLMO_HYBRID = [pytest.param(("olmo_hybrid", None), id="olmo_hybrid")]
_BLOCKED = _GRANITE + _OLMO_HYBRID
_ALL = _LAGUNA + _LFM2 + _BLOCKED


def _name(mod):
    return mod.__name__.rpartition(".")[2]


@pytest.fixture(scope="module")
def stack_setup(request):
    """(module, reference, config, parameters, tokens [2, 33]) of a model's
    ``tiny()`` preset in float32. Laguna: five layers (full + dense MLP,
    three sliding, full), 4 and 6 query heads on 2 kv heads, window 8 at 32
    positions, 16 experts top-4 and a shared one. LFM2: five layers (conv +
    dense MLP, attention, three conv), 8 experts top-2 on sigmoid scores
    plus a bias. Granite: two Mamba-2 layers, attention, Mamba-2.
    Olmo-Hybrid: three delta-rule layers and a full one."""
    model, held = request.param
    mod = import_module("ray_tpu.models." + model)
    ref = import_module("benchmark.references." + model + "_ref")
    how = {"experts_held": held} if model in ("laguna", "lfm2") else {}
    cfg = getattr(mod, _CONFIG[model]).tiny(attn_impl="reference", **how)
    params = mod.init_params(cfg, jax.random.PRNGKey(0))
    for n, kind in enumerate(params["layers"]):
        for i, name in enumerate(_MOVED[model]):
            if name in params["layers"][kind]:
                w = params["layers"][kind][name]
                params["layers"][kind][name] = w + (
                    0.1 if name == "router_bias" else 0.3
                ) * jax.random.normal(jax.random.PRNGKey(10 * n + i), w.shape)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 33))
    return mod, ref, cfg, params, tokens


# ---- forward and loss against the reference


def _laguna_is_what_tiny_says(cfg, params):
    assert cfg.pattern == ("full_dense", "sliding_moe", "sliding_moe",
                           "sliding_moe", "full_moe")
    assert params["layers"]["sliding_moe"]["wq"].shape == (3, 64, 6 * 16)
    assert params["layers"]["full_moe"]["wq"].shape == (1, 64, 4 * 16)
    assert params["layers"]["full_moe"]["e_gate"].shape[1] == (
        8 if cfg.experts_held else 16)


def _lfm2_is_what_tiny_says(cfg, params):
    assert cfg.pattern == ("conv_dense", "attn_moe", "conv_moe", "conv_moe",
                           "conv_moe")
    assert params["layers"]["conv_moe"]["w_in"].shape == (3, 64, 192)
    assert params["layers"]["attn_moe"]["q_norm"].shape == (1, 16)
    assert params["layers"]["conv_moe"]["e_gate"].shape[1] == (
        4 if cfg.experts_held else 8)
    assert "lm_head" not in params                      # tied


def _granite_is_what_tiny_says(cfg, params):
    assert cfg.pattern == ("mamba", "mamba", "attention", "mamba")
    assert params["layers"]["mamba"]["m_in"].shape == (3, 64, 128 + 160 + 8)
    assert params["layers"]["mamba"]["m_conv"].shape == (3, 160, 4)
    assert "lm_head" not in params                      # tied
    # Mamba-2's published initialisation
    A = np.exp(np.asarray(params["layers"]["mamba"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["layers"]["mamba"]["dt_bias"])))
    assert 1.0 <= A.min() and A.max() <= 16.0
    assert 0.001 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6


def _olmo_hybrid_is_what_tiny_says(cfg, params):
    assert cfg.pattern == ("linear", "linear", "linear", "full")
    linear = params["layers"]["linear"]
    assert linear["g_in"].shape == (3, 64, 128 + 256 + 8)
    assert linear["g_conv"].shape == (3, 256, 4)
    assert params["lm_head"].shape == (64, 256)          # untied
    assert not {"attn_norm", "op_norm", "mlp_norm"} & (
        set(linear) | set(params["layers"]["full"]))     # OLMo 2's order
    # the delta-net's published initialisation
    A = np.exp(np.asarray(linear["g_A_log"]))
    dt = np.log1p(np.exp(np.asarray(linear["g_dt_bias"])))
    assert 0.0 <= A.min() and A.max() <= 16.0
    assert 0.001 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6


_IS_WHAT_TINY_SAYS = {"laguna": _laguna_is_what_tiny_says,
                      "lfm2": _lfm2_is_what_tiny_says,
                      "granite": _granite_is_what_tiny_says,
                      "olmo_hybrid": _olmo_hybrid_is_what_tiny_says}


def _routed_layers_match(mod, cfg, router, terms, loss, ref):
    """The four routed layers' router logits, choices and expert counts
    (over all the experts, held or not) and the terms of the loss."""
    E = cfg.num_experts
    assert router["logits"].shape == (4, 64, E)
    np.testing.assert_allclose(np.asarray(router["logits"]),
                               ref["router_logits"], rtol=1e-5, atol=1e-5)
    want_counts = np.stack([np.bincount(c.ravel(), minlength=E)
                            for c in ref["chosen"]])
    assert (np.asarray(terms["expert_counts"]) == want_counts).all()
    assert int(want_counts.sum()) == 4 * 64 * cfg.top_k
    first, count = cfg.experts_held or (0, E)
    assert int(mod.rows_held(cfg, terms["expert_counts"])) == int(
        want_counts[:, first:first + count].sum())
    if _name(mod) == "laguna":
        for name in ("cross_entropy", "load_balance"):
            assert abs(float(terms[name]) - ref["terms"][name]) < 1e-5, name
        # the share changes the result: what the absent experts add is
        # left out
        assert ref["terms"]["load_balance"] > 1.0
        assert "chosen" not in router
        return
    chosen = np.asarray(router["chosen"])              # route's own
    assert chosen.shape == (4, 64, cfg.top_k)
    assert (np.sort(chosen, -1) == np.sort(ref["chosen"], -1)).all()
    # the bias moved some choice away from the largest scores
    plain = np.argsort(-ref["router_logits"], -1)[..., :cfg.top_k]
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()
    assert ref["terms"]["load_balance"] == 0.0
    assert float(loss) == float(terms["cross_entropy"])


def _states_match(mod, cfg, params, tokens, states, terms, ref, atol):
    """The per-position loss through the blocked head, the mixers' last
    states (what ``forward`` handed back too) and the counter."""
    with jax.default_matmul_precision("highest"):
        nll, again = jax.jit(lambda p, t: mod.token_nll(
            cfg, p, t, head_block=16))(params, tokens)
    np.testing.assert_allclose(np.asarray(nll), ref["nll"], rtol=1e-5,
                               atol=atol)
    shape, counter = {
        "granite": lambda: ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                            "ssm_state_abs_max"),
        "olmo_hybrid": lambda: ((cfg.linear_heads, cfg.linear_value_dim,
                                 cfg.linear_key_dim), "gdn_state_abs_max"),
    }[_name(mod)]()
    assert states.shape == again.shape == ref["last_states"].shape == (
        3, tokens.shape[0]) + shape
    np.testing.assert_array_equal(np.asarray(states), np.asarray(again))
    np.testing.assert_allclose(np.asarray(states), ref["last_states"],
                               rtol=1e-5, atol=atol)
    np.testing.assert_allclose(float(terms[counter]), ref["state_abs_max"],
                               rtol=1e-5)
    assert ref["state_abs_max"] == np.abs(ref["last_states"]).max() > 0


@pytest.mark.parametrize("stack_setup", _ALL, indirect=True)
def test_forward_and_loss_match_the_reference(stack_setup):
    """Logits, the loss and its terms and what the layers report (the
    routed layers' router logits and counts; the scan's and the rule's last
    states, the per-position loss through the blocked head and the
    counter) against the plain float32 reference on seeded weights, at
    1e-5. Olmo-Hybrid at 5e-5: a block that norms every sublayer's output
    to unit size damps no rounding (the gap to the reference grows
    threefold a layer, 5e-6 after one and 3e-5 after three, and two chunk
    sizes differ by 1e-5 between themselves), where Granite's residual
    weights of 0.22 do. ``tests/test_ops.py`` holds the rule and the mixer
    alone to 1e-5."""
    mod, ref_mod, cfg, params, tokens = stack_setup
    model = _name(mod)
    _IS_WHAT_TINY_SAYS[model](cfg, params)
    atol = 5e-5 if model == "olmo_hybrid" else 1e-5
    with jax.default_matmul_precision("highest"):
        logits, reported = jax.jit(lambda p, t: mod.forward(
            cfg, p, t, keep_router_logits=True))(params, tokens[:, :-1])
        loss, terms = jax.jit(lambda p, t: mod.loss_terms(
            cfg, p, {"tokens": t}))(params, tokens)
    ref = ref_mod.token_nll(cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_mod.logits(
            cfg, params, tokens[:, :-1])), rtol=1e-5, atol=atol)
    assert abs(float(loss) - ref["terms"]["loss"]) < 1e-5 * max(
        1.0, ref["terms"]["loss"])
    if model in ("laguna", "lfm2"):
        _routed_layers_match(mod, cfg, reported, terms, loss, ref)
    else:
        _states_match(mod, cfg, params, tokens, reported, terms, ref, atol)


# model -> (the leaves a gradient has: top, then each kind's; what counts
# as reached; the tolerance's factor)
_GRADIENT = {"laguna": (3 + 10 + 2 * 14, 1e-5, 1e-5),
             "lfm2": (2 + 8 + 12 + 9, 1e-5, 1e-5),
             "granite": (2 + 9 + 13, 1e-6, 1e-5),
             "olmo_hybrid": (3 + 11 + 11, 1e-6, 5e-5)}


@pytest.mark.parametrize("stack_setup", _ALL, indirect=True)
def test_gradients_match_the_reference(stack_setup):
    """Every trained leaf's gradient of the loss against the reference's;
    LFM2's bias has none."""
    mod, ref_mod, cfg, params, tokens = stack_setup
    leaves, reached, tol = _GRADIENT[_name(mod)]
    owned = getattr(mod, "trainable", lambda p: p)
    back = getattr(mod, "with_trainable", lambda p, t: t)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda t: mod.loss_fn(
            cfg, back(params, t), {"tokens": tokens})))(owned(params))
    want = jax.jit(jax.grad(lambda t: ref_mod.loss(
        cfg, back(params, t), tokens)))(owned(params))
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    assert len(flat) == leaves
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > reached, path                    # it is reached
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=tol * max(scale, 1e-2),
                                   err_msg=str(path))
    if _name(mod) == "lfm2":
        whole = jax.jit(jax.grad(lambda p: mod.loss_fn(
            cfg, p, {"tokens": tokens})))(params)
        for kind in ("attn_moe", "conv_moe"):
            assert float(jnp.abs(
                whole["layers"][kind]["router_bias"]).max()) == 0.0


def _weighted_laguna(laguna, laguna_ref, cfg, params, tokens):
    """The first layer of each kind, the embedding, the last norm and the
    head, row by row, and the rest of ``token_nll``'s result is what it is
    without the gradient."""
    weights = np.random.default_rng(3).uniform(
        0.5, 1.5, (2, 32)).astype(np.float32) / 64

    def weighted(p):
        lg, _ = laguna.forward(cfg, p, tokens[:, :-1])
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tokens[:, 1:, None], -1)[..., 0]
        return (weights * nll).sum()

    with jax.default_matmul_precision("highest"):
        got = laguna_ref.first_layers(jax.jit(jax.grad(weighted))(params))
    plain = laguna_ref.token_nll(cfg, params, tokens)
    ref = laguna_ref.token_nll(cfg, params, tokens, grad_weights=weights)
    np.testing.assert_allclose(ref["nll"], plain["nll"], atol=1e-6)
    assert (ref["chosen"] == plain["chosen"]).all()
    assert got["layers"]["sliding_moe"]["wq"].shape == (64, 6 * 16)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    assert len(flat) == 3 + 10 + 2 * 14
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(ref["grads"])):
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * max(scale, 1e-2),
                                   err_msg=str(path))


def _weighted_lfm2(lfm2, lfm2_ref, cfg, params, tokens):
    """The first layer of each kind, the embedding and the last norm, on
    forced choices."""
    weights = np.random.default_rng(2).uniform(
        0.5, 1.5, (2, 32)).astype(np.float32) / 64

    def weighted(t):
        lg, router = lfm2.forward(cfg, lfm2.with_trainable(params, t),
                                  tokens[:, :-1], keep_router_logits=True)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, jnp.asarray(tokens)[:, 1:, None], -1)[..., 0]
        return (weights * nll).sum(), router["chosen"]

    with jax.default_matmul_precision("highest"):
        (_, chosen), got = jax.jit(jax.value_and_grad(
            weighted, has_aux=True))(lfm2.trainable(params))
    chosen = np.asarray(chosen)
    ref = lfm2_ref.token_nll(cfg, params, tokens, forced_topk=chosen,
                             grad_weights=weights)
    got = lfm2_ref.first_layers(got)
    assert set(ref["grads"]) == {"embed", "final_norm", "layers"}
    for kind, leaves in ref["grads"]["layers"].items():
        assert "router_bias" not in leaves
        for name, w in leaves.items():
            np.testing.assert_allclose(
                np.asarray(got["layers"][kind][name]), np.asarray(w),
                rtol=1e-4, atol=1e-6, err_msg=f"{kind}/{name}")
    for name in ("embed", "final_norm"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(ref["grads"][name]),
                                   rtol=1e-4, atol=1e-6)


def _weighted_through_the_blocked_head(mod, ref_mod, cfg, params, tokens):
    """The first layer of each kind, the embedding, the last norm and a
    head of its own, through the blocked head."""
    granite = _name(mod) == "granite"
    weights = np.random.default_rng(2).uniform(
        0.5, 1.5, (2, 32)).astype(np.float32) / 64
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: (weights * mod.token_nll(
            cfg, p, jnp.asarray(tokens), head_block=32)[0]).sum()))(params)
    ref = ref_mod.token_nll(cfg, params, tokens, grad_weights=weights)
    got = ref_mod.first_layers(got)
    assert set(ref["grads"]) == {"embed", "final_norm", "layers"} | (
        set() if granite else {"lm_head"})
    assert set(ref["grads"]["layers"]) == (
        {"mamba", "attention"} if granite else {"linear", "full"})
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref["grads"])):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4,
            atol=(1e-5 if granite else 5e-5)
            * max(float(jnp.abs(b).max()), 1e-4))


_WEIGHTED = {"laguna": _weighted_laguna, "lfm2": _weighted_lfm2,
             "granite": _weighted_through_the_blocked_head,
             "olmo_hybrid": _weighted_through_the_blocked_head}


@pytest.mark.parametrize("stack_setup", _ALL, indirect=True)
def test_reference_gradient_of_a_weighted_loss(stack_setup):
    """What the chip check compares: ``token_nll(grad_weights=...)`` gives
    the gradient of ``sum(weights * per-position loss)`` for the first
    layer of each kind and the leaves above the stack; the program's own
    gradient of that scalar agrees."""
    mod, *rest = stack_setup
    _WEIGHTED[_name(mod)](mod, *rest)


@pytest.mark.parametrize("masked", [False, True], ids=["mean", "masked"])
@pytest.mark.parametrize("stack_setup", _BLOCKED, indirect=True)
def test_training_loss_is_the_weighted_mean_of_token_nll(stack_setup, masked):
    """The timed path held to the path the cell's check differentiates:
    ``loss_terms`` (``blocked_head_loss``, whose rule takes a block's
    gradients in the forward) and the same weighted mean of
    ``token_nll``'s positions (the checkpointed rows) give one loss and,
    leaf by leaf, one gradient, with a mask that zeroes positions and
    without."""
    mod, _ref, cfg, params, tokens = stack_setup
    tokens = jnp.asarray(tokens)
    mask = (jnp.asarray(np.random.default_rng(5).uniform(size=(2, 33)) < 0.6,
                        jnp.float32) if masked else None)
    batch = {"tokens": tokens, **({"mask": mask} if masked else {})}

    def through_rows(p):
        nll, _ = mod.token_nll(cfg, p, tokens)
        if not masked:
            return nll.mean()
        return (nll * mask[:, 1:]).sum() / jnp.maximum(mask[:, 1:].sum(), 1)

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda p: mod.loss_fn(cfg, p, batch)))(params)
        want, want_g = jax.jit(jax.value_and_grad(through_rows))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(got_g)
    assert len(flat) == _GRADIENT[_name(mod)][0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6 * max(scale, 1e-2),
                                   err_msg=str(path))


@pytest.mark.parametrize("how", ["ramp", "constant-rate", "unchanged"])
@pytest.mark.parametrize("stack_setup", _BLOCKED, indirect=True)
def test_first_step_against_the_reference_adamw(stack_setup, how):
    """What the cell's check holds the update to: the first moment and the
    parameters its own train step hands on, against optax's adamw in
    float32 on the reference's gradient of the mean loss. At the foot of
    a ramp the rate is 0 and the parameters come out bit-equal; at a
    constant rate they move as the reference's do; a step that hands on
    what it was given reads 1 on the moment."""
    import optax

    mod, ref_mod, cfg, params, tokens = stack_setup
    granite = _name(mod) == "granite"
    cell = import_module("benchmark.cells."
                         + ("train_scan" if granite else "train_delta"))
    tokens = np.asarray(tokens, np.int32)
    tx = optax.adamw(1e-3 if how == "constant-rate"
                     else optax.linear_schedule(0.0, 1e-4, 2000))
    with jax.default_matmul_precision("highest"):
        after, opt, loss, counter = jax.jit(cell.make_step(
            mod, cfg, tx))(params, tx.init(params), {"tokens": tokens})
        left = cell.first_step_left(ref_mod, after, opt)
        if how == "unchanged":
            left = {"params": jax.device_get(ref_mod.first_layers(params)),
                    "mu": jax.tree_util.tree_map(np.zeros_like, left["mu"])}
        gaps = cell.compare(mod, ref_mod, cfg, params, jnp.asarray(tokens),
                            tokens, first_step=(tx, left))
    moment = [v for leaves in gaps["first_step"]["moment_gap"].values()
              for v in leaves.values()]
    assert len(moment) == _GRADIENT[_name(mod)][0]
    if not granite:
        assert set(gaps["gradient_gap"]) == {"linear", "full", "top"}
    if how == "unchanged":
        assert all(v == 1.0 for v in moment)
    else:
        assert max(moment) < (1e-5 if granite else 1e-4)
    if how == "constant-rate":
        moved = float(jnp.abs(after["embed"] - params["embed"]).max())
        assert 5e-4 < moved < 2e-3                  # one step at 1e-3
        assert gaps["first_step"]["param_gap"] < 1e-6
    else:
        assert gaps["first_step"]["param_gap"] == 0.0
    assert gaps["state_head_gap"]["worst"] < (1e-5 if granite else 1e-4)
    assert float(counter) == pytest.approx(
        gaps["state_abs_max"]["reference"], rel=1e-5)


@pytest.mark.parametrize("stack_setup, what", [
    pytest.param((model, None), what, id=f"{model}-{what}")
    for model, more in (("granite", ()),
                        ("olmo_hybrid", ("chunk-4", "chunk-16")))
    for what in ("remat-full", "unrolled", "bf16") + more],
    indirect=["stack_setup"])
def test_variants_agree(stack_setup, what):
    """Full remat, the unrolled layer loop and another chunk of the rule
    compute what the scanned stack without remat does (at a chunk of 8);
    in bf16 the loss stays near float32's."""
    from dataclasses import replace

    mod, _, cfg, params, tokens = stack_setup
    base = float(jax.jit(lambda p: mod.loss_fn(
        cfg, p, {"tokens": tokens}))(params))
    other = {"remat-full": lambda: replace(cfg, remat=True,
                                           remat_policy="full"),
             "unrolled": lambda: replace(cfg, scan_layers=False),
             "bf16": lambda: replace(cfg, dtype=jnp.bfloat16),
             "chunk-4": lambda: replace(cfg, rule_chunk=4),
             "chunk-16": lambda: replace(cfg, rule_chunk=16)}[what]()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: mod.loss_fn(
        other, p, {"tokens": tokens})))(params)
    assert abs(float(loss) - base) < (5e-2 if what == "bf16" else 1e-5)
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def _count(mod, cfg):
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: mod.init_params(cfg, k),
                       jax.random.PRNGKey(0))))


def _lfm2_preset(lfm2):
    """24 layers, 18 conv and 6 attention, 8.34 B parameters; the cell's
    cut: layer 0 and the first period, 16 of 32, half the rows."""
    cfg = lfm2.Lfm2Config.lfm2_8b_a1b()
    assert cfg.pattern.count("conv_moe") == 16
    assert cfg.pattern.count("attn_moe") == 6
    assert cfg.pattern[:2] == ("conv_dense", "conv_dense")
    assert cfg.head_dim_ == 64
    assert abs(_count(lfm2, cfg) / 8.34e9 - 1) < 0.001
    cut = lfm2.Lfm2Config.lfm2_8b_a1b(
        num_layers=5, vocab_size=32768, num_dense_layers=1,
        attention_layers=(False, True, False, False, False),
        experts_held=(0, 16))
    assert abs(_count(lfm2, cut) / 893.7e6 - 1) < 0.001
    with pytest.raises(ValueError, match="attention_layers names"):
        lfm2.Lfm2Config.lfm2_8b_a1b(num_layers=5)


def _granite_preset(granite):
    """40 layers, 36 of them Mamba-2, 3.19 B parameters with the embedding
    tied; one period with the whole vocabulary is the cell's
    951,991,232."""
    cfg = granite.GraniteConfig.granite_4_0_h_micro(
        param_dtype=jnp.bfloat16)
    assert cfg.pattern.count("mamba") == 36 and cfg.pattern[5] == "attention"
    assert abs(_count(granite, cfg) / 3.19e9 - 1) < 0.01
    period = granite.GraniteConfig.granite_4_0_h_micro(
        num_layers=10, attention_layers=cfg.attention_layers[:10])
    assert _count(granite, period) == 951_991_232
    with pytest.raises(ValueError, match="attention_layers names"):
        granite.GraniteConfig.granite_4_0_h_micro(num_layers=10)


def _olmo_hybrid_preset(olmo_hybrid):
    """32 layers, every fourth full attention, 7.43 B parameters with an
    untied head; one period with an eighth of the vocabulary is the cell's
    928,862,196 (928.7 M by the issue's rounded addends)."""
    cfg = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(
        param_dtype=jnp.bfloat16)
    assert cfg.pattern.count("linear") == 24
    assert cfg.pattern[:4] == ("linear", "linear", "linear", "full")
    assert cfg.head_dim_ == 128 and cfg.linear_conv_dim == 11_520
    assert _count(olmo_hybrid, cfg) == 7_430_870_688
    period = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(
        num_layers=4, vocab_size=12_544)
    assert period.pattern == cfg.pattern[:4]
    assert _count(olmo_hybrid, period) == 928_862_196
    assert abs(_count(olmo_hybrid, period) / 928.7e6 - 1) < 5e-4
    with pytest.raises(ValueError, match="attention_layers names"):
        olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(
            num_layers=4, attention_layers=cfg.attention_layers)


_PRESET = {"lfm2": _lfm2_preset, "granite": _granite_preset,
           "olmo_hybrid": _olmo_hybrid_preset}


@pytest.mark.parametrize("model", list(_PRESET))
def test_preset_counts_what_the_model_card_says(model):
    """The published config's layers and parameters, and the cell's cut of
    it (Laguna's 117.6 B: ``test_models.test_layer_patterns_are_walked_by_
    runs_of_one_kind``)."""
    _PRESET[model](import_module("ray_tpu.models." + model))


@pytest.mark.parametrize("stack_setup", _BLOCKED, indirect=True)
def test_fsdp_train_step_matches_unsharded(stack_setup):
    """``param_shardings`` on an fsdp mesh: the loss and an adamw step's
    parameters agree with one device's."""
    import optax

    mod, _, cfg, params, tokens = stack_setup
    tokens = jnp.asarray(np.concatenate([tokens, tokens]))      # batch 4
    mesh = build_mesh(MeshSpec({"fsdp": 4}), devices=jax.devices()[:4])
    tx = optax.adamw(1e-3)

    def step(p, opt, mesh_):
        loss, grads = jax.value_and_grad(lambda q: mod.loss_fn(
            cfg, q, {"tokens": tokens}, mesh=mesh_))(p)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), loss

    want_p, want = jax.jit(lambda p, o: step(p, o, None))(
        params, tx.init(params))
    sharded = jax.device_put(params, mod.param_shardings(cfg, mesh))
    got_p, got = jax.jit(lambda p, o: step(p, o, mesh))(
        sharded, tx.init(sharded))
    assert abs(float(got) - float(want)) < 1e-5
    # adamw's first step is the rate times the gradient's sign, nearly: an
    # entry whose gradient is within a rounding of zero may move by a part
    # of 1e-3 more or less (one of Olmo-Hybrid's 75,264 did, by 1.7e-4)
    atol = 1e-5 if _name(mod) == "granite" else 3e-4
    for a, b in zip(jax.tree_util.tree_leaves(got_p),
                    jax.tree_util.tree_leaves(want_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=atol)


# ---- what a model has beside its table


def test_laguna_window_and_gate_are_in_the_result():
    """Leaving out the window mask, the per-head gate or the routed
    scale changes the logits: none of them is a no-op at these sizes."""
    from dataclasses import replace

    from ray_tpu.models import laguna

    cfg = laguna.LagunaConfig.tiny(attn_impl="reference")
    params = laguna.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    base = laguna.forward(cfg, params, tokens)[0]
    for other in (replace(cfg, sliding_window=None),
                  replace(cfg, sliding_window=4),
                  replace(cfg, routed_scale=1.0),
                  replace(cfg, partial_rotary_factor=1.0)):
        assert float(jnp.abs(laguna.forward(other, params, tokens)[0]
                             - base).max()) > 1e-3, other
    # a window of the whole sequence is causal attention
    np.testing.assert_allclose(
        np.asarray(laguna.forward(replace(cfg, sliding_window=32), params,
                                  tokens)[0]),
        np.asarray(laguna.forward(replace(cfg, sliding_window=None), params,
                                  tokens)[0]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model, cell, tokens, chunk", [
    ("laguna", "laguna-s-2.1-c1", 16384, 11520),
    ("lfm2", "lfm2-8b-a1b-c1", 16384, 36864)])
@pytest.mark.parametrize("held_rows, passes", [
    ("none", 0), ("chunk", 1), ("chunk+1", 2), ("all", None)])
def test_rows_passed_counts_whole_passes_a_layer(model, cell, tokens, chunk,
                                                 held_rows, passes):
    """``rows_passed`` (the ``moe_rows_passed`` counter) from hand-made
    counts at a cell's shapes, two routed layers, the second balanced
    (one pass): no held row in the first is no pass, exactly a chunk
    one, one row more two, every pair as many as hold them; held over
    passed is how full the passes were. With every expert here nothing
    is passed: the rows routed."""
    from dataclasses import replace

    mod, cfg = _cell_config(cell)
    E, pairs = cfg.num_experts, tokens * cfg.top_k
    first, count = cfg.experts_held
    held = {"none": 0, "chunk": chunk, "chunk+1": chunk + 1,
            "all": pairs}[held_rows]
    counts = np.zeros((2, E), np.int64)
    counts[0, first], counts[0, first + count] = held, pairs - held
    counts[1] = pairs // E
    want = (-(-pairs // chunk) if passes is None else passes) + 1
    assert mod.rows_passed(cfg, counts) == want * chunk
    assert int(mod.rows_held(cfg, counts)) == held + pairs * count // E
    assert mod.rows_passed(replace(cfg, experts_held=None), counts) == \
        2 * pairs


@pytest.mark.parametrize("stack_setup", _LFM2, indirect=True)
def test_update_router_bias_is_the_references_rule(stack_setup):
    lfm2, lfm2_ref, cfg, params, tokens = stack_setup
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


@pytest.mark.parametrize("stack_setup", _LFM2, indirect=True)
def test_trainable_leaves_the_bias_out_of_adamws_state(stack_setup):
    import optax

    lfm2, _, cfg, params, tokens = stack_setup
    owned = lfm2.trainable(params)
    assert all("router_bias" not in leaves
               for leaves in owned["layers"].values())
    n_all = len(jax.tree_util.tree_leaves(params))
    assert len(jax.tree_util.tree_leaves(owned)) == n_all - 2
    tx = optax.adamw(1e-3)
    opt = tx.init(owned)
    assert len(jax.tree_util.tree_leaves(opt[0].mu)) == n_all - 2
    grads = jax.grad(lambda t: lfm2.loss_fn(
        cfg, lfm2.with_trainable(params, t), {"tokens": tokens}))(owned)
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


@pytest.mark.parametrize("stack_setup", _LFM2, indirect=True)
def test_the_cells_check_sees_a_route_that_leaves_the_bias_out(
        stack_setup, monkeypatch):
    """(f) of ``benchmark/cells/train_hybrid.py``: ``route``'s own choices
    held to the selection scores recomputed from the program's logits and
    the biases. With the bias dropped inside ``ops/moe.route`` the reading
    is of the biases' size (0.1 here); the honest program reads a
    rounding."""
    from benchmark.cells import train_hybrid
    from ray_tpu.ops import moe

    lfm2, lfm2_ref, cfg, params, tokens = stack_setup
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


@pytest.mark.parametrize("stack_setup", _OLMO_HYBRID, indirect=True)
def test_attention_block_in_olmo_order_with_a_whole_vector_qk_norm(
        stack_setup):
    """A layer with ``attn_post_norm`` and no ``attn_norm``: the block's
    input is not normed, its output is, before the sum; q and k are normed
    over their whole vectors and not rotated: against
    ``olmo_hybrid_ref.attention`` on one layer's weights."""
    _, ref_mod, cfg, params, _ = stack_setup
    p = {k: v[0] for k, v in params["layers"]["full"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    sz = ref_mod._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got = llama.attention_block(cfg, x, p, None, None)
        want = jnp.stack([row + ref_mod._rms_norm(
            ref_mod.attention(row, p, sz), p["attn_post_norm"],
            cfg.rms_norm_eps) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("stack_setup", _GRANITE, indirect=True)
def test_attention_block_without_rope_at_a_stated_scale(stack_setup):
    """``cos=None`` leaves q and k unrotated, ``sm_scale`` replaces the
    head size's scale and ``resid_scale`` weighs the block's output:
    against ``granite_ref.attention`` on one layer's weights."""
    granite, granite_ref, cfg, params, _ = stack_setup
    p = {k: v[0] for k, v in params["layers"]["attention"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    sz = granite_ref._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got = llama.attention_block(cfg, x, p, None, None,
                                    sm_scale=cfg.attention_multiplier,
                                    resid_scale=cfg.residual_multiplier)
        want = jnp.stack([row + cfg.residual_multiplier
                          * granite_ref.attention(granite_ref._rms_norm(
                              row, p["attn_norm"], cfg.rms_norm_eps), p, sz)
                          for row in x])
        plain = llama.attention_block(cfg, x, p, None, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    # the scale is in the result: head_dim ** -0.5 is 1/4 here, not 1/16
    assert float(jnp.abs(plain - got).max()) > 1e-3
    with pytest.raises(ValueError, match="stated scale"):
        from dataclasses import replace
        llama.attention_block(replace(cfg, attn_impl="ring"), x, p, None,
                              None, sm_scale=0.1)


# ---- the seam: a table gives the parameters its module gave, and a table
# nobody shipped trains


def _tree_digest(tree, values):
    """sha256 over every leaf's path (in dict order), shape and dtype and,
    asked for, its bytes."""
    h = hashlib.sha256()

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
            return
        h.update(repr((path, tuple(node.shape), str(node.dtype))).encode())
        if values:
            h.update(np.asarray(node).tobytes())
    walk(tree, ())
    return h.hexdigest()[:16]


# computed on the parent of PR 42 (5b4a3bd), whose four modules each had an
# ``init_params`` of their own: a routed cell's passes depend on the
# parameters it starts from, leaf for leaf
@pytest.mark.parametrize("what, want", [
    ("laguna-tiny", "811ddefb9b7d8fb2"), ("lfm2-tiny", "06889508f837bb55"),
    ("granite-tiny", "2f7b1682a7ff3341"),
    ("olmo_hybrid-tiny", "0d0b276789c21b1b"),
    ("laguna-s-2.1-c1", "aa267dfd2b9492ea"),
    ("lfm2-8b-a1b-c1", "5b488da3ac81ab79"),
    ("granite-4.0-h-micro-c1", "f68ef20c666583ad"),
    ("olmo-hybrid-7b-c1", "1d547d419b6bc576")])
def test_init_params_is_the_tree_the_module_had_before_the_walker(what, want):
    """Names, dict order, shapes and dtypes of every cell's parameters
    (``jax.eval_shape``) and, of the ``tiny()`` presets, every byte: what
    ``Stack.init_params`` deals from the table is what the module's own
    ``init_params`` dealt, and the module has no other."""
    from ray_tpu.models import stack

    model, _, tiny = what.rpartition("-")
    if tiny == "tiny":
        mod = import_module("ray_tpu.models." + model)
        cfg = getattr(mod, _CONFIG[model]).tiny()
        got = _tree_digest(mod.init_params(cfg, jax.random.PRNGKey(0)), True)
    else:
        mod, cfg = _cell_config(what)
        got = _tree_digest(jax.eval_shape(
            lambda k: mod.init_params(cfg, k), jax.random.PRNGKey(0)), False)
    assert got == want
    for name in ("init_params", "logical_axes", "loss_terms", "loss_fn"):
        assert getattr(mod, name).__func__ is getattr(stack.Stack, name)
        assert getattr(mod, name).__self__ is mod.STACK


def test_a_table_no_model_ships_trains_and_gets_a_plan(monkeypatch):
    """Mamba-2 mixers over a routed mixture, and a short convolution over
    OLMo 2's post-norm SwiGLU: no shipped model pairs either. The table is
    built here from the parts under ``ops/`` and a config that has their
    fields; nothing in ``llama.py`` knows it. It gives parameters, a loss
    with both parts' terms, an adamw step that lowers the loss, shardings,
    and a remat plan by kind."""
    from dataclasses import dataclass

    import optax

    from ray_tpu.models import granite, stack
    from ray_tpu.ops.conv import short_conv_part
    from ray_tpu.ops.layers import swiglu_part
    from ray_tpu.ops.moe import routed_part
    from ray_tpu.ops.ssm import mamba2_part
    from ray_tpu.util import tracing

    @dataclass(frozen=True)
    class Config(granite.GraniteConfig):
        conv_taps: int = 3
        num_experts: int = 8
        top_k: int = 2
        routed_scale: float = 1.0
        moe_intermediate_size: int = 32
        router_aux_coef: float = 0.01

        @property
        def pattern(self):
            return tuple("conv_post" if conv else "mamba_moe"
                         for conv in self.attention_layers)

    model = stack.Stack(
        {"mamba_moe": (mamba2_part(counter="scan_abs_max"),
                       routed_part(balance=True)),
         "conv_post": (short_conv_part(), swiglu_part(norm="post"))},
        reports="router")
    cfg = Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=64,
                 attention_layers=(False, True, False, False), ssm_heads=8,
                 ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
                 dtype=jnp.float32, remat=False)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["mamba_moe", "conv_post"]
    assert params["layers"]["mamba_moe"]["e_gate"].shape == (3, 8, 64, 32)
    assert "mlp_post_norm" in params["layers"]["conv_post"]
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda axes, a: len(axes) == a.ndim, model.logical_axes(cfg), params,
        is_leaf=lambda x: isinstance(x, tuple))))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    logits, router = jax.jit(lambda p: model.forward(
        cfg, p, tokens[:, :-1]))(params)
    assert logits.shape == (2, 32, 256)
    assert router["counts"].shape == (3, 8)           # the routed layers'
    tx = optax.adamw(1e-2)

    @jax.jit
    def step(p, opt):
        (loss, terms), grads = jax.value_and_grad(
            lambda q: model.loss_terms(cfg, q, {"tokens": tokens}),
            has_aux=True)(p)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), opt, loss, terms

    opt, losses = tx.init(params), []
    for _ in range(3):
        params, opt, loss, terms = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1
    assert set(terms) == {"cross_entropy", "load_balance", "expert_counts",
                          "scan_abs_max"}
    assert float(terms["scan_abs_max"]) > 0
    # the plan: a level a kind, from what the kind's two parts say they
    # keep (the routed experts' and the SwiGLU's two products: the MLP
    # rung; no rung names anything in a scan or a convolution)
    from dataclasses import replace
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: 2 ** 30)
    jax.jit(lambda p: model.loss_fn(replace(cfg, remat=True), p,
                                    {"tokens": tokens}))(params)
    plan = [e["args"] for e in tracing.chrome_events()
            if e["name"] == "rtpu.train.remat_plan"][-1]
    assert plan["level"] == {"mamba_moe": "level3", "conv_post": "level3"}
    assert plan["layers"] == {"mamba_moe": 3, "conv_post": 1}
    assert all(v > 0 for v in plan["saved_bytes_per_layer"].values())
    mesh = build_mesh(MeshSpec({"fsdp": 4}), devices=jax.devices()[:4])
    assert jax.tree_util.tree_structure(
        model.param_shardings(cfg, mesh)) == \
        jax.tree_util.tree_structure(params)


# ---- the options Qwen3-Next's table asks for, and what they leave alone


def test_attention_block_with_an_elementwise_gate_in_wq():
    """``attention_part(gate="elementwise")``: ``wq`` gives a head its
    query and then its gate; the block is the ungated block on the query
    columns with ``sigmoid(gate)`` on each head's output before ``wo``."""
    from ray_tpu.ops.layers import Ctx

    cfg = llama.LlamaConfig.tiny(attn_impl="reference", head_dim=16)
    part = llama.attention_part(gate="elementwise", rope=None)
    plain = llama.attention_part(rope=None)
    leaves = part.leaves(cfg)
    assert leaves["wq"].shape == (64, 2 * 64) and "wg" not in leaves
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves) + 1)
    p = {n: (jnp.ones(l.shape) if l.start == "ones" else
             jax.random.normal(k, l.shape) / 8)
         for k, (n, l) in zip(keys, leaves.items())}
    x = jax.random.normal(keys[-1], (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        got, _ = part.body(cfg, x, p, Ctx(None, {}))
        by_head = p["wq"].reshape(64, 4, 2, 16)
        q_only = {**p, "wq": by_head[:, :, 0].reshape(64, 64)}
        # wo = identity: the ungated heads' outputs themselves
        heads, _ = plain.body(cfg, x, {**q_only, "wo": jnp.eye(64)},
                              Ctx(None, {}))
        u = llama.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(u @ by_head[:, :, 1].reshape(64, 64))
        want = x + ((heads - x) * gate) @ p["wo"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(gate - 0.5).max()) > 0.1
    assert part.keeps(cfg, {n: l.shape for n, l in leaves.items()}, 12,
                      None)["rungs"][0] == 12 * (64 * 4 + 4 * 4)


def test_routed_part_with_a_gated_shared_expert():
    """``routed_part(shared="gated")`` is ``shared=True`` with the shared
    expert's output times ``sigmoid(u . s_sigmoid)``, a number a token."""
    from ray_tpu.models.laguna import LagunaConfig
    from ray_tpu.ops.layers import Ctx, rms_norm, swiglu
    from ray_tpu.ops.moe import routed_part

    cfg = LagunaConfig.tiny()
    gated, plain = routed_part(shared="gated"), routed_part(shared=True)
    leaves = gated.leaves(cfg)
    assert list(leaves)[-1] == "s_sigmoid" and leaves["s_sigmoid"].shape == (
        64,)
    assert list(leaves)[:-1] == list(plain.leaves(cfg))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves) + 1)
    p = {n: (jnp.ones(l.shape) if l.start == "ones" else
             jax.random.normal(k, l.shape) / 8)
         for k, (n, l) in zip(keys, leaves.items())}
    x = jax.random.normal(keys[-1], (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        got, said = gated.body(cfg, x, p, Ctx(None, {}))
        base, _ = plain.body(cfg, x, p, Ctx(None, {}))
        u = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        shared = swiglu(u, p["s_gate"], p["s_up"], p["s_down"])
        gate = jax.nn.sigmoid(u @ p["s_sigmoid"])[..., None]
    np.testing.assert_allclose(got, base - shared + gate * shared,
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(gate - 0.5).max()) > 0.1
    assert said["router"]["counts"].shape == (16,)


# (model, the held share) -> what the parent commit's forward and loss gave
# on these seeds, float32 on the CPU: the loss, three logits and the sum of
# all logits' sizes. On the machine this was written on, the logits and
# every leaf's gradient were bit-equal to the parent's (CHANGES.md, PR 50).
_PARENTS = {
    "olmo_hybrid": (6.14553165435791,
                    (-0.5216737985610962, 0.50602787733078,
                     -0.5190625190734863), 12633.048828125),
    "laguna": (5.79429292678833,
               (-1.1803277730941772, -0.7559819221496582,
                -1.6450860500335693), 12889.58984375)}


@pytest.mark.parametrize("model", sorted(_PARENTS))
def test_tables_that_take_no_new_option_give_what_they_gave(model):
    """Olmo-Hybrid's and Laguna's tables name none of the options
    Qwen3-Next's asks of ``gated_delta_part``, ``attention_part``,
    ``routed_part`` and ``Stack``: every option defaults to what the code
    did before."""
    mod = import_module("ray_tpu.models." + model)
    how = {"experts_held": (4, 8)} if model == "laguna" else {}
    cfg = getattr(mod, _CONFIG[model]).tiny(attn_impl="reference", **how)
    assert not cfg.zero_centred_norm
    params = mod.init_params(cfg, jax.random.PRNGKey(7))
    assert float(params["final_norm"].min()) == 1.0
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 33))
    logits, _ = jax.jit(lambda p, t: mod.forward(cfg, p, t))(
        params, tokens[:, :-1])
    loss = jax.jit(lambda p: mod.loss_fn(cfg, p, {"tokens": jnp.asarray(
        tokens)}))(params)
    want_loss, want_logits, want_sum = _PARENTS[model]
    np.testing.assert_allclose(float(loss), want_loss, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(logits)[1, [0, 7, 31], 5],
                               want_logits, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(np.abs(np.asarray(logits)).sum()),
                               want_sum, rtol=1e-6)


# ---- kinds of one part, and a prediction module beside the head (PR 52)


def test_a_table_of_one_part_kinds_trains_and_moves_its_routers_biases():
    """A layer may be one sum: a table whose kinds are one part each (a
    scan, a dense SwiGLU, a routed mixture with a bias) beside one of two.
    It gives leaves and axes by part, a forward, a remat plan by kind, an
    adamw step that lowers the loss, and ``update_router_bias`` finds the
    routed layers whichever slot their part stands in."""
    from dataclasses import dataclass

    import optax

    from ray_tpu.models import granite, stack
    from ray_tpu.ops.layers import swiglu_part
    from ray_tpu.ops.moe import routed_part
    from ray_tpu.ops.ssm import mamba2_part

    @dataclass(frozen=True)
    class Config(granite.GraniteConfig):
        layer_kinds: tuple = ("scan", "mix", "scan", "routed", "both")
        num_experts: int = 8
        top_k: int = 2
        routed_scale: float = 1.0
        moe_intermediate_size: int = 32
        bias_update_rate: float = 0.01

        @property
        def pattern(self):
            return self.layer_kinds

    routed = routed_part(score="sigmoid", bias=True)
    model = stack.Stack(
        {"scan": (mamba2_part(),), "mix": (swiglu_part(),),
         "routed": (routed,), "both": (mamba2_part(), routed)},
        reports="router")
    cfg = Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=5, num_heads=4, num_kv_heads=2, max_seq_len=64,
                 attention_layers=(False,) * 5, ssm_heads=8, ssm_head_dim=16,
                 ssm_state=16, ssm_chunk=8, dtype=jnp.float32, remat=False)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["scan", "mix", "routed", "both"]
    assert set(params["layers"]["scan"]) == set(mamba2_part().leaves(cfg))
    assert set(params["layers"]["mix"]) == set(swiglu_part().leaves(cfg))
    assert "mtp" not in params
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda axes, a: len(axes) == a.ndim, model.logical_axes(cfg), params,
        is_leaf=lambda x: isinstance(x, tuple))))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    logits, router = jax.jit(lambda p: model.forward(
        cfg, p, tokens[:, :-1]))(params)
    assert logits.shape == (2, 32, 256)
    assert router["counts"].shape == (2, 8)     # "routed", then "both"
    described = llama.describe_stack(cfg, model.kinds, params["layers"], 64,
                                     pattern=cfg.pattern)
    assert set(described["kinds"]) == {"scan", "mix", "routed", "both"}
    both, scan, part = (described["kinds"][k]["working_bytes"]
                        for k in ("both", "scan", "routed"))
    assert both - scan == part - 64 * 4 * 4 * 64    # a kind's parts add up
    tx = optax.adamw(1e-2)

    @jax.jit
    def step(p, opt):
        trained = stack.trainable(p)
        (loss, terms), grads = jax.value_and_grad(
            lambda t: model.loss_terms(cfg, stack.with_trainable(p, t),
                                       {"tokens": tokens}),
            has_aux=True)(trained)
        updates, opt = tx.update(grads, opt, trained)
        p = stack.with_trainable(p, optax.apply_updates(trained, updates))
        return (model.update_router_bias(cfg, p, terms["expert_counts"]),
                opt, loss, terms["expert_counts"])

    opt = tx.init(stack.trainable(params))
    first = None
    for _ in range(4):
        before = params
        params, opt, loss, counts = step(params, opt)
        first = float(loss) if first is None else first
    assert float(loss) < first
    for row, kind in enumerate(("routed", "both")):
        moved = (params["layers"][kind]["router_bias"][0]
                 - before["layers"][kind]["router_bias"][0])
        c = np.asarray(counts[row], np.float32)
        np.testing.assert_allclose(moved, 0.01 * np.sign(c.mean() - c),
                                   atol=1e-7)


def test_a_prediction_module_is_named_by_the_config_and_reports_last():
    """``Stack(mtp=<field>)``: the module's kinds come from the config; its
    leaves lie under ``params["mtp"]``, dealt from keys of their own (the
    stack's leaves are what they are without a module); a row carries ``seq
    + 2`` ids and the routed layers' counts gain the module's row."""
    from ray_tpu.models import nemotron_h as mod

    cfg = mod.Nemotron_hConfig.tiny()
    bare = mod.Nemotron_hConfig.tiny(mtp_layer_pattern="")
    params = mod.init_params(cfg, jax.random.PRNGKey(0))
    without = mod.init_params(bare, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()),
        {k: v for k, v in params.items() if k != "mtp"}, without))
    assert list(params["mtp"]["layers"]) == ["attention", "moe"]
    assert params["mtp"]["layers"]["moe"]["router"].shape == (1, 64, 16)
    assert not bool((params["mtp"]["layers"]["moe"]["router"][0]
                     == params["layers"]["moe"]["router"][0]).all())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 34), 0, 256)
    nll, more, said = jax.jit(lambda p: mod.token_nlls(cfg, p, tokens))(
        params)
    assert nll.shape == more.shape == (2, 32)
    assert said["router"]["counts"].shape == (3, 16)
    main, _ = jax.jit(lambda p: mod.token_nll(bare, p, tokens[:, :33]))(
        without)
    np.testing.assert_allclose(nll, main, atol=1e-6)
    _, terms = jax.jit(lambda p: mod.loss_terms(
        cfg, p, {"tokens": tokens, "mask": jnp.ones_like(tokens)}))(params)
    assert abs(float(terms["cross_entropy"]) - float(nll.mean())) < 1e-5
    assert abs(float(terms["mtp_cross_entropy"]) - float(more.mean())) < 1e-5
