"""dots3-note-prev's language model (``models/dots3.py``) at ``tiny()`` on
seeded weights, in float32: the program against its plain reference
(logits, both loss terms, every leaf's gradient, the chosen keys), the
exact choice against ``lax.top_k``, the window ``flash_kv`` kernels at
unequal widths, which term trains which leaf, the share test (head shares
and expert shares add up to the uncut layer), the remat plan's three kinds
and the bias no optimizer owns."""

from dataclasses import replace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.references import dots3_ref as ref  # noqa: E402
from ray_tpu.models import dots3, llama  # noqa: E402
from ray_tpu.models.dots3 import Dots3Config  # noqa: E402
from ray_tpu.ops import dsa, mla  # noqa: E402
from ray_tpu.ops.attention import (attention_reference,  # noqa: E402
                                   flash_attention, with_shared_key)
from ray_tpu.ops.layers import Ctx  # noqa: E402

_MOVED = ("attn_norm", "q_a_norm", "kv_a_norm", "mlp_norm", "wi_k_norm",
          "wi_k_bias")
_SHARES = [pytest.param((None, None, None), id="whole"),
           pytest.param((2, 1, (4, 8)), id="half-the-heads-experts-4..11")]


@pytest.fixture(scope="module")
def setup(request):
    """(config, parameters, tokens [2, 49]) of ``tiny()`` in float32: four
    layers F F S S, layer 0 dense, 4 full heads with keys of 16 + 8, 2
    window heads with keys of 24 + 8, values of 12, an index of 4 heads of
    16 choosing 8 of 48 positions, a window of 5, 16 experts, 3 a token.
    The norms, the index key's bias and the routers' biases are moved off
    their starts: one applied twice or dropped would go unseen."""
    heads, swa_heads, experts = request.param
    cfg = Dots3Config.tiny(
        attn_impl="reference", experts_held=experts,
        **({"num_heads": heads, "heads_of": 4, "swa_num_heads": swa_heads,
            "swa_heads_of": 2} if heads else {}))
    params = dots3.init_params(cfg, jax.random.PRNGKey(0))
    for n, kind in enumerate(params["layers"]):
        for i, name in enumerate(_MOVED + ("router_bias",)):
            if name in params["layers"][kind]:
                w = params["layers"][kind][name]
                params["layers"][kind][name] = w + (
                    0.05 if name == "router_bias" else 0.3
                ) * jax.random.normal(jax.random.PRNGKey(10 * n + i), w.shape)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 49))
    return cfg, params, tokens


@pytest.fixture(scope="module")
def both(setup):
    """The program's forward with everything kept, and the reference's
    logits on the program's own choices."""
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        logits, said = jax.jit(lambda p, t: dots3.forward_reports(
            cfg, p, t))(params, tokens[:, :-1])
    want = ref.logits(cfg, params, tokens[:, :-1],
                      forced_topk=said["router"]["chosen"],
                      forced_keys=said["dsa"]["choice"])
    return logits, said, want


@pytest.mark.parametrize("setup", _SHARES, indirect=True)
def test_logits_match_the_reference(setup, both):
    cfg, params, tokens = setup
    assert cfg.pattern == ("full_dense", "full_moe", "sliding_moe",
                           "sliding_moe")
    full, win = params["layers"]["full_moe"], params["layers"]["sliding_moe"]
    assert full["wq_b"].shape == (1, 32, cfg.num_heads * 24)
    assert win["wq_b"].shape == (2, 32, cfg.swa_num_heads * 32)
    assert win["wkv_a"].shape == (2, 64, 32 + 8)
    assert full["wi_q"].shape == (1, 32, 4 * 16)      # the index is whole
    assert "wi_q" not in win and "wg" in win
    assert full["e_gate"].shape[1] == (8 if cfg.experts_held else 16)
    logits, said, want = both
    assert said["dsa"]["choice"].shape == (2, 2, 48, 6)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("setup", _SHARES, indirect=True)
def test_both_loss_terms_match_the_reference(setup, both):
    cfg, params, tokens = setup
    _, said, _ = both
    with jax.default_matmul_precision("highest"):
        loss, terms = dots3.loss_terms(cfg, params, {"tokens": tokens})
    ce, l_i = ref.loss_terms(cfg, params, tokens,
                             forced_topk=said["router"]["chosen"],
                             forced_keys=said["dsa"]["choice"])
    np.testing.assert_allclose(terms["cross_entropy"], ce, rtol=1e-5)
    np.testing.assert_allclose(terms["dsa_index_loss"], l_i, rtol=1e-4)
    np.testing.assert_allclose(loss, ce + l_i, rtol=1e-5)
    assert float(l_i) > 0.05
    # 48 positions, 8 keys each past the first 8: (36 + 40 x 8) / 1176
    np.testing.assert_allclose(terms["dsa_pairs_chosen_share"],
                               (36 + 40 * 8) / (48 * 49 / 2), rtol=1e-6)
    assert terms["expert_counts"].shape == (3, 16)


@pytest.fixture(scope="module")
def gradients(setup, both):
    cfg, params, tokens = setup
    _, said, _ = both
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: dots3.loss_fn(
            cfg, p, {"tokens": tokens})))(params)
    want = jax.grad(lambda p: sum(ref.loss_terms(
        cfg, p, tokens, forced_topk=said["router"]["chosen"],
        forced_keys=said["dsa"]["choice"])))(params)
    return got, want


@pytest.mark.parametrize("group", ["full_dense", "full_moe", "sliding_moe",
                                   "top"])
@pytest.mark.parametrize("setup", _SHARES, indirect=True)
def test_every_leafs_gradient_matches_the_reference(setup, gradients, group):
    got, want = gradients
    if group == "top":
        got, want = ({k: v for k, v in g.items() if k != "layers"}
                     for g in (got, want))
    else:
        got, want = got["layers"][group], want["layers"][group]
    assert set(got) == set(want)
    for name in got:
        if name == "router_bias":
            assert not np.asarray(got[name]).any()
            continue
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("setup", _SHARES[:1], indirect=True)
def test_the_choice_is_the_references_plain_top_k(setup, both):
    """``S_t`` in both full layers: the reference's ``lax.top_k`` over the
    causal scores, and every causal key where ``t < topk``."""
    cfg, params, tokens = setup
    _, said, _ = both
    got = np.asarray(dsa.unpack_choice(said["dsa"]["choice"], 48))
    want = np.asarray(ref.chosen_keys(cfg, params, tokens[:, :-1]))
    assert got.shape == want.shape == (2, 2, 48, 48)
    assert (got == want).all()
    causal = np.tril(np.ones((48, 48), bool))
    assert (got[..., :8, :] == causal[:8]).all()
    assert (got.sum(-1)[..., 8:] == 8).all() and not (got & ~causal).any()


@pytest.mark.parametrize("first,ties", [(0, False), (32, False), (0, True),
                                        (16, True), (32, True)])
def test_choose_is_exact_with_ties_to_the_lower_position(first, ties):
    scores = jax.random.normal(jax.random.PRNGKey(first + ties), (16, 48))
    if ties:        # a few distinct values, zeros of both signs among them
        scores = jnp.round(scores * 1.5) / 1.5 * jnp.where(
            jnp.arange(48) % 5 == 0, -0.0, 1.0)
    got = np.asarray(dsa.choose(scores, first, 8))
    want = np.asarray(ref.plain_top_k(scores, first, 8))
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(first + np.arange(16) + 1, 8)).all()


def test_choose_takes_every_key_a_query_sees_when_asked_for_more():
    scores = jax.random.normal(jax.random.PRNGKey(0), (8, 24))
    got = np.asarray(dsa.choose(scores, 16, 64))
    assert (got == np.tril(np.ones((24, 24), bool))[16:]).all()


@pytest.mark.parametrize("window", [5, 100, 129])
def test_window_flash_kernels_at_unequal_widths(window):
    """Forward, dQ, dK, dV and the shared key's gradient of the
    ``flash_kv_*`` kernels under a band, in ``interpret`` mode, at keys of
    48 + 16 shared and values of 24, against ``attention_reference``."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(keys[0], (1, 256, 2, 64))
    k = jax.random.normal(keys[1], (1, 256, 2, 48))
    v = jax.random.normal(keys[2], (1, 256, 2, 24))
    kx = jax.random.normal(keys[3], (1, 256, 16))
    w = jax.random.normal(keys[4], (1, 256, 2, 24))

    def kernel(q, k, v, kx):
        return (flash_attention(q, k, v, use_pallas=True, interpret=True,
                                block_q=64, block_k=64, k_shared=kx,
                                window=window, sm_scale=0.0625) * w).sum()

    def plain(q, k, v, kx):
        return (attention_reference(q, with_shared_key(k, kx), v,
                                    sm_scale=0.0625, window=window)
                * w).sum()

    got = jax.value_and_grad(kernel, (0, 1, 2, 3))(q, k, v, kx)
    want = jax.value_and_grad(plain, (0, 1, 2, 3))(q, k, v, kx)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("term", ["cross_entropy", "dsa_index_loss"])
@pytest.mark.parametrize("setup", _SHARES[:1], indirect=True)
def test_each_term_trains_its_own_leaves_alone(setup, term):
    """The cross entropy gives the index's leaves exactly zero; ``L_I``
    gives every leaf outside the index exactly zero."""
    cfg, params, tokens = setup
    grads = jax.grad(lambda p: dots3.loss_terms(
        cfg, p, {"tokens": tokens})[1][term])(params)
    flat = {f"{kind}.{name}": g for kind, leaves in grads["layers"].items()
            for name, g in leaves.items()}
    flat.update({k: v for k, v in grads.items() if k != "layers"})
    index = {k for k in flat if k.split(".")[-1] in dots3.INDEX_LEAVES}
    assert len(index) == 10
    zero = index if term == "cross_entropy" else set(flat) - index
    for name, g in flat.items():
        assert bool(np.asarray(g).any()) == (
            name not in zero and not name.endswith("router_bias")), name


def _attention(cfg, kind, p, x, tokens):
    part = dots3.LAYER_KINDS[kind][0]
    ctx = Ctx(None, {part.once: part.once(cfg, tokens)})
    with jax.default_matmul_precision("highest"):
        return part.body(cfg, x, p, ctx)[0] - x


@pytest.mark.parametrize("kind,heads,prefix", [("full_moe", 4, ""),
                                               ("sliding_moe", 2, "swa_")])
@pytest.mark.parametrize("setup", _SHARES[:1], indirect=True)
def test_head_shares_add_up_to_the_whole_layers_attention(setup, kind, heads,
                                                          prefix):
    """One head a chip, the index whole on every chip (it is not divided,
    so every chip chooses the same keys): what the blocks add to the
    residual stream sums to the whole layer's, program and reference."""
    cfg, params, tokens = setup
    p = {k: v[0] for k, v in params["layers"][kind].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    sz = mla.sizes(cfg, prefix)
    dn, dr, dv = sz.d_n, sz.d_r, sz.d_v
    whole = _attention(cfg, kind, p, x, tokens[:1, :-1])
    np.testing.assert_allclose(
        whole[0], ref.attention_layer(cfg, p, x[0], kind)[0], rtol=1e-4,
        atol=1e-5)
    parts, ref_parts = [], []
    for head in range(heads):
        mine = {**p,
                "wq_b": p["wq_b"][:, head * (dn + dr):(head + 1) * (dn + dr)],
                "wkv_b": p["wkv_b"][:, head * (dn + dv):
                                    (head + 1) * (dn + dv)],
                "wg": p["wg"][:, head:head + 1],
                "wo": p["wo"][head * dv:(head + 1) * dv]}
        held = replace(cfg, **{prefix + "num_heads": 1,
                               prefix + "heads_of": heads})
        want = {n: leaf.shape for n, leaf in dots3.LAYER_KINDS[kind][
            0].leaves(held).items()}
        assert want == {n: mine[n].shape for n in want}
        parts.append(_attention(held, kind, mine, x, tokens[:1, :-1]))
        ref_parts.append(ref.attention_layer(held, mine, x[0], kind)[0])
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sum(ref_parts), whole[0], rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 1e-3


def test_expert_shares_add_up_to_the_uncut_layer():
    """Sixteen chips with one of sixteen experts each: their routed parts,
    plus the shared expert counted once, are the uncut reference's layer."""
    from ray_tpu.ops.moe import routed_experts

    cfg = Dots3Config.tiny()
    params = dots3.init_params(cfg, jax.random.PRNGKey(2))
    p = {k: v[0] for k, v in params["layers"]["full_moe"].items()}
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    u = jax.random.normal(jax.random.PRNGKey(6), (48, 64))
    want = ref.routed_layer(cfg, p, u)
    shared = want - ref.routed_layer(cfg, p, u, shared=False)
    total = ref_total = shared
    for e in range(16):
        mine = {**p, **{n: p[n][e:e + 1]
                        for n in ("e_gate", "e_up", "e_down")}}
        held = replace(cfg, experts_held=(e, 1))
        with jax.default_matmul_precision("highest"):
            out, _, counts = routed_experts(
                u, mine["router"], mine["e_gate"], mine["e_up"],
                mine["e_down"], cfg.top_k, renormalize=True, held=(e, 1),
                scale=cfg.routed_scale, score="sigmoid",
                select_bias=mine["router_bias"])
        assert int(counts.sum()) == 48 * 3
        total = total + out
        ref_total = ref_total + ref.routed_layer(held, mine, u, shared=False)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_total, want, rtol=1e-4, atol=1e-5)


def test_the_plan_knows_the_three_kinds():
    cfg = Dots3Config.tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda k: dots3.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    stack = llama.describe_stack(cfg, dots3.LAYER_KINDS, params["layers"],
                                 48, pattern=cfg.pattern, head_tokens=48)
    assert stack["runs"] == (("full_dense", 1), ("full_moe", 1),
                             ("sliding_moe", 2))
    kinds = stack["kinds"]
    assert set(kinds) == {"full_dense", "full_moe", "sliding_moe"}
    # an index layer keeps its two latents alone on the first rung (the
    # walk has no flash output to keep); a window layer its flash output
    # and log-sum-exp besides
    assert kinds["full_moe"]["rungs"][0] == 48 * (32 + 16 + 8) * 2
    assert kinds["sliding_moe"]["rungs"][0] == 48 * (
        2 * 12 * 2 + 2 * 4 + (32 + 32 + 8) * 2)
    assert kinds["full_dense"]["working_bytes"] > \
        kinds["sliding_moe"]["working_bytes"]
    plan = llama.remat_plan(cfg, stack, 48, 10 ** 6, 10 ** 9, False)
    assert set(plan["level"]) == set(kinds)
    with pytest.raises(ValueError, match="does not know the layer kind"):
        llama.describe_stack(cfg, dots3.LAYER_KINDS,
                             {"mamba": params["layers"]["full_moe"]}, 48,
                             pattern=("mamba",))


def test_the_bias_moves_by_the_rule_and_no_optimizer_owns_it():
    import optax

    cfg = Dots3Config.tiny()
    params = dots3.init_params(cfg, jax.random.PRNGKey(0))
    owned = dots3.trainable(params)
    assert all("router_bias" not in leaves
               for leaves in owned["layers"].values())
    assert "router_bias" in params["layers"]["sliding_moe"]
    tokens = np.random.default_rng(2).integers(0, 256, (2, 49))
    tx = optax.adamw(1e-2)
    (_, aux), grads = jax.value_and_grad(
        lambda t: dots3.loss_terms(cfg, dots3.with_trainable(params, t),
                                   {"tokens": tokens}), has_aux=True)(owned)
    updates, _ = tx.update(grads, tx.init(owned), owned)
    stepped = dots3.with_trainable(params, optax.apply_updates(owned,
                                                               updates))
    for kind in ("full_moe", "sliding_moe"):
        assert not np.asarray(stepped["layers"][kind]["router_bias"]).any()
        assert float(jnp.abs(stepped["layers"][kind]["router"]
                             - params["layers"][kind]["router"]).max()) > 0
    counts = aux["expert_counts"]
    moved = dots3.update_router_bias(cfg, stepped, counts)
    got = ref.router_biases(cfg, moved)
    want = ref.updated_bias(cfg, np.zeros((3, 16), np.float32),
                            np.asarray(counts))
    np.testing.assert_array_equal(got, want)
    assert float(dots3.router_bias_abs_max(moved)) == pytest.approx(0.001)


def test_preset_is_the_published_stack():
    """46 layers, full at 0, 1, 5, 9, ...: 13 full and 33 window, the
    first dense; 279.6 B parameters in the language model."""
    cfg = Dots3Config.dots3_note_prev()
    assert cfg.pattern.count("sliding_moe") == 33
    assert cfg.pattern[:6] == ("full_dense", "full_moe", "sliding_moe",
                               "sliding_moe", "sliding_moe", "full_moe")
    assert cfg.pattern[-1] == "full_moe"
    shapes = jax.eval_shape(lambda k: dots3.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    total = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert abs(total / 279.6e9 - 1) < 0.005


def test_a_mesh_walks_each_chips_own_rows():
    """Under a data-parallel mesh the index, the choice and the attention
    over it run each chip's rows of the batch: the loss terms are the
    unsharded program's."""
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = Dots3Config.tiny(attn_impl="reference")
    params = dots3.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(4).integers(0, 256, (4, 49))
    mesh = build_mesh(MeshSpec({"dp": 4}), devices=jax.devices()[:4])
    want = dots3.loss_terms(cfg, params, {"tokens": tokens})[1]
    got = jax.jit(lambda p, t: dots3.loss_terms(
        cfg, p, {"tokens": t}, mesh=mesh)[1])(params, tokens)
    for name in ("cross_entropy", "dsa_index_loss",
                 "dsa_pairs_chosen_share"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5)


def test_the_cells_flops_and_bytes_against_hand_counts():
    """``benchmark/lib/sparse_flops.py`` on the cell's configuration file,
    against counts written out by hand, and the parameters the program
    holds against the same."""
    import json
    import os

    from benchmark.lib import sparse_flops as sf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots3-note-prev-c1.json")) as f:
        m = json.load(f)
    assert (sf.full_layers(m), sf.window_layers(m), sf.routed_layers(m)) \
        == (2, 3, 4)
    full = (5120 * 1024 + 1024 * 16 * 192 + 5120 * 576 + 512 * 16 * 256
            + 16 * 128 * 5120 + 5120 * 16)
    win = (5120 * 1024 + 1024 * 8 * 256 + 5120 * 1088 + 1024 * 8 * 320
           + 8 * 128 * 5120 + 5120 * 8)
    index = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    assert sf.mla_proj_params(m) == full == 24_002_560
    assert sf.mla_proj_params(m, "swa_") == win == 20_815_872
    assert sf.index_proj_params(m) == index == 9_371_648
    T = 16_384
    assert sf.proj_flops_per_step(m, T) == T * (
        6 * (2 * full + 3 * win) + 4 * 2 * index)
    assert sf.causal_pairs(T) == T * (T + 1) / 2 == 134_225_920
    chosen = 2048 * 2049 / 2 + (T - 2048) * 2048
    band = 513 * 514 / 2 + (T - 513) * 513
    assert sf.kept_pairs(T, 2048) == chosen == 31_458_304
    assert sf.kept_pairs(T, 513) == band
    assert sf.kept_pairs(48, 2048) == 48 * 49 / 2
    # the index: 16,384 a causal pair forward, twice that a chosen pair back
    assert sf.index_flops_per_step(m, 1, T) == 2 * 2 * 64 * 128 * (
        T * (T + 1) / 2 + 2 * chosen)
    assert sf.sparse_flash_flops_per_step(m, 1, T) == \
        2 * 16 * (8 * 192 + 6 * 128) * chosen
    assert sf.window_flash_flops_per_step(m, 1, T) == \
        3 * 8 * (8 * 256 + 6 * 128) * band
    assert sf.flash_bytes_per_step(m, T) == 2 * T * 2 * (
        2 * (16 * 192 + 16 * 128 + 64 + 16 * 128) + 2 * 16 * 128)
    assert sf.flash_bytes_per_step(m, T, "swa_") == 3 * T * 2 * (
        2 * (8 * 256 + 8 * 192 + 64 + 8 * 128) + 2 * 8 * 128)
    assert sf.mlp_params(m) == 3 * 5120 * (13_824 + 4 * 1536)
    assert sf.head_params(m) == 5120 * 19_008
    rows = 4 * T * 8 * 8 / 256
    whole = sf.train_flops_per_step(m, 1, T, rows)
    by_hand = (
        T * (6 * (2 * full + 3 * win) + 8 * index)
        + 6 * T * (3 * 5120 * (13_824 + 4 * 1536) + 4 * 5120 * 256
                   + 5120 * 19_008)
        + 6 * 3 * 5120 * 1536 * rows
        + 2 * 2 * 64 * 128 * (T * (T + 1) / 2 + 2 * chosen)
        + 3 * (2 * 16 * 640 * chosen + 3 * 8 * 768 * band))
    assert whole == by_hand
    # the index is a fifth of what the step needs at 16,384 positions
    assert 0.1 < sf.index_flops_per_step(m, 1, T) / whole < 0.25
    # and the program holds what the file says it does
    from benchmark.cells.train_hybrid import load_model

    model, _, cfg = load_model(m["model_config"])
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    held = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    norms = 5 * 2 * 5120 + 5120 + 5 * 1024 + 2 * 512 + 3 * 1024 + 2 * 256
    assert held == (2 * (full + index) + 3 * win + 3 * 5120 * 13_824
                    + 4 * (5120 * 256 + 256 + 3 * 5120 * 1536 * 9)
                    + 2 * 5120 * 19_008 + norms)
    assert str(held) in m["deployment"].replace(",", "")


def test_the_steps_scopes_span_and_counters():
    """The compiled train step carries the index's, the gate's and the
    window's scopes; tracing the op writes ``rtpu.dsa.shapes``; the
    counters' names are ``STEP_COUNTERS``'."""
    import re

    import optax

    from benchmark.cells import train_sparse
    from ray_tpu.train.session import STEP_COUNTERS
    from ray_tpu.util import tracing

    cfg = Dots3Config.tiny(vocab_size=128, attn_impl="reference", remat=True,
                           experts_held=(4, 4))
    params = jax.eval_shape(lambda k: dots3.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, dots3.trainable(params))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 49), jnp.int32)}
    n0 = len(tracing.chrome_events())
    lowered = jax.jit(train_sparse.make_step(dots3, cfg, tx),
                      donate_argnums=(0, 1)).lower(params, opt, batch)
    spans = [e["args"] for e in tracing.chrome_events()[n0:]
             if e["name"] == "rtpu.dsa.shapes"]
    assert spans and all(
        (a["index_heads"], a["index_head_dim"], a["topk"], a["positions"],
         a["pairs_scored"], a["pairs_chosen"])
        == (4, 16, 8, 48, 2 * 48 * 49 // 2, 2 * (36 + 40 * 8))
        for a in spans)
    # on the CPU the scores are XLA's form (tests/test_dsa_kernels.py has
    # the kernels')
    assert all((a["block"], a["tiers"], a["scores_form"], a["scores_tile"])
               == (16, 3, "xla", None) for a in spans)
    assert lowered.out_info[3].shape == (3, 16)
    assert set(lowered.out_info[4]) == {
        "cross_entropy", "dsa_index_loss", "dsa_pairs_chosen_share",
        "moe_router_bias_abs_max"}
    assert {"dsa_index_loss", "dsa_pairs_chosen_share",
            "moe_router_bias_abs_max"} <= set(STEP_COUNTERS)
    text = lowered.compile().as_text()
    for scope in ("embed", "mla_q", "mla_kv", "mla_rope", "mla_out",
                  "attn_gate", "dsa_proj", "dsa_scores", "dsa_select",
                  "flash_sparse", "dsa_loss", "flash_window", "mlp",
                  "moe_route", "moe_shared", "moe_bias_update", "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope
