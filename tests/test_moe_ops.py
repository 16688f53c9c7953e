"""The routed experts (``ops/moe.py``: ``route``, ``routed_experts``,
``routed_part``) with every expert here: against a loop over experts, under
a checkpoint, with sigmoid scores and a bias, in a latent with two-matrix
experts, on the TPU path in ``interpret`` mode, and shares that add up to
the uncut layer. A chip's held share and its passes:
``test_held_ops.py``."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.layers import swiglu  # noqa: E402


def _experts_by_loop(x, router_w, e_gate, e_up, e_down, top_k,
                     renormalize=False, held=None, scale=1.0):
    """Every expert (``held=(first, count)``: those alone) over every
    token, a mask keeping the chosen ones."""
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    top_w = top_w * scale
    out = jnp.zeros_like(x)
    first, count = held or (0, router_w.shape[1])
    for e in range(first, first + count):
        gate = jnp.where(top_e == e, top_w, 0.0).sum(-1)
        out = out + gate[:, None] * swiglu(x, e_gate[e], e_up[e], e_down[e])
    return out


def _held_share(held, x, router_w, e_gate, e_up, e_down, top_k, **kw):
    """``routed_experts`` handed the held experts' weights alone."""
    from ray_tpu.ops.moe import routed_experts

    if held is not None:
        e_gate, e_up, e_down = (jax.lax.dynamic_slice_in_dim(w, *held)
                                for w in (e_gate, e_up, e_down))
    return routed_experts(x, router_w, e_gate, e_up, e_down, top_k,
                          held=held, **kw)


def _routed_inputs(skewed, toward=(8, 16)):
    n, h, f, E = 96, 32, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (n, h))
    router_w = jax.random.normal(ks[1], (h, E))
    if skewed:      # a constant feature the router sends to experts 8..15
        x = x.at[:, 0].set(5.0)
        router_w = (router_w * 0.01).at[0, slice(*toward)].add(10.0)
    return (x, router_w, jax.random.normal(ks[2], (E, h, f)) / 6,
            jax.random.normal(ks[3], (E, h, f)) / 6,
            jax.random.normal(ks[4], (E, f, h)) / 7,
            jax.random.normal(ks[5], (n, h)))


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("renormalize", [False, True])
def test_routed_experts_match_the_expert_loop(skewed, renormalize):
    """Forward and gradients (inputs, router, every expert matrix)
    against the plain loop, at balanced routing and with a router that
    sends every token to the same 8 of 16 experts: nothing is dropped,
    and the 8 empty groups are handled."""
    from ray_tpu.ops.moe import routed_experts

    *args, cot = _routed_inputs(skewed)
    with jax.default_matmul_precision("highest"):
        out, logits, counts = jax.jit(
            lambda *a: routed_experts(*a, 8, renormalize))(*args)
        want = _experts_by_loop(*args, 8, renormalize)
        got_g = jax.jit(jax.grad(
            lambda *a: (routed_experts(*a, 8, renormalize)[0] * cot).sum(),
            argnums=(0, 1, 2, 3, 4)))(*args)
        want_g = jax.jit(jax.grad(
            lambda *a: (_experts_by_loop(*a, 8, renormalize) * cot).sum(),
            argnums=(0, 1, 2, 3, 4)))(*args)
    assert int(counts.sum()) == 96 * 8          # no row dropped
    if skewed:
        assert counts.tolist() == [0] * 8 + [96] * 8
    else:
        assert int(counts.min()) > 0
    np.testing.assert_allclose(np.asarray(logits), np.asarray(
        args[0] @ args[1]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for got, ref in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("renormalize", [False, True])
def test_checkpointed_routed_experts_never_recompute_the_down_projection(
        skewed, renormalize):
    """The gate weight sits in front of the down projection, so nothing
    in the backward reads that projection's output and a layer's
    ``jax.checkpoint`` recomputes two grouped matmuls, not three: 11 in
    the gradient (3 forward, 2 recomputed, 6 transposed). ``d top_w``
    comes out of the activation's backward: the router's gradient still
    matches the plain loop."""
    from ray_tpu.ops.moe import routed_experts
    from tests.test_remat import _count_primitives

    *args, cot = _routed_inputs(skewed)
    layer = jax.checkpoint(
        lambda *a: routed_experts(*a, 8, renormalize)[0])
    grad = jax.grad(lambda *a: (layer(*a) * cot).sum(),
                    argnums=(0, 1, 2, 3, 4))
    assert _count_primitives(jax.make_jaxpr(grad)(*args).jaxpr)[
        "ragged_dot_general"] == 11
    with jax.default_matmul_precision("highest"):
        got = jax.jit(grad)(*args)[1]
        want = jax.jit(jax.grad(
            lambda *a: (_experts_by_loop(*a, 8, renormalize) * cot).sum(),
            argnums=1))(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_routed_experts_single_expert_is_the_dense_swiglu():
    from ray_tpu.ops.moe import routed_experts

    x, router_w, e_gate, e_up, e_down, _ = _routed_inputs(False)
    out, _, counts = routed_experts(x, router_w[:, :1], e_gate[:1], e_up[:1],
                                    e_down[:1], top_k=1)
    assert counts.tolist() == [96]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(swiglu(x, e_gate[0], e_up[0], e_down[0])),
        rtol=1e-5, atol=1e-5)


def _laguna_routed_layer():
    """One routed layer at tiny widths with Laguna's router: 256 experts,
    10 a token, renormalised, scaled by 2.5, a shared expert beside."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))          # benchmark/ lies beside tests/
    from benchmark.references import laguna_ref
    from ray_tpu.models import laguna

    cfg = laguna.LagunaConfig.tiny(num_experts=256, top_k=10)
    n, h, f, E = 64, cfg.hidden_size, cfg.moe_intermediate_size, 256
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    p = {"router": jax.random.normal(ks[0], (h, E)) * 0.3,
         "e_gate": jax.random.normal(ks[1], (E, h, f)) / 8,
         "e_up": jax.random.normal(ks[2], (E, h, f)) / 8,
         "e_down": jax.random.normal(ks[3], (E, f, h)) / 6,
         "s_gate": jax.random.normal(ks[4], (h, f)) / 8,
         "s_up": jax.random.normal(ks[5], (h, f)) / 8,
         "s_down": jax.random.normal(ks[6], (f, h)) / 6}
    return cfg, laguna_ref, p, jax.random.normal(ks[7], (n, h))


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The share a chip holds is tied to the model: the parts that the 16
    shares ``held=(16 i, 16)`` of one routed layer give, with the shared
    expert (which every chip computes alike) counted once, add up to the
    uncut reference layer, which holds all 256 experts."""
    from ray_tpu.ops.moe import routed_experts

    cfg, laguna_ref, p, u = _laguna_routed_layer()

    with jax.default_matmul_precision("highest"):
        total = swiglu(u, p["s_gate"], p["s_up"], p["s_down"])
        held_rows = 0
        for i in range(16):
            out, _, counts = routed_experts(
                u, p["router"], *(p[k][16 * i:16 * i + 16]
                                  for k in ("e_gate", "e_up", "e_down")),
                cfg.top_k, renormalize=True, held=(16 * i, 16),
                scale=cfg.routed_scale)
            total = total + out
            held_rows += int(counts[16 * i:16 * i + 16].sum())
        want = laguna_ref.routed_layer(cfg, p, u)
    assert held_rows == int(counts.sum()) == 64 * 10
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_two_shares_add_up_to_the_uncut_sigmoid_layer():
    """LFM2's cut, tied to the model: the parts that the two shares
    ``held=(0, 16)`` and ``held=(16, 16)`` of one routed layer give (a
    sigmoid router with a bias over all 32 experts, no shared expert) add
    up to the uncut reference layer, which holds all 32."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))          # benchmark/ lies beside tests/
    from benchmark.references import lfm2_ref
    from ray_tpu.models import lfm2
    from ray_tpu.ops.moe import routed_experts

    cfg = lfm2.Lfm2Config.tiny(num_experts=32, top_k=4)
    n, h, f, E = 64, cfg.hidden_size, cfg.moe_intermediate_size, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    p = {"router": jax.random.normal(ks[0], (h, E)) * 0.3,
         "router_bias": jax.random.normal(ks[1], (E,)) * 0.1,
         "e_gate": jax.random.normal(ks[2], (E, h, f)) / 8,
         "e_up": jax.random.normal(ks[3], (E, h, f)) / 8,
         "e_down": jax.random.normal(ks[4], (E, f, h)) / 6}
    u = jax.random.normal(ks[5], (n, h))
    with jax.default_matmul_precision("highest"):
        total, held_rows = jnp.zeros_like(u), 0
        for first in (0, 16):
            out, _, counts = routed_experts(
                u, p["router"], *(p[k][first:first + 16]
                                  for k in ("e_gate", "e_up", "e_down")),
                cfg.top_k, renormalize=True, held=(first, 16),
                score="sigmoid", select_bias=p["router_bias"],
                renorm_eps=cfg.renorm_eps)
            total = total + out
            held_rows += int(counts[first:first + 16].sum())
        want = lfm2_ref.routed_layer(cfg, p, u)
    assert held_rows == int(counts.sum()) == 64 * 4
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_routed_experts_names_its_scopes_forward_and_backward():
    """The four scopes ``benchmark/lib/moe_scopes.py`` reads, on the
    operations of the forward and of the hand-written transposes."""
    from ray_tpu.ops.moe import routed_experts

    *args, _ = _routed_inputs(False)
    text = jax.jit(jax.grad(
        lambda *a: routed_experts(*a, 8)[0].sum(), argnums=(0, 2))).lower(
        *args).as_text(debug_info=True)
    for scope in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine"):
        assert f"jvp({scope})" in text, scope
        assert f"transpose(jvp({scope}))" in text, scope


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["all", "held-4..11"])
def test_routed_experts_tpu_path_in_interpret_mode(monkeypatch, held):
    """What a TPU runs: the megablox kernels behind ``grouped_matmul``'s
    own transposes, here through the Pallas interpreter (768 rows, three
    tiles of 256, groups that end inside a tile, eight empty groups); and
    with half the experts held, the passes over the held rows (one of 512
    rows, two tiles: the kernels write no row past the pass's groups)."""
    from functools import partial

    from ray_tpu.ops import moe

    mb = moe._megablox()

    class Interpreted:
        gmm = staticmethod(partial(mb.gmm, interpret=True))
        tgmm = staticmethod(partial(mb.tgmm, interpret=True))

    monkeypatch.setattr(moe, "_megablox", lambda: Interpreted)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for skewed in (False, True):
        *args, cot = _routed_inputs(skewed)
        with jax.default_matmul_precision("highest"):
            fn = lambda *a: (_held_share(held, *a, 8)[0] * cot).sum()
            text = jax.jit(fn).lower(*args).as_text()
            assert "ragged_dot" not in text
            got = jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4)))(
                *args)
            want = jax.jit(jax.value_and_grad(
                lambda *a: (_experts_by_loop(*a, 8, held=held) * cot).sum(),
                argnums=(0, 1, 2, 3, 4)))(*args)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("with_bias", [False, True])
def test_route_sigmoid_selects_on_scores_plus_bias_and_weighs_by_scores(
        with_bias):
    """Selection on ``s + b``, weights from ``s`` alone over their sum
    plus 1e-6, times the scale; no gradient into ``b``."""
    from ray_tpu.ops.moe import route

    n, h, E, K = 64, 16, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (n, h))
    w = jax.random.normal(ks[1], (h, E))
    b = (jax.random.normal(ks[2], (E,)) if with_bias
         else jnp.zeros((E,)))
    logits, top_w, top_e = route(x, w, K, renormalize=True, scale=1.5,
                                 score="sigmoid", select_bias=b,
                                 renorm_eps=1e-6)
    s = np.asarray(jax.nn.sigmoid(x @ w), np.float64)
    want_e = np.argsort(-(s + np.asarray(b, np.float64)), axis=-1)[:, :K]
    assert (np.sort(np.asarray(top_e), -1) == np.sort(want_e, -1)).all()
    chosen = np.take_along_axis(s, np.asarray(top_e), -1)
    np.testing.assert_allclose(
        np.asarray(top_w), 1.5 * chosen / (chosen.sum(-1, keepdims=True)
                                           + 1e-6), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)
    if with_bias:     # the bias moved some choice, and gets no gradient
        assert (np.sort(np.argsort(-s, -1)[:, :K], -1)
                != np.sort(want_e, -1)).any()
    g_b, g_w = jax.grad(
        lambda b_, w_: (route(x, w_, K, True, 1.5, "sigmoid", b_, 1e-6)[1]
                        * jnp.arange(K)).sum(), argnums=(0, 1))(b, w)
    assert float(jnp.abs(g_b).max()) == 0.0 < float(jnp.abs(g_w).max())


def test_route_renorm_eps_is_in_the_denominator():
    from ray_tpu.ops.moe import route

    x = jnp.ones((1, 2))
    w = jnp.full((2, 4), -20.0)          # sigmoid scores of 4e-18
    tiny = route(x, w, 2, True, score="sigmoid", renorm_eps=1e-6)[1]
    assert float(tiny.sum()) < 1e-6      # s / (2 s + 1e-6), not 1/2 each
    plain = route(x, w, 2, True, score="sigmoid")[1]
    np.testing.assert_allclose(np.asarray(plain), 0.5, rtol=1e-6)


@pytest.mark.parametrize("renormalize,scale", [(False, 1.0), (True, 1.0),
                                               (True, 2.5)])
def test_route_softmax_callers_trace_what_they_did(renormalize, scale):
    """The three old callers' arguments give the jaxpr they gave before
    ``score``, ``select_bias`` and ``renorm_eps``: bit-equal results and
    the same equations."""
    from ray_tpu.ops.moe import route

    def before(x, router_w, top_k, renormalize=False, scale=1.0):
        logits = jnp.dot(x, router_w.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, top_k)
        if renormalize:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        if scale != 1.0:
            top_w = top_w * scale
        return logits, top_w, top_e

    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    for got, want in zip(route(x, w, 3, renormalize, scale),
                         before(x, w, 3, renormalize, scale)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert str(jax.make_jaxpr(lambda a, b: route(a, b, 3, renormalize,
                                                 scale))(x, w)) == \
        str(jax.make_jaxpr(lambda a, b: before(a, b, 3, renormalize,
                                               scale))(x, w))
    with pytest.raises(ValueError, match="softmax | sigmoid"):
        route(x, w, 3, score="tanh")


@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all", "held-4..7"])
def test_routed_experts_sigmoid_with_bias_match_the_expert_loop(held):
    """``routed_experts(score="sigmoid", select_bias=...)``, all experts
    and a share, against every expert over every token."""
    n, h, f, E, K = 96, 32, 48, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (n, h))
    router_w = jax.random.normal(ks[1], (h, E)) / 4
    bias = jax.random.normal(ks[5], (E,)) / 4
    e_gate, e_up, e_down = (jax.random.normal(ks[2], (E, h, f)) / 6,
                            jax.random.normal(ks[3], (E, h, f)) / 6,
                            jax.random.normal(ks[4], (E, f, h)) / 7)
    with jax.default_matmul_precision("highest"):
        got, _, counts = _held_share(
            held, x, router_w, e_gate, e_up, e_down, K, renormalize=True,
            score="sigmoid", select_bias=bias, renorm_eps=1e-6)
        s = jax.nn.sigmoid(x @ router_w)
        top_e = jax.lax.top_k(s + bias, K)[1]
        top_w = jnp.take_along_axis(s, top_e, -1)
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
        want = jnp.zeros_like(x)
        first, count = held or (0, E)
        for e in range(first, first + count):
            gate = jnp.where(top_e == e, top_w, 0.0).sum(-1)
            want = want + gate[:, None] * swiglu(x, e_gate[e], e_up[e],
                                                 e_down[e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(counts) == np.bincount(
        np.asarray(top_e).ravel(), minlength=E)).all()


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "held"])
def test_routed_experts_in_a_latent_with_two_matrices(held):
    """``routed_experts(e_gate=None, router_x=)``: the router reads the
    hidden state, the experts multiply latent rows with ``relu(. W1)^2 W2``,
    all experts here or a held share in passes, forward and gradient against
    a loop over the experts."""
    from ray_tpu.ops import moe

    k = jax.random.split(jax.random.PRNGKey(1), 5)
    n, h, l, f, E, K = 24, 16, 8, 12, 8, 3
    u = jax.random.normal(k[0], (n, h))
    lat = jax.random.normal(k[1], (n, l))
    router = jax.random.normal(k[2], (h, E))
    first, count = held or (0, E)
    e_up = jax.random.normal(k[3], (E, l, f))[first:first + count] / 3
    e_down = jax.random.normal(k[4], (E, f, l))[first:first + count] / 3
    how = dict(renormalize=True, scale=2.5, score="sigmoid",
               renorm_eps=1e-20, held=held)

    def program(lat, e_up, e_down):
        out, logits, counts = moe.routed_experts(
            lat, router, None, e_up, e_down, K, router_x=u, **how)
        return out, (logits, counts)

    def plain(lat, e_up, e_down):
        s = jax.nn.sigmoid(u @ router)
        w, chosen = jax.lax.top_k(s, K)
        w = 2.5 * w / (w.sum(-1, keepdims=True) + 1e-20)
        out = jnp.zeros_like(lat)
        for j in range(count):
            gate = jnp.where(chosen == first + j, w, 0.0).sum(-1)
            out = out + gate[:, None] * (
                jnp.square(jax.nn.relu(lat @ e_up[j])) @ e_down[j])
        return out

    (out, (logits, counts)) = jax.jit(program)(lat, e_up, e_down)
    assert out.shape == (n, l) and int(counts.sum()) == n * K
    np.testing.assert_allclose(logits, u @ router, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, plain(lat, e_up, e_down), rtol=1e-4,
                               atol=1e-4)
    w = jax.random.normal(jax.random.PRNGKey(9), (n, l))
    got = jax.jit(jax.grad(lambda *a: (program(*a)[0] * w).sum(),
                           (0, 1, 2)))(lat, e_up, e_down)
    want = jax.jit(jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2)))(
        lat, e_up, e_down)
    for g, t in zip(got, want):
        np.testing.assert_allclose(g, t, rtol=1e-3, atol=1e-4)


def test_routed_part_options_for_a_latent_are_off_by_default():
    """``routed_part(latent=, act="relu2", shared="relu2")`` has no
    ``e_gate`` and no ``s_gate`` leaf, rows of the latent's width and a
    plan's reckoning at that width; the default table is what it was."""
    from dataclasses import dataclass

    from ray_tpu.models import lfm2
    from ray_tpu.ops import moe

    @dataclass(frozen=True)
    class Config(lfm2.Lfm2Config):
        moe_latent_size: int = 16
        shared_intermediate_size: int = 48

    cfg = Config.tiny()
    plain = moe.routed_part(score="sigmoid", bias=True,
                            renorm_eps="renorm_eps")
    latent = moe.routed_part(score="sigmoid", bias=True,
                             renorm_eps="renorm_eps", shared="relu2",
                             latent="moe_latent_size", act="relu2")
    assert list(plain.leaves(cfg)) == ["mlp_norm", "router", "router_bias",
                                      "e_gate", "e_up", "e_down"]
    leaves = latent.leaves(cfg)
    assert list(leaves) == ["mlp_norm", "router", "router_bias", "l_down",
                            "l_up", "e_up", "e_down", "s_up", "s_down"]
    assert leaves["e_up"].shape == (8, 16, 32)
    assert leaves["e_down"].shape == (8, 32, 16)
    assert leaves["l_down"].shape == (64, 16)
    shape = {k: v.shape for k, v in leaves.items()}
    kept = latent.keeps(cfg, shape, 128, None)
    pairs, act = 128 * cfg.top_k, 4
    assert kept["rungs"][2] == pairs * 32 * act + 128 * 48 * act
    assert kept["rows"] == pairs * (2 * 16 + 4 * 32) * act
    assert kept["width"] == 3 * 48 + 4 * 16
    with pytest.raises(ValueError, match="unknown expert activation"):
        moe.routed_part(act="gelu")
    with pytest.raises(NotImplementedError, match="without a mesh"):
        moe.routed_experts_on(object(), jnp.zeros((1, 2, 16)),
                              jnp.zeros((64, 8)), None, None, None, 2,
                              router_x=jnp.zeros((1, 2, 64)))
