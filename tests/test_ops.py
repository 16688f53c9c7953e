"""Numerics tests for ops: layers, flash attention (interpret mode), ring
attention on the 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.attention import attention_reference, flash_attention  # noqa: E402
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu  # noqa: E402
from ray_tpu.ops.ring_attention import ring_attention  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402


def test_rms_norm_matches_definition():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)
    w = jnp.ones((32,)) * 1.5
    got = rms_norm(x, w)
    expect = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True)
                         + 1e-6) * 1.5
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(64, 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 4, 64))
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-4,
    )


def test_rope_position_zero_identity():
    cos, sin = rope_frequencies(16, 8)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 2, 16))
    y = apply_rope(x, cos, sin)  # position 0: cos=1, sin=0
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)


def test_rope_relative_property():
    """Scores q_i . k_j depend only on i-j after RoPE."""
    d = 32
    cos, sin = rope_frequencies(d, 64)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 1, d))
    # same underlying q/k at every position
    q = jnp.broadcast_to(q[:, :1], q.shape)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    qr = apply_rope(q, cos, sin)[0, :, 0]
    kr = apply_rope(k, cos, sin)[0, :, 0]
    s = np.asarray(qr @ kr.T)
    # diagonal bands constant: s[i, j] == s[i+1, j+1]
    np.testing.assert_allclose(s[0, 1], s[10, 11], rtol=1e-4)
    np.testing.assert_allclose(s[5, 2], s[20, 17], rtol=1e-4)


def test_swiglu_shapes_and_values():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16))
    wg = jax.random.normal(jax.random.PRNGKey(6), (16, 32)) * 0.1
    wu = jax.random.normal(jax.random.PRNGKey(7), (16, 32)) * 0.1
    wd = jax.random.normal(jax.random.PRNGKey(8), (32, 16)) * 0.1
    y = swiglu(x, wg, wu, wd)
    assert y.shape == (4, 16)
    expect = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect), rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    b, s, h, kvh, d = 2, 128, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d), jnp.float32)
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, use_pallas=True,
                          interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grads_match():
    b, s, h, d = 1, 128, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))

    gf = jax.grad(lambda *a: flash_attention(
        *a, use_pallas=True, interpret=True, block_q=64, block_k=64).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: attention_reference(*a).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_flash_attention_grads_match_gqa():
    # Grouped-query attention: dK/dV must reduce over the query-head group.
    b, s, h, kvh, d = 2, 128, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))

    def loss(fn):
        # non-uniform cotangent so dO varies per element
        return lambda *a: (fn(*a) * jnp.arange(d, dtype=jnp.float32)).sum()

    gf = jax.grad(loss(lambda *a: flash_attention(
        *a, causal=True, use_pallas=True, interpret=True,
        block_q=64, block_k=64)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda *a: attention_reference(*a, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        # arange-weighted cotangent makes grads O(100); compare relatively
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=1e-4)


def test_flash_attention_grads_cross_seq():
    # sk > sq (chunked prefill / decode alignment): causal offset path.
    b, sq, sk, h, d = 1, 64, 128, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, h, d))
    gf = jax.grad(lambda *a: flash_attention(
        *a, causal=True, use_pallas=True, interpret=True,
        block_q=64, block_k=64).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: attention_reference(*a, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_flash_attention_rejects_ragged():
    q = jnp.zeros((1, 100, 2, 32))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, q, q, use_pallas=True, interpret=True,
                        block_q=64, block_k=64)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    mesh = build_mesh(MeshSpec({"sp": 8}))
    b, s, h, d = 2, 256, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, axis_name="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_gqa():
    mesh = build_mesh(MeshSpec({"sp": 8}))
    b, s, h, kvh, d = 1, 128, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
    ref = attention_reference(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_differentiable():
    mesh = build_mesh(MeshSpec({"sp": 8}))
    b, s, h, d = 1, 64, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    gr = jax.grad(lambda *a: attention_reference(*a).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    # jitted, as a train step has it: op by op the same gradient costs
    # hundreds of small eight-device programs
    gg = jax.jit(jax.grad(lambda *a: ring_attention(*a, mesh).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gg, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


# ------------------------------------------------------------------ ulysses


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_full(causal):
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec({"sp": 8}))
    b, s, h, d = 2, 256, 8, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d), jnp.float32)
    ref = attention_reference(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh, axis_name="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("kvh", [4, 2])
def test_ulysses_attention_gqa(kvh):
    """kvh of 4 and 2 don't divide sp=8, exercising the minimal-KV-
    replication path (r = n/gcd(kv, n) of 2 and 4); kvh=8 is the aligned
    case covered above."""
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec({"sp": 8}))
    b, s, h, d = 1, 128, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
    ref = attention_reference(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, mesh, axis_name="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_attention_differentiable():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec({"sp": 8}))
    b, s, h, d = 1, 64, 8, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    gr = jax.grad(lambda *a: attention_reference(*a).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(lambda *a: ulysses_attention(*a, mesh).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gg, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = build_mesh(MeshSpec({"sp": 8}))
    q = jnp.zeros((1, 64, 6, 16))
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, q, q, mesh)


# ------------------------------------------------------- routed experts


def _experts_by_loop(x, router_w, e_gate, e_up, e_down, top_k,
                     renormalize=False):
    """Every expert over every token, a mask keeping the chosen ones."""
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(router_w.shape[1]):
        gate = jnp.where(top_e == e, top_w, 0.0).sum(-1)
        out = out + gate[:, None] * swiglu(x, e_gate[e], e_up[e], e_down[e])
    return out


def _routed_inputs(skewed: bool):
    n, h, f, E = 96, 32, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (n, h))
    router_w = jax.random.normal(ks[1], (h, E))
    if skewed:      # a constant feature the router sends to experts 8..15
        x = x.at[:, 0].set(5.0)
        router_w = (router_w * 0.01).at[0, 8:].add(10.0)
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
    from tests.test_models import _count_primitives

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


def test_routed_experts_tpu_path_in_interpret_mode(monkeypatch):
    """What a TPU runs: the megablox kernels behind ``grouped_matmul``'s
    own transposes, here through the Pallas interpreter (768 rows, three
    tiles of 256, groups that end inside a tile, eight empty groups)."""
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
            fn = lambda *a: (moe.routed_experts(*a, 8)[0] * cot).sum()
            text = jax.jit(fn).lower(*args).as_text()
            assert "ragged_dot" not in text
            got = jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4)))(
                *args)
            want = jax.jit(jax.value_and_grad(
                lambda *a: (_experts_by_loop(*a, 8) * cot).sum(),
                argnums=(0, 1, 2, 3, 4)))(*args)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-4)
