"""Numerics of the plain layers (``ops/layers.py``: norms, rotations, the
SwiGLU and the squared-ReLU MLP) and of ring and Ulysses attention on the
8-device CPU mesh. The other operators' files: ``test_flash_ops.py``,
``test_moe_ops.py``, ``test_held_ops.py``, ``test_conv_ops.py``,
``test_ssm_ops.py``, ``test_delta_ops.py`` (one file was a worker's whole
run: ``--dist loadfile`` hands out files)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.attention import attention_reference  # noqa: E402
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


def test_partial_rope_rotates_the_first_dims_and_passes_the_rest():
    """Tables made for 8 of a head's 16 dims: dims 0..3 and 4..7 are the
    two halves of the rotation, written out here, and 8..15 pass."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 3, 16))
    cos, sin = rope_frequencies(8, 12, theta=100.0)
    assert cos.shape == (12, 4)
    got = np.asarray(apply_rope(x, cos, sin))
    inv = 1.0 / 100.0 ** (np.arange(0, 8, 2) / 8)
    ang = np.arange(12)[:, None] * inv[None]                    # [12, 4]
    c, s_ = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    xn = np.asarray(x)
    x1, x2 = xn[..., :4], xn[..., 4:8]
    want = np.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_, xn[..., 8:]],
                          -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., 8:], xn[..., 8:])
    # tables of the whole width rotate the whole head, as before
    full = apply_rope(x, *rope_frequencies(16, 12, theta=100.0))
    assert float(jnp.abs(full[:, 1:, :, 8:] - x[:, 1:, :, 8:]).max()) > 0.1


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


def test_rms_norm_zero_centred_scales_by_one_plus_the_weight():
    from ray_tpu.ops.layers import rms_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16))
    w = 0.2 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(rms_norm(x, w, 1e-6, zero_centred=True), want,
                               rtol=1e-6, atol=1e-6)
    # a weight of zeros is the unit scale, and the default is the plain norm
    np.testing.assert_array_equal(
        rms_norm(x, jnp.zeros(16), zero_centred=True),
        rms_norm(x, jnp.ones(16)))
    np.testing.assert_array_equal(rms_norm(x, w), rms_norm(x, w, 1e-6, False))
    # in bfloat16 the one is added in float32: a weight of 2^-9 is not lost
    small = jnp.full((16,), 2.0 ** -9, jnp.bfloat16)
    xb = jnp.ones((1, 16), jnp.float32)
    assert float(rms_norm(xb, small, 0.0, True)[0, 0]) == 1 + 2.0 ** -9


def test_partial_rope_at_a_quarter_rotates_the_first_dims_alone():
    from ray_tpu.ops.layers import apply_rope, rope_frequencies

    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 2, 16))
    cos, sin = rope_frequencies(4, 12, 10_000_000.0)
    got = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    ang = jnp.arange(12.0)[:, None] * (1.0 / 10_000_000.0 ** (
        jnp.arange(0, 4, 2) / 4))[None]
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :2], x[..., 2:4]
    np.testing.assert_allclose(got[..., :2], x1 * c - x2 * s, atol=1e-6)
    np.testing.assert_allclose(got[..., 2:4], x2 * c + x1 * s, atol=1e-6)
    # position 0 is not rotated; a later one is
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)
    assert float(jnp.abs(got[:, 5, :, :4] - x[:, 5, :, :4]).max()) > 1e-2


def test_relu2_mlp_is_two_matrices_around_a_squared_relu():
    from ray_tpu.ops.layers import relu2_kept, relu2_mlp

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 8, 16))
    up, down = (jax.random.normal(k[1], (16, 24)),
                jax.random.normal(k[2], (24, 16)))
    want = np.square(np.maximum(np.asarray(x) @ np.asarray(up), 0.0)
                     ) @ np.asarray(down)
    np.testing.assert_allclose(relu2_mlp(x, up, down), want, rtol=1e-5,
                               atol=1e-4)
    # the MLP rung keeps the one product, named as swiglu names its up
    jaxpr = str(jax.make_jaxpr(relu2_mlp)(x, up, down))
    assert jaxpr.count("name=mlp_up") == 1 and "mlp_gate" not in jaxpr
    assert relu2_kept(64, 24, 2) == {"rungs": (0, 0, 64 * 24 * 2, 0),
                                     "width": 72, "rows": 0}
