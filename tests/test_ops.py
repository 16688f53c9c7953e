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
