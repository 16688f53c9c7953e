"""The gated delta rule's kernels (``ops/delta.rule_kernels``, through the
Pallas interpreter) with fewer key heads than value heads: q and k read
at the key heads, their norms taken inside the calls, a wrong head map
refused. Equal heads: ``test_delta_kernels.py``."""


import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests.test_delta_ops import _delta_recurrence, _rule, _rule_inputs  # noqa: E402
from tests.test_delta_kernels import _rule_scalar, rule_kernels  # noqa: E402


def _grouped_inputs(ratio, key_heads, s, seed=0):
    """``_rule_inputs`` with q and k at ``key_heads`` heads under ``ratio``
    times as many value heads."""
    q, k, v, g, beta = _rule_inputs(s=s, H=ratio * key_heads, seed=seed)
    return q[:, :, :key_heads], k[:, :, :key_heads], v, g, beta


def _joined_recurrence(q, k, *rest):
    """The recurrence with value head ``i`` reading key head ``i // ratio``:
    q and k copied to the value heads before it (their gradients sum
    back over a key head's value heads)."""
    ratio = rest[0].shape[2] // q.shape[2]
    return _delta_recurrence(jnp.repeat(q, ratio, axis=2),
                             jnp.repeat(k, ratio, axis=2), *rest)


@pytest.mark.parametrize("ratio,key_heads,heads,block,chunks,base,chunk,s", [
    (1, 3, 3, 3, 2, 4, 8, 32), (2, 4, 4, 4, 2, 8, 8, 40),
    (4, 2, 4, 4, 1, 4, 8, 24), (4, 1, 8, 4, 8, 4, 16, 30),
    (2, 3, 6, 6, 2, 4, 8, 27)],
    ids=["ratio-1", "ratio-2-two-blocks", "ratio-4-two-blocks",
         "ratio-4-ragged", "ratio-2-ragged-three-key-heads"])
def test_rule_kernels_at_grouped_heads_match_the_recurrence(
        ratio, key_heads, heads, block, chunks, base, chunk, s,
        rule_kernels):
    """``ratio`` value heads a key head through the kernels (a batch of
    two; one block of heads and several; sequences that are not whole
    steps, nor whole chunks) against the recurrence on q and k copied to
    the value heads, float32 at 1e-5 (gradients 1e-4): outputs, the last
    state and the gradients of q and k at the key heads (a key head's
    value heads summed inside the call), v, g and beta; the plan says the
    heads are joined by the index map and a block holds whole key heads."""
    from ray_tpu.ops import delta

    rule_kernels(heads, chunks, base)
    args = _grouped_inputs(ratio, key_heads, s)
    plan = delta.rule_plan(*args[2].shape[:3], args[0].shape[-1],
                           args[2].shape[-1], chunk, key_heads=key_heads)
    assert (plan["form"], plan["heads_a_block"]) == ("pallas", block)
    assert plan["joined"] == (None if ratio == 1 else "index_map")
    with jax.default_matmul_precision("highest"):
        o, S = jax.jit(lambda *a: _rule(*a, chunk))(*args)
        want_o, want_S = jax.jit(_joined_recurrence)(*args)
        got = jax.jit(jax.grad(_rule_scalar(lambda *a: _rule(*a, chunk)),
                               argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(_rule_scalar(_joined_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_the_norms_inside_the_calls_are_l2_norm(rule_kernels, ratio=2):
    """q and k handed to the calls as the taps leave them, positions last
    and not normed (``norm=``: the calls take the L2 norms in VMEM,
    forward and backward, q's times ``K^-0.5``), against the same calls on
    q and k that ``l2_norm`` normed in XLA: outputs, the last state and
    every gradient (q's and k's through the norm) agree to float32's
    rounding, on a sequence padded with zero rows (whose norm is 0)."""
    from ray_tpu.ops import delta

    rule_kernels(4, 2, 4)
    q, k, v, g, beta = _grouped_inputs(ratio, 2, 28, seed=5)
    b, s, H, V = v.shape
    K = q.shape[-1]
    plan = delta.rule_plan(b, s, H, K, V, 8, key_heads=2)

    def last(a):
        return jnp.swapaxes(a.reshape(b, s, -1), 1, 2)

    def inside(q, k, v, g, beta):
        o, S = delta.rule_kernels(last(q), last(k), last(v), g, beta, plan,
                                  norm=(delta.QK_NORM_EPS, K ** -0.5))
        return jnp.swapaxes(o, 1, 2).reshape(b, s, H, V), S

    def outside(q, k, v, g, beta):
        return delta.gated_delta_rule(
            delta.l2_norm(q, delta.QK_NORM_EPS, K ** -0.5),
            delta.l2_norm(k, delta.QK_NORM_EPS), v, g, beta, chunk=8)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(f)(q, k, v, g, beta) + jax.jit(jax.grad(
            _rule_scalar(f), argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
            for f in (inside, outside))
    for name, a, w in zip(("o", "S", "dq", "dk", "dv", "dg", "dbeta"), got,
                          want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_a_wrong_head_map_in_the_kernels_is_another_function(
        rule_kernels, monkeypatch):
    """The kernels' map from a block's value heads to its key heads
    (``_key_head``, looked up while they trace) with key head ``i mod 2``
    planted for ``i // 2``: outputs and every gradient leave the
    recurrence's by more than 10% where the honest map agrees at 1e-5;
    afterwards the module is what it was."""
    from ray_tpu.ops import delta

    rule_kernels(4, 2, 4)
    args = _grouped_inputs(2, 2, 32, seed=2)

    def readings():
        # (new functions each time: a jitted one would keep its first trace)
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: _rule(*a, 8))(*args) + jax.jit(
                jax.grad(_rule_scalar(lambda *a: _rule(*a, 8)),
                         argnums=(0, 1, 2, 3, 4)))(*args)

    def gaps(got, want):
        return [float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                for g, w in zip(got, want)]

    with jax.default_matmul_precision("highest"):
        want = jax.jit(_joined_recurrence)(*args) + jax.jit(jax.grad(
            _rule_scalar(_joined_recurrence), argnums=(0, 1, 2, 3, 4)))(*args)
    honest_map = delta._key_head
    assert max(gaps(readings(), want)) < 1e-4
    with monkeypatch.context() as planted:
        planted.setattr(delta, "_key_head", lambda h, ratio: h % 2)
        wrong = readings()
    assert delta._key_head is honest_map
    assert min(gaps(wrong, want)) > 0.1, gaps(wrong, want)
