"""``ops/dsa.py`` under grouped keys (``G`` key/value heads each serving ``H
/ G`` query heads, no shared rope key): XLA's form against the per-head form
on repeated keys and values, the Mosaic calls ``dsa_attend_gqa_fwd`` and
``dsa_attend_gqa_bwd`` through the Pallas interpreter against XLA's form,
alone and inside the walk, the scores' kernels at an index of half a lane's
width, and what the span and the plan say of the layout."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import dsa  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402
from tests.test_dsa_kernels import HEADS, DIM, _inputs, kernels  # noqa: E402


def _block(n, keys, G=2, R=2, d=32, dv=32, seed=5, dtype=jnp.float32):
    """A block's q [n, G R, d] and the k [keys, G, d], v [keys, G, d_v] of
    the tier it attends over."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = ((n, G * R, d), (keys, G, d), (keys, G, dv))
    return tuple(jax.random.normal(k, shape, jnp.float32).astype(dtype)
                 for k, shape in zip(ks, shapes))


def _a_choice(n, keys, first, topk, seed=6):
    return dsa.choose(jax.random.normal(jax.random.PRNGKey(seed), (n, keys),
                                        jnp.float32), first, topk)


def _xla(chosen, scale=0.2):
    def attend(q, k, v):
        out, p = dsa.plain_attend_grouped(q, k, v, chosen, scale)
        return out, p.sum(0)

    return attend


def _per_head(chosen, scale=0.2):
    """The per-head form on keys and values repeated to every head, the
    rope key of no width."""
    def attend(q, k, v):
        R = q.shape[1] // k.shape[1]
        out, p = dsa.plain_attend(
            q, jnp.repeat(k, R, axis=1), jnp.repeat(v, R, axis=1),
            jnp.zeros((k.shape[0], 0), q.dtype), chosen, scale)
        return out, p.sum(0)

    return attend


def _kernel(chosen, first, tile, scale=0.2):
    """The same through ``attend_kernels_grouped``, which takes a group's
    heads in a row and hands ``out`` back turned."""
    def attend(q, k, v):
        n, H, d = q.shape
        G = k.shape[1]
        out, _, p = dsa.attend_kernels_grouped(
            jnp.swapaxes(q, 0, 1).reshape(G, H // G * n, d),
            jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1), chosen, first,
            scale, tile)
        out = out.reshape(G, -1, H // G, n)               # [G, d_v, R, n]
        return jnp.transpose(out, (3, 0, 2, 1)).reshape(n, H, -1), p

    return attend


def _gradients(attend, args, g):
    return jax.grad(lambda *a: (attend(*a)[0].astype(jnp.float32) * g).sum(),
                    argnums=(0, 1, 2))(*args)


NAMES = ("out", "p_sum", "dq", "dk", "dv")


@pytest.mark.parametrize("G,R", [(2, 2), (1, 4), (3, 1)],
                         ids=["two-groups-of-two", "one-group", "a-head-each"])
def test_xlas_grouped_form_is_the_per_head_form_on_repeated_keys(G, R):
    """``plain_attend_grouped`` against ``plain_attend`` on K and V
    repeated to every head: the output, every head's probabilities' sum,
    and the gradients, the keys' and values' summed over a group's heads."""
    args = _block(32, 96, G, R)
    chosen = _a_choice(32, 96, 64, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (32, G * R, 32))
    with jax.default_matmul_precision("highest"):
        got = _xla(chosen)(*args) + _gradients(_xla(chosen), args, g)
        want = (_per_head(chosen)(*args)
                + _gradients(_per_head(chosen), args, g))
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("n,keys,tile,rows,G,R,lanes,d", [
    (32, 128, 64, 16, 2, 2, 32, 32),
    (64, 192, 64, 32, 1, 3, 32, 32),
    (32, 96, 96, 32, 2, 4, 32, 32),
    (64, 256, 128, 64, 4, 2, 32, 32),
    (128, 256, 128, 128, 2, 2, 128, 128)],
    ids=["32-by-tiles-of-64", "one-group-of-three", "groups-of-four",
         "four-groups-chunks-of-64", "real-lanes"])
def test_grouped_kernels_match_xlas_form(n, keys, tile, rows, G, R, lanes, d,
                                         kernels):
    """``out``, all heads' summed probabilities and the three gradients
    against XLA's grouped form and ``jax.grad`` of it, float32 at the
    highest matmul precision, the block's queries the last of the keys'
    positions, over queries, keys, tiles, chunks, groups and heads a
    group."""
    kernels(tile, rows, lanes, attend=(tile, rows))
    args = _block(n, keys, G, R, d, d)
    first = keys - n
    chosen = _a_choice(n, keys, first, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, G * R, d))
    with jax.default_matmul_precision("highest"):
        want = _xla(chosen)(*args) + _gradients(_xla(chosen), args, g)
        kernel = _kernel(chosen, jnp.int32(first), tile)
        got = kernel(*args) + _gradients(kernel, args, g)
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    assert not np.asarray(got[1])[~np.asarray(chosen)].any()


def test_grouped_kernels_take_bfloat16_as_xla_does(kernels):
    """bfloat16 operands: float32 sums and a float32 softmax, ``p`` cast
    before PV and ``dS`` before its products: XLA's to bfloat16's
    rounding."""
    kernels(64, 32, attend=(64, 32))
    args = _block(32, 128, 2, 4, dtype=jnp.bfloat16)
    chosen = _a_choice(32, 128, 96, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (32, 8, 32))
    want = _xla(chosen)(*args) + _gradients(_xla(chosen), args, g)
    kernel = _kernel(chosen, jnp.int32(96), 64)
    got = kernel(*args) + _gradients(kernel, args, g)
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 2e-2, name


def _walk_inputs(b, s, G=2, R=2, d=32, seed=3):
    """q, k, v under grouped keys, None for the rope key, and the index's
    three inputs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32
    return (jax.random.normal(ks[0], (b, s, G * R, d), f32),
            jax.random.normal(ks[1], (b, s, G, d), f32),
            jax.random.normal(ks[2], (b, s, G, d), f32), None,
            jax.random.normal(ks[3], (b, s, HEADS, DIM), f32),
            jax.random.normal(ks[4], (b, s, DIM), f32),
            jax.random.normal(ks[5], (b, s, HEADS), f32) * 0.1)


_ARRAYS = (0, 1, 2, 4, 5, 6)


def test_the_grouped_walk_is_the_per_head_walk_on_repeated_keys():
    """``sparse_attention`` under grouped keys against the per-head layout
    on K and V repeated to every head and a rope key of no width: the same
    choice bit for bit, the same output, pairs and term."""
    args = _walk_inputs(2, 128)
    how = dict(scale=0.2, topk=24, block=32, tiers=2, keep_choice=True)
    q, k, v, _, *index = args
    with jax.default_matmul_precision("highest"):
        got = dsa.sparse_attention(*args, **how)
        want = dsa.sparse_attention(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
            jnp.zeros(q.shape[:2] + (0,)), *index, **how)
    np.testing.assert_array_equal(got[3], want[3])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="do not divide"):
        dsa.sparse_attention(q[:, :, :3], k, v, None, *index, **how)


def test_the_grouped_walk_with_both_kernels_is_the_walk_with_xlas_forms(
        kernels, monkeypatch):
    """Two rows of 256 positions, blocks of 32 queries in two tiers, tiles
    of 64 keys, scores and attention through their kernels, against the
    walk with XLA's forms: the same choice bit for bit, the same output,
    pairs and term, and the gradients of both to all six inputs."""
    args = _walk_inputs(2, 256)
    how = dict(scale=0.2, topk=24, block=32, tiers=2)
    g = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def walk(*a, **more):
        return dsa.sparse_attention(*a[:3], None, *a[3:], **how, **more)

    def loss(*a):
        out, kl, _ = walk(*a)
        return (out * g).sum() + kl.sum()

    arrays = tuple(args[i] for i in _ARRAYS)
    with jax.default_matmul_precision("highest"):
        want = walk(*arrays, keep_choice=True)
        want_grads = jax.grad(loss, argnums=tuple(range(6)))(*arrays)
        kernels(32, 16, attend=(64, 32))
        seen = []
        real = dsa.attend_kernels_grouped
        # as the walk passes them: (q, k, v, chosen, first, scale, tile, v_t)
        monkeypatch.setattr(
            dsa, "attend_kernels_grouped",
            lambda *a, **more: seen.append((a[0].shape, a[4], a[6]))
            or real(*a, **more))
        monkeypatch.setattr(dsa, "attend_kernels", None)
        got = walk(*arrays, keep_choice=True)
        got_grads = jax.grad(loss, argnums=tuple(range(6)))(*arrays)
    # a group's two heads of 32 queries in a row, told where they stand
    assert seen and all(shape == (2, 64, 32) and first is not None
                        and tile == 64 for shape, first, tile in seen)
    np.testing.assert_array_equal(got[3], want[3])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for name, a, b in zip(("dq", "dk", "dv", "dq_i", "dk_i", "dw"),
                          got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5, err_msg=name)


def test_the_span_says_the_layout_and_the_groups(kernels):
    """``rtpu.dsa.shapes`` says ``attend_layout`` and ``kv_groups``:
    ``grouped`` and the key/value heads without a rope key, ``per_head``
    and the heads with one."""
    def said(args):
        here = tracing.since()
        jax.eval_shape(lambda *a: dsa.sparse_attention(
            *a[:3], args[3] if args[3] is None else a[3], *a[-3:],
            scale=0.3, topk=8, block=32, tiers=2),
            *(x for x in args if x is not None))
        (span,) = [e["args"] for e in here.events()
                   if e["name"] == "rtpu.dsa.shapes"]
        return span["attend_layout"], span["kv_groups"], span["attend_form"]

    kernels(32, 16, attend=(64, 32))
    grouped = _walk_inputs(1, 128)
    assert said(grouped) == ("grouped", 2, "kernel")
    q, k, v, _, *index = grouped
    per_head = (q, jnp.repeat(k, 2, 2)[..., :24], jnp.repeat(v, 2, 2),
                jnp.zeros((1, 128, 8)), *index)
    assert said(per_head)[:2] == ("per_head", 4)


def test_the_plan_fits_the_grouped_calls_under_the_ceiling(monkeypatch):
    """At the cell's widths (32 query heads on 4 key/value heads of 128, an
    index of 16 heads of 64, bfloat16, 16,384 positions) on a TPU backend:
    both layouts' calls are kernels, a block of 256 queries stands, the
    grouped calls' needs are reckoned with their temporaries and lie under
    ``VMEM_CEILING``; a ceiling under them steps the block down."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = dsa.Widths(32, 128, 0, 128, 16, 64, jnp.bfloat16, 4)
    assert dsa.scores_plan(256, 4096, 16, 64) == {
        "scores_form": "kernel", "scores_tile": 512}
    needs = dsa.walk_needs(256, 4096, cell)
    assert set(needs) == {"dsa_scores_fwd", "dsa_scores_bwd",
                          "dsa_attend_gqa_fwd", "dsa_attend_gqa_bwd"}
    assert max(needs.values()) <= dsa.VMEM_CEILING
    # six [512, 8 x 256] float32 temporaries are in the grouped needs
    assert needs["dsa_attend_gqa_bwd"] > 6 * 512 * 2048 * 4
    assert dsa.walk_plan(16_384, 256, 4, cell) == (256, 4)
    monkeypatch.setattr(dsa, "VMEM_CEILING", max(needs.values()) - 1)
    assert dsa.walk_plan(16_384, 256, 4, cell) == (128, 4)


def test_the_scores_kernels_take_an_index_of_half_a_lanes_width(kernels):
    """16 index heads of 64 at the module's own lanes: the kernel form,
    forward and the three gradients against XLA's."""
    kernels(256, 64, 128)
    args = _inputs(128, 512, heads=16, dim=64)
    assert dsa.scores_plan(128, 512, 16, 64) == {
        "scores_form": "kernel", "scores_tile": 256}
    with jax.default_matmul_precision("highest"):
        want = dsa.plain_scores(*args)
        np.testing.assert_allclose(dsa.index_scores(*args), want, rtol=1e-5,
                                   atol=1e-5)
        g = jnp.where(dsa.choose(want, 384, 16), 1.0, 0.0)
        wants = jax.grad(lambda *a: (dsa.plain_scores(*a) * g).sum(),
                         argnums=(0, 1, 2))(*args)
        gots = jax.grad(lambda *a: (dsa.index_scores(*a) * g).sum(),
                        argnums=(0, 1, 2))(*args)
    for name, a, b in zip(("dq_i", "dk_i", "dw"), gots, wants):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


# ---- the walk's rule of its own (PR 58) under grouped keys


@pytest.mark.parametrize("s,block,tiers,topk", [
    (36, 12, 3, 5), (48, 8, 2, 6)],
    ids=["a-ragged-byte-a-tier", "three-blocks-a-tier"])
def test_xlas_grouped_walk_is_the_checkpointed_walk(s, block, tiers, topk):
    """``test_xlas_walk_is_the_checkpointed_walk`` under grouped keys."""
    from tests.test_dsa_attend_kernels import (
        check_against_the_checkpointed_walk)

    check_against_the_checkpointed_walk(
        _walk_inputs(2, s), dict(scale=0.3, topk=topk, block=block,
                                 tiers=tiers), exact=True)


def test_the_grouped_kernels_walk_is_the_checkpointed_walk(kernels):
    """Both pairs of kernels under the rule, grouped keys, blocks of 32
    queries in two tiers of two blocks, against the checkpointed walk of
    XLA's forms."""
    from tests.test_dsa_attend_kernels import (
        check_against_the_checkpointed_walk)

    kernels(32, 16, attend=(64, 32))
    check_against_the_checkpointed_walk(
        _walk_inputs(2, 128), dict(scale=0.2, topk=24, block=32, tiers=2),
        exact=False)


def test_the_grouped_gradient_runs_each_forward_call_once_a_tier(kernels):
    """The jaxpr of the grouped walk's gradient, two tiers: the grouped
    forward call once a tier, bare and under a layer's policy that holds
    ``KEPT_NAMES``; twice under a policy that holds nothing; the per-head
    calls never."""
    from jax.ad_checkpoint import checkpoint_policies as policies

    from tests.dsa_reference import mosaic_calls
    from tests.test_dsa_attend_kernels import _gradient_jaxpr

    kernels(32, 16, attend=(64, 32))
    args = _walk_inputs(1, 128)
    how = dict(scale=0.2, topk=24, block=32, tiers=2)
    assert mosaic_calls(_gradient_jaxpr(args, **how)) == {
        "dsa_attend_gqa_fwd": 2, "dsa_attend_gqa_bwd": 2,
        "dsa_scores_fwd": 4, "dsa_scores_bwd": 2}
    held = mosaic_calls(_gradient_jaxpr(
        args, policies.save_only_these_names(*dsa.KEPT_NAMES), **how))
    bare = mosaic_calls(_gradient_jaxpr(
        args, policies.nothing_saveable, **how))
    assert held["dsa_attend_gqa_fwd"] == 2 and bare[
        "dsa_attend_gqa_fwd"] == 4
    assert held["dsa_attend_gqa_bwd"] == bare["dsa_attend_gqa_bwd"] == 2
