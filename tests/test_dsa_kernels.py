"""The index's scores as kernels (``ops/dsa.score_kernels``: the Mosaic
calls ``dsa_scores_fwd`` and ``dsa_scores_bwd``) through the Pallas
interpreter, against XLA's form of the same equations (``plain_scores``),
alone and inside the walk, and which form a call takes. The attention over
the choice: ``test_dsa_attend_kernels.py``."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import dsa  # noqa: E402


HEADS, DIM = 4, 32


@pytest.fixture
def kernels(monkeypatch):
    """``index_scores`` and the walk's attention take their kernel paths,
    the Pallas interpreter in Mosaic's place: ``kernels(tile, rows, lanes)``
    sets the scores' kernels' three constants, ``attend=(tile, rows)`` the
    attention's two."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dsa, "score_kernels", functools.partial(
        dsa.score_kernels, interpret=True))
    monkeypatch.setattr(dsa, "attend_kernels", functools.partial(
        dsa.attend_kernels, interpret=True))
    monkeypatch.setattr(dsa, "attend_kernels_grouped", functools.partial(
        dsa.attend_kernels_grouped, interpret=True))

    def constants(tile, rows, lanes=32, attend=None):
        monkeypatch.setattr(dsa, "SCORE_TILE", tile)
        monkeypatch.setattr(dsa, "SCORE_ROWS", rows)
        monkeypatch.setattr(dsa, "KERNEL_LANES", lanes)
        if attend:
            monkeypatch.setattr(dsa, "ATTEND_TILE", attend[0])
            monkeypatch.setattr(dsa, "ATTEND_ROWS", attend[1])

    return constants


def _inputs(n, keys, seed=0, dtype=jnp.float32, heads=HEADS, dim=DIM):
    """q_i [n, J, d], k_i [keys, d], head weights of both signs [n, J]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = jax.random.normal(ks[2], (n, heads), jnp.float32)
    assert (w > 0).any() and (w < 0).any()
    return (jax.random.normal(ks[0], (n, heads, dim), jnp.float32
                              ).astype(dtype),
            jax.random.normal(ks[1], (keys, dim), jnp.float32).astype(dtype),
            w * (heads * dim) ** -0.5)


def _planted(scores, first, topk, seed=1):
    """A float32 cotangent that is zero off ``choose``'s pairs."""
    chosen = dsa.choose(scores, first, topk)
    return jnp.where(chosen, jax.random.normal(
        jax.random.PRNGKey(seed), scores.shape, jnp.float32), 0.0)


@pytest.mark.parametrize("n,keys,tile,rows,lanes,dim", [
    (128, 256, 64, 64, 32, DIM),
    (128, 384, 128, 32, 32, DIM),
    (128, 96, 96, 128, 32, DIM),
    (256, 256, 128, 64, 32, DIM),
    (256, 512, 64, 128, 32, DIM),
    (128, 256, 256, 16, 128, 128)],
    ids=["128-by-tiles-of-64", "128-by-three-tiles-of-128",
         "128-in-one-chunk-one-tile", "256-by-tiles-of-128",
         "256-in-two-chunks-of-128", "real-lanes"])
def test_score_kernels_match_xlas_form(n, keys, tile, rows, lanes, dim,
                                       kernels):
    """Forward and the three gradients (a cotangent that is zero off a
    planted choice) against XLA's form and ``jax.grad`` of it, float32 at
    the highest matmul precision, over blocks of 128 and 256 queries, the
    keys a grid step takes, the queries a chunk takes and head weights of
    both signs."""
    kernels(tile, rows, lanes)
    args = _inputs(n, keys, dim=dim)
    assert dsa.scores_plan(n, keys, HEADS, dim) == {
        "scores_form": "kernel", "scores_tile": tile}
    with jax.default_matmul_precision("highest"):
        want = dsa.plain_scores(*args)
        got = dsa.index_scores(*args)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        g = _planted(want, keys - n, 16)
        wants = jax.grad(lambda *a: (dsa.plain_scores(*a) * g).sum(),
                         argnums=(0, 1, 2))(*args)
        gots = jax.grad(lambda *a: (dsa.index_scores(*a) * g).sum(),
                        argnums=(0, 1, 2))(*args)
    for name, a, b in zip(("dq_i", "dk_i", "dw"), gots, wants):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def test_score_kernels_take_bfloat16_as_xla_does(kernels):
    """bfloat16 queries and keys: the products are the arrays' as they
    are, float32 sums, so the scores are XLA's to float32 rounding; the
    gradients, whose ``y`` goes to the MXU in bfloat16, to bfloat16's."""
    kernels(64, 64)
    args = _inputs(128, 256, dtype=jnp.bfloat16)
    want = dsa.plain_scores(*args)
    np.testing.assert_allclose(dsa.index_scores(*args), want, rtol=1e-5,
                               atol=1e-5)
    g = _planted(want, 128, 16)
    wants = jax.grad(lambda *a: (dsa.plain_scores(*a) * g).sum(),
                     argnums=(0, 1, 2))(*args)
    gots = jax.grad(lambda *a: (dsa.index_scores(*a) * g).sum(),
                    argnums=(0, 1, 2))(*args)
    for name, a, b in zip(("dq_i", "dk_i", "dw"), gots, wants):
        assert a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-2, name


@pytest.mark.parametrize("tile,rows", [(64, 64), (128, 32), (256, 128)])
def test_a_pairs_score_does_not_depend_on_the_block_that_scored_it(
        tile, rows, kernels):
    """256 queries as one block, as two blocks of 128, and under other
    tiles and chunks: bit for bit the same scores (the cell's check scores
    blocks of 256, as the walk does)."""
    q_i, k_i, w = _inputs(256, 512, dtype=jnp.bfloat16)
    kernels(128, 64)
    whole = dsa.index_scores(q_i, k_i, w)
    kernels(tile, rows)
    halves = jnp.concatenate([dsa.index_scores(q_i[:128], k_i, w[:128]),
                              dsa.index_scores(q_i[128:], k_i, w[128:])])
    np.testing.assert_array_equal(whole, halves)


@pytest.mark.parametrize("first", [0, 64, 100, 384])
def test_tiles_past_the_last_query_are_zeros_and_the_rest_untouched(
        first, kernels):
    """Told where its queries stand, the kernel scores every tile that
    holds a causal pair as it would have, and writes zeros in the others;
    backward, a cotangent planted in a skipped tile moves no gradient."""
    kernels(64, 64)
    n, keys = 128, 512
    args = _inputs(n, keys)
    seen = -(-(first + n) // 64) * 64
    with jax.default_matmul_precision("highest"):
        whole = dsa.index_scores(*args)
        got = dsa._scores(*args, jnp.int32(first))
        np.testing.assert_array_equal(got[:, :seen], whole[:, :seen])
        np.testing.assert_array_equal(got[:, seen:], 0.0)
        g = jax.random.normal(jax.random.PRNGKey(2), (n, keys), jnp.float32)
        causal = jnp.arange(keys)[None] <= first + jnp.arange(n)[:, None]
        wants = jax.grad(lambda *a: jnp.where(
            causal, dsa.plain_scores(*a) * g, 0.0).sum(), argnums=(0, 1, 2)
        )(*args)
        # the fill's cotangent is not masked: the kernel must not read it
        fill = jnp.arange(keys)[None] >= seen
        gots = jax.grad(lambda *a: jnp.where(
            causal | fill, dsa._scores(*a, jnp.int32(first)) * g, 0.0).sum(),
            argnums=(0, 1, 2))(*args)
    for name, a, b in zip(("dq_i", "dk_i", "dw"), gots, wants):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def _attention_inputs(b, s, H=2, dn=8, dr=4, dv=8, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    f32 = jnp.float32
    return (jax.random.normal(ks[0], (b, s, H, dn + dr), f32),
            jax.random.normal(ks[1], (b, s, H, dn), f32),
            jax.random.normal(ks[2], (b, s, H, dv), f32),
            jax.random.normal(ks[3], (b, s, dr), f32),
            jax.random.normal(ks[4], (b, s, HEADS, DIM), f32),
            jax.random.normal(ks[5], (b, s, DIM), f32),
            jax.random.normal(ks[6], (b, s, HEADS), f32) * 0.1)


def test_a_skipped_tiles_fill_is_seen_by_neither_the_choice_nor_the_term(
        kernels, monkeypatch):
    """The walk with the kernels (two rows of 256 positions, blocks of 32
    queries in two tiers, tiles of 32 keys: most blocks skip tiles)
    against the walk with XLA's form, which scores every pair: the same
    choice bit for bit, the same output, pairs and term, and the term's
    gradient to the index's three inputs."""
    args = _attention_inputs(2, 256)
    how = dict(scale=0.3, topk=24, block=32, tiers=2)

    def walk(*a, **more):
        return dsa.sparse_attention(*a, **how, **more)

    def term(q_i, k_i, w):
        return walk(*args[:4], q_i, k_i, w)[1].sum()

    with jax.default_matmul_precision("highest"):
        want = walk(*args, keep_choice=True)
        want_grads = jax.grad(term, argnums=(0, 1, 2))(*args[4:])
        kernels(32, 16)
        seen = []
        real = dsa.score_kernels
        monkeypatch.setattr(dsa, "score_kernels",
                            lambda *a: seen.append(a[3]) or real(*a))
        got = walk(*args, keep_choice=True)
        got_grads = jax.grad(term, argnums=(0, 1, 2))(*args[4:])
    assert seen and all(first is not None for first in seen)
    np.testing.assert_array_equal(got[3], want[3])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for name, a, b in zip(("dq_i", "dk_i", "dw"), got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5, err_msg=name)


def test_a_replaced_index_scores_is_what_the_walk_calls(kernels,
                                                        monkeypatch):
    """``benchmark/tests/sparse_limits.py`` plants a fault by replacing
    ``dsa.index_scores`` while the program traces: the walk calls what
    stands under that name, on a backend that takes the kernels too."""
    kernels(32, 16)
    args = _attention_inputs(1, 128)
    calls = []

    def planted(q_i, k_i, w):
        calls.append(q_i.shape)
        return dsa.plain_scores(q_i, k_i, -w)

    honest = dsa.sparse_attention(*args, scale=0.3, topk=8, block=32,
                                  tiers=2)
    monkeypatch.setattr(dsa, "index_scores", planted)
    monkeypatch.setattr(dsa, "score_kernels", None)     # never reached
    faulty = dsa.sparse_attention(*args, scale=0.3, topk=8, block=32,
                                  tiers=2)
    assert calls and not np.allclose(honest[0], faulty[0])


@pytest.mark.parametrize("backend,n,keys,dim,tile", [
    ("cpu", 128, 512, 128, None), ("tpu", 128, 512, 128, 512),
    ("tpu", 256, 16384, 128, 512), ("tpu", 128, 384, 128, 384),
    ("tpu", 128, 640, 128, 128), ("tpu", 128, 200, 128, None),
    ("tpu", 100, 512, 128, None), ("tpu", 128, 512, 96, None),
    ("tpu", 16, 48, 16, None)],
    ids=["the-cpu", "whole-tiles", "the-checks-block", "a-tile-of-384",
         "five-tiles-of-128", "keys-off-the-lanes", "ragged-queries",
         "a-head-off-the-lanes", "tiny"])
def test_the_form_is_read_from_the_backend_and_the_shapes(
        backend, n, keys, dim, tile, monkeypatch):
    """``scores_plan`` at the module's own constants; where it says "xla"
    ``index_scores`` is ``plain_scores`` and no kernel is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    plan = dsa.scores_plan(n, keys, 64, dim)
    assert plan == {"scores_form": "kernel" if tile else "xla",
                    "scores_tile": tile}
    if tile is None and keys < 1024:
        monkeypatch.setattr(dsa, "score_kernels", None)
        args = _inputs(n, keys, heads=2, dim=dim)
        np.testing.assert_array_equal(dsa.index_scores(*args),
                                      dsa.plain_scores(*args))


def test_the_span_says_which_form_scored(kernels):
    """``rtpu.dsa.shapes`` carries the form and the tile beside ``block``
    and ``tiers``: the kernels' where the backend and the shapes take
    them, XLA's on the CPU."""
    from ray_tpu.util import tracing

    args = _attention_inputs(1, 128)

    def spans():
        here = tracing.since()
        jax.eval_shape(functools.partial(
            dsa.sparse_attention, scale=0.3, topk=8, block=32, tiers=2),
            *args)
        return [e["args"] for e in here.events()
                if e["name"] == "rtpu.dsa.shapes"]

    kernels(32, 16)
    (said,) = spans()
    assert (said["block"], said["tiers"], said["scores_form"],
            said["scores_tile"]) == (32, 2, "kernel", 32)
    kernels(32, 64)         # a block is not whole chunks of 64 queries
    (said,) = spans()
    assert (said["scores_form"], said["scores_tile"]) == ("xla", None)


# ---- every Mosaic call of the walk is traced once a distinct shape, and
# what a control plants above the cache still takes effect (PR 58)


def test_two_equal_layers_trace_each_kernel_body_once_a_shape(kernels,
                                                              monkeypatch):
    """A step of two layers of the same shapes, each the walk under a
    layer's ``jax.checkpoint`` that holds nothing (so the forward is traced
    as the rule's primal, as its ``fwd`` and in the layer's recomputation),
    two tiers: each of the four kernel bodies is traced once a tier, twice
    in all, whoever calls it."""
    from tests.dsa_reference import clear_call_caches

    kernels(32, 16, attend=(64, 32))
    clear_call_caches()
    traced = {}
    for name in ("_scores_fwd_kernel", "_scores_bwd_kernel",
                 "_attend_fwd_kernel", "_attend_bwd_kernel"):
        def body(*a, _real=getattr(dsa, name), _name=name, **k):
            traced[_name] = traced.get(_name, 0) + 1
            return _real(*a, **k)
        monkeypatch.setattr(dsa, name, body)
    args = _attention_inputs(1, 128, dn=32, dr=16, dv=32)

    @jax.checkpoint
    def layer(q, *rest):
        o, kl, _ = dsa.sparse_attention(q, *rest, scale=0.2, topk=24,
                                        block=32, tiers=2)
        return q + jnp.pad(o, ((0, 0),) * 3 + ((0, 16),)), kl.sum()

    def step(q, *rest):
        q, kl_0 = layer(q, *rest)
        q, kl_1 = layer(q, *rest)
        return (q ** 2).sum() + kl_0 + kl_1

    jax.make_jaxpr(jax.grad(step, argnums=tuple(range(7))))(*args)
    assert traced == {"_scores_fwd_kernel": 2, "_scores_bwd_kernel": 2,
                      "_attend_fwd_kernel": 2, "_attend_bwd_kernel": 2}
    clear_call_caches()


def test_a_plant_after_an_honest_trace_still_takes_effect(kernels,
                                                          monkeypatch):
    """``benchmark/tests/sparse_limits.py`` traces the honest program and
    then, in the same process, a program with ``dsa.index_scores`` or
    ``dsa.choose`` replaced: the kernels' traces are cached below those
    names, so the second program is the planted one, forward and
    gradient (the term's gradient flows through what was planted)."""
    kernels(32, 16, attend=(64, 32))
    args = _attention_inputs(1, 128, dn=32, dr=16, dv=32)
    how = dict(scale=0.2, topk=8, block=32, tiers=2)

    def read():
        def loss(*a):
            o, kl, _ = dsa.sparse_attention(*a, **how)
            return (o ** 2).sum() + kl.sum()

        return jax.jit(jax.value_and_grad(loss, argnums=(4, 5, 6)))(*args)

    honest, again = read(), read()
    np.testing.assert_array_equal(honest[0], again[0])
    real = dsa.choose
    monkeypatch.setattr(dsa, "choose", lambda scores, first, topk: real(
        scores, first, scores.shape[-1]))
    every_key = read()
    assert not np.allclose(every_key[0], honest[0])
    monkeypatch.setattr(dsa, "choose", real)
    monkeypatch.setattr(dsa, "index_scores",
                        lambda q_i, k_i, w: dsa.plain_scores(q_i, k_i, -w))
    negated = read()
    assert not np.allclose(negated[0], honest[0])
    # the planted scores' own gradient: d/dw of the negated scores
    assert not np.allclose(negated[1][2], honest[1][2])
    monkeypatch.undo()
