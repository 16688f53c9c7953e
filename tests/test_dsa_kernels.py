"""The index's scores and the attention over its choice as kernels
(``ops/dsa.score_kernels``: the Mosaic calls ``dsa_scores_fwd`` and
``dsa_scores_bwd``; ``ops/dsa.attend_kernels``: ``dsa_attend_fwd`` and
``dsa_attend_bwd``) through the Pallas interpreter, against XLA's forms of
the same equations (``plain_scores``, ``plain_attend``), alone and inside
the walk, and which form a call takes."""

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
    blocks of 256, the walk blocks of 128)."""
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
        n0 = len(tracing.chrome_events())
        jax.eval_shape(functools.partial(
            dsa.sparse_attention, scale=0.3, topk=8, block=32, tiers=2),
            *args)
        return [e["args"] for e in tracing.chrome_events()[n0:]
                if e["name"] == "rtpu.dsa.shapes"]

    kernels(32, 16)
    (said,) = spans()
    assert (said["block"], said["tiers"], said["scores_form"],
            said["scores_tile"]) == (32, 2, "kernel", 32)
    kernels(32, 64)         # a block is not whole chunks of 64 queries
    (said,) = spans()
    assert (said["scores_form"], said["scores_tile"]) == ("xla", None)


# ---- attention over the choice

WIDTHS = (32, 16, 32)           # d_n, d_r, d_v at 32 lanes


def _block(n, keys, heads=2, widths=WIDTHS, seed=5, dtype=jnp.float32):
    """A block's q [n, H, d_n + d_r], and k_n, v [keys, H, .], k_r [keys,
    d_r] of the tier it attends over."""
    dn, dr, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((n, heads, dn + dr), (keys, heads, dn), (keys, heads, dv),
              (keys, dr))
    return tuple(jax.random.normal(k, shape, jnp.float32).astype(dtype)
                 for k, shape in zip(ks, shapes))


def _a_choice(n, keys, first, topk, seed=6):
    return dsa.choose(jax.random.normal(jax.random.PRNGKey(seed), (n, keys),
                                        jnp.float32), first, topk)


def _xla(chosen, scale=0.2):
    """(q, k_n, v, k_r) -> (out [n, H, d_v], sum_h p [n, keys])."""
    def attend(q, kn, v, kr):
        out, p = dsa.plain_attend(q, kn, v, kr, chosen, scale)
        return out, p.sum(0)

    return attend


def _kernel(chosen, first, tile, scale=0.2):
    """The same through ``attend_kernels``, which takes and hands out
    arrays that lie heads first."""
    def attend(q, kn, v, kr):
        out, p = dsa.attend_kernels(
            *(jnp.swapaxes(x, 0, 1) for x in (q, kn, v)), kr, chosen, first,
            scale, tile)
        return jnp.transpose(out, (2, 0, 1)), p

    return attend


def _gradients(attend, args, g):
    return jax.grad(lambda *a: (attend(*a)[0].astype(jnp.float32) * g).sum(),
                    argnums=(0, 1, 2, 3))(*args)


GRADS = ("dq", "dk_n", "dv", "dk_r")


@pytest.mark.parametrize("n,keys,tile,rows,heads,lanes,widths", [
    (32, 128, 64, 16, 2, 32, WIDTHS),
    (64, 192, 64, 32, 3, 32, WIDTHS),
    (32, 96, 96, 32, 2, 32, WIDTHS),
    (64, 256, 128, 64, 4, 32, WIDTHS),
    (128, 256, 128, 128, 2, 128, (128, 64, 128))],
    ids=["32-by-tiles-of-64", "three-heads-three-tiles", "one-tile-of-96",
         "four-heads-chunks-of-64", "real-lanes"])
def test_attend_kernels_match_xlas_form(n, keys, tile, rows, heads, lanes,
                                        widths, kernels):
    """``out``, the heads' summed probabilities and the four gradients
    against XLA's form and ``jax.grad`` of it, float32 at the highest
    matmul precision, the block's queries the last of the keys' positions,
    over queries, keys, the keys a grid step takes, the keys a head's
    products take at a time and the heads."""
    kernels(tile, rows, lanes, attend=(tile, rows))
    assert dsa.attend_plan(n, keys, widths[0], widths[2]) == {
        "attend_form": "kernel", "attend_tile": tile}
    args = _block(n, keys, heads, widths)
    first = keys - n
    chosen = _a_choice(n, keys, first, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, heads, widths[2]))
    with jax.default_matmul_precision("highest"):
        want = _xla(chosen)(*args)
        got = _kernel(chosen, jnp.int32(first), tile)(*args)
        wants = _gradients(_xla(chosen), args, g)
        gots = _gradients(_kernel(chosen, jnp.int32(first), tile), args, g)
    for name, a, b in zip(("out", "p_sum") + GRADS, got + gots,
                          want + wants):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    assert not np.asarray(got[1])[~np.asarray(chosen)].any()


@pytest.mark.parametrize("n,keys,tile,rows,heads", [
    (32, 128, 64, 32, 2), (64, 256, 128, 32, 4)],
    ids=["two-heads", "four-heads"])
def test_attend_kernels_take_bfloat16_as_xla_does(n, keys, tile, rows, heads,
                                                  kernels):
    """bfloat16 operands: the products are the arrays' as they are, float32
    sums and a float32 softmax, ``p`` cast before PV and ``dS`` before its
    products, so everything is XLA's to bfloat16's rounding."""
    kernels(tile, rows, attend=(tile, rows))
    args = _block(n, keys, heads, dtype=jnp.bfloat16)
    first = keys - n
    chosen = _a_choice(n, keys, first, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, heads, WIDTHS[2]))
    want = _xla(chosen)(*args)
    got = _kernel(chosen, jnp.int32(first), tile)(*args)
    wants = _gradients(_xla(chosen), args, g)
    gots = _gradients(_kernel(chosen, jnp.int32(first), tile), args, g)
    for name, a, b in zip(("out", "p_sum") + GRADS, got + gots,
                          want + wants):
        assert a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-2, name


def test_rows_with_few_keys_or_all_in_the_last_tile_hold_no_nan(kernels):
    """The block at the sequence's start (a row sees fewer keys than
    ``topk``, the first one key) and a block whose row 5 chose keys of its
    last tile alone (its running maximum stands at the mask's fill through
    every tile before): finite everywhere, no weight on an unchosen key, a
    row's summed probabilities the number of heads."""
    kernels(64, 16, attend=(64, 16))
    n, keys, heads = 32, 256, 2
    args = _block(n, keys, heads)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, heads, WIDTHS[2]))
    late = _a_choice(n, keys, keys - n, 24)
    late = late.at[5].set(jnp.arange(keys) >= keys - 40
                          ) & (jnp.arange(keys)[None]
                               <= keys - n + jnp.arange(n)[:, None])
    assert not late[5, :keys - 64].any() and late[5].any()
    for first, chosen in ((0, _a_choice(n, keys, 0, 24)), (keys - n, late)):
        assert int(chosen[0].sum()) == (1 if first == 0 else 24)
        with jax.default_matmul_precision("highest"):
            want = _xla(chosen)(*args)
            got = _kernel(chosen, jnp.int32(first), 64)(*args)
            wants = _gradients(_xla(chosen), args, g)
            gots = _gradients(_kernel(chosen, jnp.int32(first), 64), args, g)
        for name, a, b in zip(("out", "p_sum") + GRADS, got + gots,
                              want + wants):
            assert np.isfinite(np.asarray(a)).all(), name
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                       err_msg=name)
        assert not np.asarray(got[1])[~np.asarray(chosen)].any()
        np.testing.assert_allclose(got[1].sum(-1), heads, rtol=1e-5)


@pytest.mark.parametrize("first", [0, 40, 100, 224])
def test_tiles_past_the_last_query_are_not_read_by_the_attention(first,
                                                                 kernels):
    """Told where its queries stand, the kernel visits the tiles that hold
    a causal pair and no other: keys and values of NaN past them reach
    neither ``out``, the heads' sum (zeros there) nor a gradient (zeros for
    those keys), and what it visits reads as XLA's form on clean arrays."""
    kernels(64, 32, attend=(64, 32))
    n, keys, heads = 32, 256, 2
    seen = -(-(first + n) // 64) * 64
    q, kn, v, kr = _block(n, keys, heads)
    chosen = _a_choice(n, keys, first, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, heads, WIDTHS[2]))
    fill = (jnp.arange(keys) >= seen)
    dirty = tuple(jnp.where(fill.reshape((-1,) + (1,) * (x.ndim - 1)),
                            jnp.nan, x) for x in (kn, v, kr))
    with jax.default_matmul_precision("highest"):
        want = _xla(chosen)(q, kn, v, kr)
        wants = _gradients(_xla(chosen), (q, kn, v, kr), g)
        attend = _kernel(chosen, jnp.int32(first), 64)
        got = attend(q, dirty[0], dirty[1], dirty[2])
        gots = _gradients(attend, (q, dirty[0], dirty[1], dirty[2]), g)
    for name, a, b in zip(("out", "p_sum") + GRADS, got + gots,
                          want + wants):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)
    np.testing.assert_array_equal(got[1][:, seen:], 0.0)
    for dk in gots[1:]:
        np.testing.assert_array_equal(dk[seen:], 0.0)


@pytest.mark.parametrize("tile,rows", [(32, 32), (128, 64), (256, 32)])
def test_a_blocks_attention_does_not_depend_on_the_tile(tile, rows, kernels):
    """64 queries over 256 keys under tiles of 64 and under other tiles and
    chunks: the same out, summed probabilities and gradients to float32's
    rounding (the sums over the tiles run in another order)."""
    n, keys = 64, 256
    args = _block(n, keys, 3)
    chosen = _a_choice(n, keys, keys - n, 24)
    g = jax.random.normal(jax.random.PRNGKey(7), (n, 3, WIDTHS[2]))

    def read(tile, rows):
        kernels(tile, rows, attend=(tile, rows))
        assert dsa.attend_plan(n, keys, 32, 32)["attend_tile"] == tile
        attend = _kernel(chosen, jnp.int32(keys - n), tile)
        with jax.default_matmul_precision("highest"):
            return attend(*args) + _gradients(attend, args, g)

    for name, a, b in zip(("out", "p_sum") + GRADS, read(tile, rows),
                          read(64, 16)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)


def _wide_inputs(b, s, H=2, seed=3):
    """``_attention_inputs`` at widths the attention's kernels take under
    32 lanes."""
    dn, dr, dv = WIDTHS
    return _attention_inputs(b, s, H=H, dn=dn, dr=dr, dv=dv, seed=seed)


def test_the_walk_with_both_kernels_is_the_walk_with_xlas_forms(
        kernels, monkeypatch):
    """Two rows of 256 positions, blocks of 32 queries in two tiers, tiles
    of 64 keys (most blocks skip tiles), scores and attention through their
    kernels, against the walk with XLA's forms: the same choice bit for
    bit, the same output, pairs and term, and the gradients of both to all
    seven inputs."""
    args = _wide_inputs(2, 256)
    how = dict(scale=0.2, topk=24, block=32, tiers=2)
    g = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def walk(*a, **more):
        return dsa.sparse_attention(*a, **how, **more)

    def loss(*a):
        out, kl, _ = walk(*a)
        return (out * g).sum() + kl.sum()

    with jax.default_matmul_precision("highest"):
        want = walk(*args, keep_choice=True)
        want_grads = jax.grad(loss, argnums=tuple(range(7)))(*args)
        kernels(32, 16, attend=(64, 32))
        seen = []
        real = dsa.attend_kernels
        # as the walk passes them: (.., chosen, first, scale, tile, v_t)
        monkeypatch.setattr(dsa, "attend_kernels",
                            lambda *a: seen.append(a[5:8]) or real(*a))
        got = walk(*args, keep_choice=True)
        got_grads = jax.grad(loss, argnums=tuple(range(7)))(*args)
    assert seen and all(first is not None and tile == 64
                        for first, _, tile in seen)
    np.testing.assert_array_equal(got[3], want[3])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for name, a, b in zip(("dq", "dk_n", "dv", "dk_r", "dq_i", "dk_i", "dw"),
                          got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5, err_msg=name)


def test_a_replaced_target_and_choice_are_what_the_kernel_form_calls(
        kernels, monkeypatch):
    """``benchmark/tests/sparse_limits.py`` plants faults by replacing
    ``dsa.kl_target`` and ``dsa.choose`` while the program traces: the walk
    calls what stands under those names where the attention runs as
    kernels too, the target on the heads' summed probabilities with a
    leading axis of one (``p.sum(0)`` of it is the array)."""
    kernels(32, 16, attend=(64, 32))
    args = _wide_inputs(1, 128)
    how = dict(scale=0.2, topk=8, block=32, tiers=2)
    honest = dsa.sparse_attention(*args, **how)
    targets, choices = [], []
    real_choose = dsa.choose

    def unnormalised(p):
        targets.append(p.shape)
        return jax.lax.stop_gradient(p.sum(0))

    def every_causal_key(scores, first_q, topk):
        choices.append(scores.shape)
        return real_choose(scores, first_q, scores.shape[-1])

    monkeypatch.setattr(dsa, "kl_target", unnormalised)
    planted = dsa.sparse_attention(*args, **how)
    assert targets and all(shape[0] == 1 and len(shape) == 3
                           for shape in targets)
    # the heads' sum is H times a distribution: the term moves, out not
    np.testing.assert_allclose(planted[0], honest[0], rtol=1e-6, atol=1e-6)
    assert not np.allclose(planted[1], honest[1])
    monkeypatch.setattr(dsa, "choose", every_causal_key)
    dense = dsa.sparse_attention(*args, **how)
    assert choices and int(dense[2][0]) == 128 * 129 // 2
    assert not np.allclose(dense[0], honest[0])


@pytest.mark.parametrize("backend,n,keys,d_n,d_v,tile", [
    ("cpu", 128, 4096, 128, 128, None), ("tpu", 128, 4096, 128, 128, 512),
    ("tpu", 256, 16384, 128, 128, 512), ("tpu", 128, 384, 128, 128, 384),
    ("tpu", 128, 640, 128, 128, 128), ("tpu", 128, 200, 128, 128, None),
    ("tpu", 96, 512, 128, 128, None), ("tpu", 128, 512, 192, 128, None),
    ("tpu", 128, 512, 128, 64, None), ("tpu", 16, 48, 8, 8, None)],
    ids=["the-cpu", "whole-tiles", "a-block-of-256", "a-tile-of-384",
         "five-tiles-of-128", "keys-off-the-lanes", "ragged-queries",
         "keys-of-192-lanes", "values-of-64-lanes", "tiny"])
def test_the_attentions_form_is_read_from_the_backend_and_the_shapes(
        backend, n, keys, d_n, d_v, tile, monkeypatch):
    """``attend_plan`` at the module's own constants; where it says "xla"
    the walk attends through ``plain_attend`` and traces no kernel (a key
    count that is not whole tiles among them)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dsa.attend_plan(n, keys, d_n, d_v) == {
        "attend_form": "kernel" if tile else "xla", "attend_tile": tile}
    if backend == "tpu" and keys == 200:
        monkeypatch.setattr(dsa, "attend_kernels", None)    # never reached
        monkeypatch.setattr(dsa, "score_kernels", None)
        args = _attention_inputs(1, 200, dn=128, dr=64, dv=128)
        out, kl, pairs = dsa.sparse_attention(*args, scale=0.1, topk=16,
                                              block=100, tiers=1)
        assert np.isfinite(np.asarray(out)).all() and int(pairs[0]) > 0


def test_the_span_says_which_form_attended(kernels):
    """``rtpu.dsa.shapes`` carries the attention's form and tile beside the
    scores': the kernels' where the backend and the shapes take them,
    XLA's where they do not, each on its own."""
    from ray_tpu.util import tracing

    args = _wide_inputs(1, 128)

    def span():
        n0 = len(tracing.chrome_events())
        jax.eval_shape(functools.partial(
            dsa.sparse_attention, scale=0.3, topk=8, block=32, tiers=2),
            *args)
        (said,) = [e["args"] for e in tracing.chrome_events()[n0:]
                   if e["name"] == "rtpu.dsa.shapes"]
        return tuple(said[k] for k in ("scores_form", "scores_tile",
                                       "attend_form", "attend_tile"))

    kernels(32, 16, attend=(64, 32))
    assert span() == ("kernel", 32, "kernel", 64)
    kernels(32, 16, attend=(16, 16))    # no tile of whole lanes up to 16
    assert span() == ("kernel", 32, "xla", None)
    kernels(32, 64, attend=(32, 32))    # nor one of the scores' 64
    assert span() == ("xla", None, "kernel", 32)
