"""The attention over the index's choice as kernels
(``ops/dsa.attend_kernels``: the Mosaic calls ``dsa_attend_fwd`` and
``dsa_attend_bwd``) through the Pallas interpreter, against XLA's form of
the same equations (``plain_attend``), alone and inside the walk, and
which form a call takes. The scores: ``test_dsa_kernels.py``."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import dsa  # noqa: E402
from tests.test_dsa_kernels import _attention_inputs, kernels  # noqa: E402


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
        out, _, p = dsa.attend_kernels(
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
                            lambda *a, **more: seen.append(a[5:8]) or real(*a, **more))
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


@pytest.mark.parametrize("block", [64, 128])
def test_the_walk_does_not_depend_on_its_block(block, kernels):
    """A row of 256 positions in two tiers through both kernels, blocks
    of 32 queries against blocks of 64 and of 128 (a tier in one block):
    the block is in no equation, so the choice is the same bit for bit,
    ``pairs`` too, and the output, the term and the gradients to all seven
    inputs are the same to float32's rounding (a key's gradient is summed
    over fewer, larger blocks)."""
    kernels(32, 16, attend=(64, 32))
    args = _wide_inputs(1, 256)
    g = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def read(block):
        how = dict(scale=0.2, topk=24, block=block, tiers=2)
        assert dsa.walk_plan(256, block, 2, dsa.Widths.of(
            *args[:3], args[4])) == (block, 2)

        def loss(*a):
            out, kl, _ = dsa.sparse_attention(*a, **how)
            return (out * g).sum() + kl.sum()

        with jax.default_matmul_precision("highest"):
            return (jax.jit(functools.partial(
                dsa.sparse_attention, **how, keep_choice=True))(*args),
                jax.jit(jax.grad(loss, argnums=tuple(range(7))))(*args))

    (got, got_grads), (want, want_grads) = read(block), read(32)
    np.testing.assert_array_equal(got[3], want[3])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)
    for name, a, b in zip(("dq", "dk_n", "dv", "dk_r", "dq_i", "dk_i", "dw"),
                          got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


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
        here = tracing.since()
        jax.eval_shape(functools.partial(
            dsa.sparse_attention, scale=0.3, topk=8, block=32, tiers=2),
            *args)
        (said,) = [e["args"] for e in here.events()
                   if e["name"] == "rtpu.dsa.shapes"]
        return tuple(said[k] for k in ("scores_form", "scores_tile",
                                       "attend_form", "attend_tile"))

    kernels(32, 16, attend=(64, 32))
    assert span() == ("kernel", 32, "kernel", 64)
    kernels(32, 16, attend=(16, 16))    # no tile of whole lanes up to 16
    assert span() == ("kernel", 32, "xla", None)
    kernels(32, 64, attend=(32, 32))    # nor one of the scores' 64
    assert span() == ("xla", None, "kernel", 32)


def test_the_span_says_what_the_guard_held_the_block_to(kernels,
                                                        monkeypatch):
    """``rtpu.dsa.shapes`` carries the largest VMEM need of the walk's
    calls at the block that ran and the block before the guard: equal
    where the calls fit under ``VMEM_CEILING``, and a smaller ``block``
    beside the ``block_asked`` where the ceiling stands under them."""
    from ray_tpu.util import tracing

    kernels(32, 16, attend=(64, 32))
    args = _wide_inputs(1, 256)
    widths = dsa.Widths.of(*args[:3], args[4])

    def span():
        here = tracing.since()
        jax.eval_shape(functools.partial(
            dsa.sparse_attention, scale=0.3, topk=8, block=128, tiers=2),
            *args)
        (said,) = [e["args"] for e in here.events()
                   if e["name"] == "rtpu.dsa.shapes"]
        return tuple(said[k] for k in ("block", "block_asked", "tiers",
                                       "vmem_need_bytes"))

    at = {b: max(dsa.walk_needs(b, 128, widths).values())
          for b in (32, 64, 128)}
    assert at[32] < at[64] < at[128]
    assert span() == (128, 128, 2, at[128])
    monkeypatch.setattr(dsa, "VMEM_CEILING", at[64])
    assert span() == (64, 128, 2, at[64])
    monkeypatch.setattr(dsa, "VMEM_CEILING", at[64] - 1)
    assert span() == (32, 128, 2, at[32])


# ---- the walk's rule of its own (PR 58): what a block's forward computed
# is kept, and the backward reads it


def _walk_loss(walk, args, g, **how):
    """(outputs with the choice, the gradients to every array of ``args``)
    of ``(o g).sum() + 0.3 kl.sum()`` through ``walk``, jitted."""
    at = tuple(i for i, x in enumerate(args) if x is not None)

    def loss(*a):
        full = list(args)
        for i, x in zip(at, a):
            full[i] = x
        o, kl, pairs, choice = walk(*full, **how)
        return (o * g).sum() + 0.3 * kl.sum(), (o, kl, pairs, choice)

    (_, said), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(at))), has_aux=True))(
            *(args[i] for i in at))
    return said, grads


def check_against_the_checkpointed_walk(args, how, exact):
    from tests.dsa_reference import checkpointed_walk

    g = jax.random.normal(jax.random.PRNGKey(9), args[2].shape[:2] + (
        args[0].shape[2], args[2].shape[3]))
    with jax.default_matmul_precision("highest"):
        want, want_grads = _walk_loss(checkpointed_walk, args, g, **how)
        got, got_grads = _walk_loss(functools.partial(
            dsa.sparse_attention, keep_choice=True), args, g, **how)
    np.testing.assert_array_equal(got[3], want[3])
    np.testing.assert_array_equal(got[2], want[2])
    tol = dict(rtol=1e-6, atol=1e-6) if exact else dict(rtol=1e-3, atol=1e-5)
    for a, b in zip(got[:2] + got_grads, want[:2] + want_grads):
        np.testing.assert_allclose(a, b, **tol)


@pytest.mark.parametrize("s,block,tiers,topk", [
    (36, 12, 3, 5), (48, 8, 2, 6), (40, 20, 2, 40)],
    ids=["a-ragged-byte-a-tier", "three-blocks-a-tier", "every-causal-key"])
def test_xlas_walk_is_the_checkpointed_walk(s, block, tiers, topk):
    """XLA's forms under the walk's own rule against the walk that
    checkpoints each block and lets jax differentiate it: the choice and
    the pairs bit for bit, the output, the term and all seven gradients to
    float32's last digits, where a tier's keys are no whole bytes of the
    packed choice (12 and 20 keys) and where a tier holds three blocks."""
    check_against_the_checkpointed_walk(
        _attention_inputs(2, s), dict(scale=0.3, topk=topk, block=block,
                                      tiers=tiers), exact=True)


def test_the_kernels_walk_is_the_checkpointed_walk(kernels):
    """Both pairs of kernels under the rule, blocks of 32 queries in two
    tiers of two blocks, tiles of 64 keys: against the checkpointed walk of
    XLA's forms."""
    kernels(32, 16, attend=(64, 32))
    check_against_the_checkpointed_walk(
        _wide_inputs(2, 128), dict(scale=0.2, topk=24, block=32, tiers=2),
        exact=False)


def _gradient_jaxpr(args, policy=None, **how):
    """The jaxpr of the gradient of a layer that is the walk (``named``)
    under ``jax.checkpoint(policy=)``, or bare."""
    at = tuple(i for i, x in enumerate(args) if x is not None)

    def layer(*a):
        full = list(args)
        for i, x in zip(at, a):
            full[i] = x
        o, kl, _ = dsa.sparse_attention(*full, **how, named=True)
        return (o ** 2).sum() + kl.sum()

    if policy is not None:
        layer = jax.checkpoint(layer, policy=policy)
    return jax.make_jaxpr(jax.grad(layer, argnums=tuple(range(len(at)))))(
        *(args[i] for i in at))


def test_the_gradient_runs_each_forward_call_once_a_tier(kernels):
    """The jaxpr of the walk's gradient, two rows of two tiers: the
    attention's forward call once a tier and row and its backward call once
    a tier and row; the scores' forward once a tier and row forward and
    once more where the index term's gradient forms the scores; one span
    says so (``block_forwards`` 1,
    ``kept_bytes_a_layer``: the packed choice [32 x 8 + 32 x 16 bytes a
    tier's rows], the log-sum-exp and the output of two rows)."""
    from jax.ad_checkpoint import checkpoint_policies as policies

    from ray_tpu.util import tracing
    from tests.dsa_reference import mosaic_calls

    kernels(32, 16, attend=(64, 32))
    args = _wide_inputs(2, 128)
    how = dict(scale=0.2, topk=24, block=32, tiers=2)
    here = tracing.since()
    assert mosaic_calls(_gradient_jaxpr(args, **how)) == {
        "dsa_attend_fwd": 4, "dsa_attend_bwd": 4, "dsa_scores_fwd": 8,
        "dsa_scores_bwd": 4}
    (said,) = [e["args"] for e in here.events()
               if e["name"] == "rtpu.dsa.shapes"]
    assert said["block_forwards"] == 1
    assert said["kept_bytes_a_layer"] == 2 * (
        64 * 8 + 64 * 16 + 128 * 2 * (4 + 32 * 4))
    # a layer's policy that holds the kept names runs no forward call for
    # its backward; one that holds nothing runs the forward once more
    held = mosaic_calls(_gradient_jaxpr(
        args, policies.save_only_these_names(*dsa.KEPT_NAMES), **how))
    assert (held["dsa_attend_fwd"], held["dsa_attend_bwd"]) == (4, 4)
    bare = mosaic_calls(_gradient_jaxpr(
        args, policies.nothing_saveable, **how))
    assert (bare["dsa_attend_fwd"], bare["dsa_attend_bwd"]) == (8, 4)


def test_xlas_form_says_it_forms_its_products_twice():
    """On the CPU the attention is XLA's: the rule keeps the choice alone
    and its backward differentiates ``plain_attend`` where it stands, so
    ``block_forwards`` reads 2 and the kept bytes are the packed choice."""
    from ray_tpu.util import tracing

    here = tracing.since()
    jax.eval_shape(functools.partial(
        dsa.sparse_attention, scale=0.3, topk=8, block=16, tiers=2),
        *_attention_inputs(1, 64))
    (said,) = [e["args"] for e in here.events()
               if e["name"] == "rtpu.dsa.shapes"]
    assert said["block_forwards"] == 2
    assert said["kept_bytes_a_layer"] == 32 * 4 + 32 * 8
