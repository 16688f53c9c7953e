"""The short convolutions (``ops/conv.py``): the gated short convolution
against a loop over taps, and the ``taps_silu`` kernels in ``interpret``
mode against causal taps."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _conv_by_loop(h, w_in, w_conv, w_out):
    """The operator as its equations read, one sequence and one position
    at a time: ``v_t = sum_j w_j u_{t - (L - 1) + j}``, zeros before 0."""
    h, w_in, w_conv, w_out = (np.asarray(a, np.float64)
                              for a in (h, w_in, w_conv, w_out))
    taps = w_conv.shape[1]
    out = np.zeros(h.shape[:2] + (w_out.shape[1],))
    for n in range(h.shape[0]):
        b, c, x = np.split(h[n] @ w_in, 3, axis=-1)
        u = b * x
        for t in range(h.shape[1]):
            v = sum(w_conv[:, j] * u[t - (taps - 1) + j]
                    for j in range(taps) if t - (taps - 1) + j >= 0)
            out[n, t] = (c[t] * v) @ w_out
    return out


def _conv_inputs(seq, batch=2, hidden=8):
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    return (jax.random.normal(ks[0], (batch, seq, hidden)),
            jax.random.normal(ks[1], (hidden, 3 * hidden)) / 3,
            jax.random.normal(ks[2], (hidden, 3)),
            jax.random.normal(ks[3], (hidden, hidden)) / 3)


@pytest.mark.parametrize("seq", [1, 2, 3, 64])
def test_gated_short_conv_matches_a_loop_over_taps(seq):
    """Outputs and all three weight gradients (and the input's) against
    the loop, float32 at 1e-5, at lengths shorter than the taps too."""
    from ray_tpu.ops.conv import gated_short_conv

    args = _conv_inputs(seq)
    with jax.default_matmul_precision("highest"):
        got = gated_short_conv(*args)
        np.testing.assert_allclose(np.asarray(got), _conv_by_loop(*args),
                                   rtol=1e-5, atol=1e-5)
        cot = jax.random.normal(jax.random.PRNGKey(9), got.shape)
        grads = jax.grad(lambda *a: (gated_short_conv(*a) * cot).sum(),
                         argnums=(0, 1, 2, 3))(*args)
    # the loop's gradient by central differences in float64, a few entries
    # of each argument
    rng = np.random.default_rng(seq)
    for which, g in enumerate(grads):
        base = [np.asarray(a, np.float64) for a in args]
        for _ in range(4):
            at = tuple(rng.integers(0, n) for n in base[which].shape)
            up, down = (list(base), list(base))
            for side, sign in ((up, 1e-4), (down, -1e-4)):
                side[which] = base[which].copy()
                side[which][at] += sign
            want = ((_conv_by_loop(*up) - _conv_by_loop(*down))
                    * np.asarray(cot, np.float64)).sum() / 2e-4
            assert abs(float(g[at]) - want) < 1e-5 * max(1.0, abs(want)), (
                which, at)


def test_gated_short_conv_keeps_the_sequences_of_a_batch_apart():
    """Two sequences in a batch: the second's first positions see zeros,
    not the first's last, in the output and in the gradient."""
    from ray_tpu.ops.conv import gated_short_conv

    h, *w = _conv_inputs(5)
    both = gated_short_conv(h, *w)
    for n in range(2):
        alone = gated_short_conv(h[n:n + 1], *w)
        np.testing.assert_array_equal(np.asarray(both[n]),
                                      np.asarray(alone[0]))
    # the second sequence's output does not depend on the first's input
    g = jax.grad(lambda h_: gated_short_conv(h_, *w)[1].sum())(h)
    assert float(jnp.abs(g[0]).max()) == 0.0 < float(jnp.abs(g[1]).max())


def test_gated_short_conv_is_float32_inside_and_bf16_outside():
    from ray_tpu.ops.conv import conv_mix

    bcx = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 24)
                            ).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 3)).astype(jnp.bfloat16)
    got = conv_mix(bcx, w)
    assert got.dtype == jnp.bfloat16
    want = conv_mix(bcx.astype(jnp.float32), w.astype(jnp.float32))
    # rounded once, at the end
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.bfloat16)
                                             .astype(jnp.float32)))


def test_short_conv_names_its_scopes_forward_and_backward():
    """``short_conv`` and the three scopes inside it, which
    ``benchmark/lib/hybrid_flops.py`` reads, on the operations of the
    forward and of its transpose."""
    from ray_tpu.ops.conv import gated_short_conv

    args = _conv_inputs(8)
    text = jax.jit(jax.grad(lambda *a: (gated_short_conv(*a) ** 2).sum(),
                            argnums=(0, 1, 2, 3))).lower(*args).as_text(
        debug_info=True)
    for scope in ("conv_in", "conv_mix", "conv_out"):
        assert f"jvp(short_conv)/{scope}" in text, scope
        assert f"transpose(jvp(short_conv))/{scope}" in text, scope


def _taps_silu_reference(u, w, bias, first, sizes):
    """``causal_taps`` + bias + silu in float32 on ``u [b, wide, s]``'s
    channels from ``first`` on, cut as ``taps_silu`` cuts them."""
    from ray_tpu.ops.conv import causal_taps

    x = jnp.swapaxes(u[:, first:first + w.shape[0]], 1, 2)
    y = jax.nn.silu(causal_taps(x.astype(jnp.float32), w)
                    + bias.astype(jnp.float32))
    return tuple(jnp.split(jnp.swapaxes(y, 1, 2), np.cumsum(sizes)[:-1],
                           axis=1))


@pytest.mark.parametrize("taps,dtype,seq,wide,first,sizes,rows,lanes", [
    (4, jnp.float32, 300, 448, 128, (128, 64, 64), 128, 64),
    (3, jnp.float32, 256, 96, 0, (96,), 128, 32),
    (4, jnp.bfloat16, 300, 160, 32, (64, 32, 32), 256, None),
    (3, jnp.bfloat16, 40, 64, 0, (32, 32), None, 16),
], ids=["f32-4taps-ragged-3parts", "f32-3taps-whole-blocks",
        "bf16-4taps-ragged-3parts", "bf16-3taps-short"])
def test_taps_silu_kernels_match_causal_taps(taps, dtype, seq, wide, first,
                                             sizes, rows, lanes,
                                             monkeypatch):
    """The kernel pair (``interpret=True``) against ``causal_taps`` + bias
    + silu in float32: every part's output and the gradients of ``u``,
    ``w`` and ``bias``. The cases hold a sequence that is not whole blocks
    (300 positions in blocks of 128 or 256: positions on both sides of
    every block edge are compared, and the tile after the last block is no
    position), one shorter than a block, channels in several blocks and in
    two or three parts behind an offset, 3 and 4 taps, float32 and bf16.
    The first ``taps - 1`` positions of a row see zeros and not the row
    before: row 1 run alone is bit-equal to row 1 of the pair."""
    from ray_tpu.ops import conv

    if rows:
        monkeypatch.setattr(conv, "TAPS_BLOCK_ROWS", rows)
    if lanes:
        monkeypatch.setattr(conv, "TAPS_BLOCK_CHANNELS", lanes)
    c = sum(sizes)
    k = jax.random.split(jax.random.PRNGKey(taps), 4)
    u = jax.random.normal(k[0], (2, wide, seq)).astype(dtype)
    w = (0.5 * jax.random.normal(k[1], (c, taps))).astype(dtype)
    bias = (0.1 * jax.random.normal(k[2], (c,))).astype(dtype)
    cts = jnp.split(jax.random.normal(k[3], (2, c, seq)),
                    np.cumsum(sizes)[:-1], axis=1)

    def kernel(u, w, bias):
        return conv.taps_silu(u, w, bias, first=first, sizes=sizes,
                              interpret=True)

    def loss(f):
        return lambda *a: sum((out.astype(jnp.float32) * ct).sum()
                              for out, ct in zip(f(*a), cts))

    got = jax.jit(kernel)(u, w, bias)
    want = _taps_silu_reference(u, w, bias, first, sizes)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for g, wv, n in zip(got, want, sizes):
        assert g.shape == (2, n, seq) and g.dtype == dtype
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(wv),
                                   rtol=tol, atol=tol)
    alone = jax.jit(kernel)(u[1:], w, bias)
    for a, g in zip(alone, got):
        assert jnp.array_equal(a[0], g[1])
    got_g = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(u, w, bias)
    want_g = jax.grad(loss(lambda *a: _taps_silu_reference(
        *a, first, sizes)), argnums=(0, 1, 2))(
        *(a.astype(jnp.float32) for a in (u, w, bias)))
    for name, g, wv in zip(("u", "w", "bias"), got_g, want_g):
        assert g.dtype == dtype, name
        scale = float(jnp.abs(wv).max())
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(wv),
                                   rtol=tol, atol=tol * scale, err_msg=name)
    # no gradient to the channels beside the taps'
    beside = jnp.concatenate([got_g[0][:, :first], got_g[0][:, first + c:]],
                             axis=1)
    assert not beside.size or float(jnp.abs(beside).max()) == 0.0
