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


# (seq_q, seq_k, block_q, block_k): a query block's loop meets tiles the
# band's far edge cuts, interior tiles and tiles the diagonal cuts; the
# cases below have each kind somewhere and lack it elsewhere (counted in
# test_window_cases_meet_every_kind_of_tile)
_WINDOW_SHAPES = {"square": (64, 64, 16, 16), "keys-ahead": (32, 64, 16, 32),
                  "wide-q-blocks": (64, 64, 32, 16)}


@pytest.mark.parametrize("window,shape", [
    (8, "square"), (16, "square"), (40, "square"), (56, "square"),
    (40, "keys-ahead"), (24, "wide-q-blocks")],
    ids=["under-a-block", "a-block", "over-a-block", "several-blocks",
         "keys-ahead", "wide-q-blocks"])
@pytest.mark.parametrize("group", [6, 9])
def test_flash_attention_window_matches_reference(group, window, shape):
    """A sliding window (a query sees the ``window`` keys that end at its
    own position) in all three kernels, in interpret mode: forward and
    every gradient against the masked softmax, at Laguna's two GQA ratios
    (48 and 72 query heads on 8 kv heads). The loops skip the key blocks
    behind the band, so a query row can meet a block it sees nothing of."""
    sq, sk, block_q, block_k = _WINDOW_SHAPES[shape]
    b, kvh, d = 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(ks[0], (b, sq, kvh * group, d))
    k = jax.random.normal(ks[1], (b, sk, kvh, d))
    v = jax.random.normal(ks[2], (b, sk, kvh, d))
    cot = jax.random.normal(ks[3], q.shape)

    def flash(*a):
        return flash_attention(*a, causal=True, window=window,
                               use_pallas=True, interpret=True,
                               block_q=block_q, block_k=block_k)

    def plain(*a):
        return attention_reference(*a, causal=True, window=window)

    want = plain(q, k, v)
    # the mask is the band: the last query sees the last `window` keys alone
    far = k.at[:, :sk - window].set(9.0)
    np.testing.assert_array_equal(np.asarray(plain(q, far, v)[:, -1]),
                                  np.asarray(want[:, -1]))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(want),
                               atol=2e-5)
    got_g = jax.grad(lambda *a: (flash(*a) * cot).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(lambda *a: (plain(*a) * cot).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    for got, ref in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-5)


def _brute_force_tiles(sq, sk, block_q, block_k, window, causal=True):
    """Per tile of the [sq, sk] mask: does it hold a kept element, is it
    all kept; and the mask."""
    pos = (sk - sq) + np.arange(sq)[:, None]
    key = np.arange(sk)[None, :]
    mask = np.ones((sq, sk), bool)
    if causal:
        mask = pos >= key
        if window is not None:
            mask &= pos - key < window
    t = mask.reshape(sq // block_q, block_q, sk // block_k, block_k)
    return t.any(axis=(1, 3)), t.all(axis=(1, 3)), mask


_PLAN_CASES = [
    # sq, sk, block_q, block_k, window
    (64, 64, 16, 16, None), (64, 64, 16, 16, 8), (64, 64, 16, 16, 16),
    (64, 64, 16, 16, 40), (2048, 2048, 512, 512, 512),
    (2048, 2048, 256, 256, 512), (2048, 2048, 128, 128, 512),
    (64, 128, 16, 16, None), (64, 128, 16, 16, 40), (32, 64, 16, 32, 40),
    (64, 64, 32, 16, None), (64, 64, 16, 32, None), (64, 64, 32, 16, 24),
    (64, 128, 64, 32, 8), (48, 96, 16, 32, 1), (64, 64, 64, 64, 512),
]


@pytest.mark.parametrize("sq,sk,block_q,block_k,window", _PLAN_CASES)
def test_tile_plan_matches_a_brute_force_count(sq, sk, block_q, block_k,
                                               window):
    """The loops' bounds against the mask itself: the visited tiles are
    those that hold a kept element, the bare ones those the mask leaves
    whole, by row (forward, dQ) and by column (dK/dV), and ``tile_plan``
    counts them."""
    from ray_tpu.ops import attention

    any_kept, all_kept, mask = _brute_force_tiles(sq, sk, block_q, block_k,
                                                  window)
    nq, nk = any_kept.shape

    def walked(bounds, n_outer, by_row):
        first, bare_first, bare_end, end = (
            np.broadcast_to(b, (n_outer,)) for b in bounds)
        visited = np.zeros((nq, nk), bool)
        bare = np.zeros((nq, nk), bool)
        for outer in range(n_outer):
            assert (0 <= first[outer] <= bare_first[outer]
                    <= bare_end[outer] <= end[outer])
            for inner in range(first[outer], end[outer]):
                at = (outer, inner) if by_row else (inner, outer)
                visited[at] = True
                bare[at] = bare_first[outer] <= inner < bare_end[outer]
        return visited, bare

    by_row = walked(attention._key_bounds(
        np.arange(nq), block_q, block_k, sk, sk - sq, True, window, xp=np),
        nq, True)
    by_col = walked(attention._query_bounds(
        np.arange(nk), block_q, block_k, sq, sk - sq, True, window, xp=np),
        nk, False)
    for visited, bare in (by_row, by_col):
        np.testing.assert_array_equal(visited, any_kept)
        np.testing.assert_array_equal(bare, all_kept)
    plan = attention.tile_plan(sq, sk, block_q, block_k, window)
    assert plan["tiles_visited"] == any_kept.sum()
    assert plan["tiles_edge"] == (any_kept & ~all_kept).sum()
    assert plan["kept_share"] == pytest.approx(
        mask.sum() / (any_kept.sum() * block_q * block_k))


def test_tile_plan_reads_what_the_issue_reckoned():
    from ray_tpu.ops.attention import _auto_block, tile_plan

    def plan(*a, **kw):
        p = tile_plan(*a, **kw)
        return p["tiles_visited"], p["tiles_edge"], round(p["kept_share"], 2)

    assert plan(4096, 4096, 512, 512) == (36, 8, 0.89)
    assert plan(8192, 8192, 512, 512) == (136, 16, 0.94)
    assert plan(8192, 8192, 512, 512, 512) == (31, 31, 0.5)
    # the second query block's far tile is whole: 62 edges, not 63
    assert plan(8192, 8192, 256, 256, 512) == (93, 62, 0.67)
    assert plan(64, 64, 16, 16, causal=False) == (16, 0, 1.0)
    # the block a call gets where its caller names none: from the sequence
    assert [_auto_block(s) for s in (8192, 4096, 768, 384, 100)] == [
        512, 512, 256, 128, 128]


def test_window_cases_meet_every_kind_of_tile():
    """The interpret-mode cases above give a query block tiles that the
    band's far edge cuts, interior tiles and tiles the diagonal cuts, and
    leave each kind out somewhere."""
    from ray_tpu.ops import attention

    def kinds(sq, sk, block_q, block_k, window):
        first, bare_first, bare_end, end = (
            np.broadcast_to(b, (sq // block_q,)) for b in
            attention._key_bounds(np.arange(sq // block_q), block_q, block_k,
                                  sk, sk - sq, True, window, xp=np))
        return np.stack([bare_first - first, bare_end - bare_first,
                         end - bare_end])

    seen = np.concatenate(
        [kinds(*_WINDOW_SHAPES[shape], window) for window, shape in
         ((8, "square"), (16, "square"), (40, "square"), (56, "square"),
          (40, "keys-ahead"), (24, "wide-q-blocks"))]
        + [kinds(64, 128, bq, bk, w) for bq, bk, w in
           ((64, 64, None), (32, 64, None), (64, 32, None), (16, 16, None),
            (32, 32, 48), (16, 32, 8))], axis=1)
    assert (seen == 0).any(axis=1).all() and (seen > 0).any(axis=1).all()
    assert seen[1].max() >= 2           # several interior tiles in a row


def test_flash_attention_window_needs_causal():
    q = jnp.zeros((1, 16, 2, 8))
    for fn in (flash_attention, attention_reference):
        with pytest.raises(ValueError, match="causal"):
            fn(q, q, q, causal=False, window=4)


@pytest.mark.parametrize("block_q,block_k,window", [
    (64, 64, None), (32, 64, None), (64, 32, None), (16, 16, None),
    (32, 32, 48), (16, 32, 8)],
    ids=["one-q-block", "narrow-q", "narrow-k", "many-interior",
         "window-of-blocks", "window-under-a-block"])
def test_flash_attention_grads_cross_seq(block_q, block_k, window):
    # sk > sq (chunked prefill / decode alignment): causal offset path,
    # with equal and unequal blocks, bare and under a window.
    b, sq, sk, h, d = 1, 64, 128, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, h, d))
    cot = jax.random.normal(jax.random.PRNGKey(3), q.shape)

    def flash(*a):
        return flash_attention(
            *a, causal=True, window=window, use_pallas=True, interpret=True,
            block_q=block_q, block_k=block_k)

    def plain(*a):
        return attention_reference(*a, causal=True, window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)), atol=2e-5)
    gf = jax.grad(lambda *a: (flash(*a) * cot).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (plain(*a) * cot).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5)


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


@pytest.mark.parametrize("toward", [None, (8, 16), (4, 12)],
                         ids=["balanced", "half-held", "all-held"])
def test_held_experts_match_the_expert_loop(toward):
    """``held=(4, 8)``: the part experts 4..11 of 16 give, forward and
    every gradient (the router's over all 16 outputs, the expert
    matrices' for the eight held) against the loop over those experts,
    renormalised and scaled as Laguna routes. A pass takes 512 of the 768
    pairs (the share of 384 and an eighth, in row tiles): a balanced
    router fills a part of one, the skewed one sends every token to
    experts 8..15, half of them held (384 rows, one pass), and the one
    skewed to the held experts themselves holds all 768: a pass and a
    half, which twice the share took in one."""
    from ray_tpu.ops import moe

    *args, cot = _routed_inputs(toward is not None, toward or (8, 16))
    kw = dict(renormalize=True, scale=2.5)
    with jax.default_matmul_precision("highest"):
        out, logits, counts = jax.jit(
            lambda *a: _held_share((4, 8), *a, 8, **kw))(*args)
        want = _experts_by_loop(*args, 8, held=(4, 8), **kw)
        got_g = jax.jit(jax.grad(
            lambda *a: (_held_share((4, 8), *a, 8, **kw)[0] * cot).sum(),
            argnums=(0, 1, 2, 3, 4)))(*args)
        want_g = jax.jit(jax.grad(
            lambda *a: (_experts_by_loop(*a, 8, held=(4, 8), **kw)
                        * cot).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    assert int(counts.sum()) == 96 * 8 and counts.shape == (16,)
    chunk = moe._held_chunk(96 * 8, 8, 16)
    assert chunk == 512
    held_rows = int(counts[4:12].sum())
    if toward == (8, 16):
        assert counts.tolist() == [0] * 8 + [96] * 8 and held_rows == 384
    elif toward == (4, 12):     # between one pass and two
        assert counts.tolist() == [0] * 4 + [96] * 8 + [0] * 4
        assert chunk < held_rows == 768 < 2 * chunk
    else:
        assert 0 < held_rows < chunk
    np.testing.assert_allclose(np.asarray(logits), np.asarray(
        args[0] @ args[1]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for got, ref in zip(got_g, want_g):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def _wide_routed_inputs(toward, n=192, top_k=4):
    """A layer wide enough for column blocks (768 columns, six lane
    tiles): 16 experts of 48. ``toward=(a, b)``: every token chooses
    experts a..b-1, ``top_k`` of them."""
    h, f, E = 768, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (n, h))
    router_w = jax.random.normal(ks[1], (h, E)) * h ** -0.5
    if toward is not None:
        x = x.at[:, 0].set(5.0)
        router_w = (router_w * 0.01).at[0, slice(*toward)].add(10.0)
    return (x, router_w, jax.random.normal(ks[2], (E, h, f)) * h ** -0.5,
            jax.random.normal(ks[3], (E, h, f)) * h ** -0.5,
            jax.random.normal(ks[4], (E, f, h)) / 7,
            jax.random.normal(ks[5], (n, h)))


@pytest.mark.parametrize("limit, blocks", [(1024, 1), (256, 3), (512, 2)],
                         ids=["under", "a-multiple", "not-a-multiple"])
@pytest.mark.parametrize("toward, passes", [(None, 1), ((4, 8), 3),
                                            ((8, 12), 0)],
                         ids=["one-pass", "three-passes", "none-held"])
def test_held_experts_sum_their_rows_in_column_blocks(
        monkeypatch, limit, blocks, toward, passes):
    """Past ``_SUM_WHOLE`` columns a pass adds its rows into the tokens'
    sums in blocks of at most ``_SUM_COLUMNS``, carried apart and joined
    after the loop: 768 columns under the first (one block, the statement
    as it was), in three blocks of 256 and, for a limit of 512 that does
    not divide them, in two of 384. Forward and every gradient (``d x``, the router's, which
    carries ``d top_w``, and the held experts' three) against the loop
    over experts 4..7 of 16, where a balanced router fills one pass, where
    every token chooses the four held (768 rows, three passes of 256) and
    where none does."""
    from ray_tpu.ops import layers, moe

    monkeypatch.setattr(layers, "_SUM_WHOLE", limit)
    monkeypatch.setattr(layers, "_SUM_COLUMNS", limit)
    assert moe._sum_columns(768) * blocks == 768
    *args, cot = _wide_routed_inputs(toward)
    held, kw = (4, 4), dict(renormalize=True, scale=2.5)
    with jax.default_matmul_precision("highest"):
        out, _, counts = jax.jit(
            lambda *a: _held_share(held, *a, 4, **kw))(*args)
        want = _experts_by_loop(*args, 4, held=held, **kw)
        got_g = jax.jit(jax.grad(
            lambda *a: (_held_share(held, *a, 4, **kw)[0] * cot).sum(),
            argnums=(0, 1, 2, 3, 4)))(*args)
        want_g = jax.jit(jax.grad(
            lambda *a: (_experts_by_loop(*a, 4, held=held, **kw)
                        * cot).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    chunk = moe._held_chunk(192 * 4, 4, 16)
    assert chunk == 256
    held_rows = int(counts[4:8].sum())
    assert -(-held_rows // chunk) == passes
    assert held_rows == {0: 0, 3: 768}.get(passes, held_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for got, ref in zip(got_g, want_g):
        assert got.shape == ref.shape
        # the skewed router's constant feature makes gradients of 1e2-1e3
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4,
            atol=1e-5 * max(10.0, float(jnp.abs(ref).max())))


def test_column_blocks_give_the_one_blocks_bits_where_no_token_repeats(
        monkeypatch):
    """The blocks change where a sum's columns live, not what is added to
    them: with one choice a token (no token twice in a pass, so no sum
    depends on the order a scatter takes its rows in) the result and every
    gradient in three blocks are the one block's bit for bit, over three
    passes."""
    from ray_tpu.ops import layers, moe

    *args, cot = _wide_routed_inputs((4, 8), n=768, top_k=1)

    def both(limit):
        monkeypatch.setattr(layers, "_SUM_WHOLE", limit)
        monkeypatch.setattr(layers, "_SUM_COLUMNS", limit)
        out, _, counts = jax.jit(
            lambda *a: _held_share((4, 4), *a, 1, scale=2.5))(*args)
        grads = jax.jit(jax.grad(
            lambda *a: (_held_share((4, 4), *a, 1, scale=2.5)[0]
                        * cot).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
        assert int(counts[4:8].sum()) == 768 == 3 * moe._held_chunk(
            768, 4, 16)
        return (out,) + grads

    one, three = both(1024), both(256)
    assert moe._sum_columns(768) == 256
    assert float(jnp.abs(one[0]).max()) > 0
    for a, b in zip(one, three):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("h, whole, limit, want", [
    (5120, None, None, 1280),   # train-deepseek-v2-1chip: four blocks
    (3072, None, None, 3072), (2048, None, None, 2048),     # Laguna, LFM2
    (4096, None, None, 4096), (2560, None, None, 2560),
    (6144, None, None, 1024), (8192, None, None, 1024),
    (7168, None, None, 1024), (4608, None, None, 1152),
    (5120, 4096, 4096, 2560), (768, 512, 512, 384), (768, 256, 256, 256),
    (768, 100, 100, 768),
    (5000, None, None, 5000),   # no divisor in whole lane tiles: one block
])
def test_sum_columns_is_a_divisor_in_whole_lane_tiles(monkeypatch, h, whole,
                                                      limit, want):
    """A block of the sums is the whole width up to ``_SUM_WHOLE`` and past
    it the largest divisor of the width in whole 128-lane tiles that is at
    most ``_SUM_COLUMNS``; a width without one stays one block. The
    constants as they stand (``None``) leave 2,048 and 3,072 columns one
    sum and take 5,120 in four. The kept span of a traced layer carries
    the count."""
    from ray_tpu.ops import layers, moe
    from ray_tpu.util import tracing

    if whole is not None:
        monkeypatch.setattr(layers, "_SUM_WHOLE", whole)
        monkeypatch.setattr(layers, "_SUM_COLUMNS", limit)
    width = moe._sum_columns(h)
    assert width == want and h % width == 0
    assert width == h or (width <= layers._SUM_COLUMNS and width % 128 == 0)
    assert [b.shape for b in moe._zero_sums(8, h)] == [(8, width)] * (
        h // width)
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in (
        (16, h), (h, 16), (8, h, 8), (8, h, 8), (8, 8, h))]
    n0 = len(tracing.chrome_events())
    jax.eval_shape(lambda *a: moe.routed_experts(*a, 8, held=(4, 8))[0],
                   *shapes)
    (ev,) = [e for e in tracing.chrome_events()[n0:]
             if e["name"] == "rtpu.moe.held_pass"]
    assert ev["args"]["sum_blocks"] == h // want


def _one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("dp",))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("columns, limit, mesh, blocks", [
    (768, 1024, None, 1), (768, 256, None, 3), (768, 512, None, 2),
    (700, 256, None, 1), (768, 256, _one_device_mesh, 1),
], ids=["under", "a-multiple", "not-a-multiple", "no-divisor", "a-mesh"])
def test_embed_rows_adds_its_gradient_in_column_blocks(
        monkeypatch, dtype, columns, limit, mesh, blocks):
    """``embed_rows`` is ``table.astype(dtype)[tokens]`` and, past
    ``_SUM_WHOLE`` columns with a divisor and no mesh, a ``custom_vjp`` whose
    backward adds the cotangent's rows into blocks of columns: value and
    gradient are the plain gather's bit for bit, repeated tokens each time
    (128 draws of 39 rows) and a row never drawn at zero; within the limit,
    at a width with no divisor and under a mesh there is nothing around the
    plain expression; the kept span says which."""
    from ray_tpu.ops import layers
    from ray_tpu.util import tracing

    monkeypatch.setattr(layers, "_SUM_WHOLE", limit)
    monkeypatch.setattr(layers, "_SUM_COLUMNS", limit)
    mesh = mesh and mesh()
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((40, columns), np.float32))
    tokens = jnp.asarray(rng.integers(0, 39, (2, 64)))

    def ours(t, at):
        return layers.embed_rows(t, at, dtype, mesh)

    def plain(t, at):
        return t.astype(dtype)[at]

    # every array an argument: a closed-over one compiles into the program
    def both(t, at, cot):
        return tuple((f(t, at), jax.grad(
            lambda t_: (f(t_, at).astype(jnp.float32) * cot).sum())(t))
            for f in (ours, plain))

    args = table, tokens, jnp.asarray(
        rng.standard_normal((2, 64, columns), np.float32))
    n0 = len(tracing.chrome_events())
    text = str(jax.make_jaxpr(both)(*args))
    (said,) = [e["args"] for e in tracing.chrome_events()[n0:]
               if e["name"] == "rtpu.embed.plan"][:1]
    assert ("custom_vjp" in text) == (blocks > 1)
    assert text.count("scatter-add[") == blocks + 1
    assert {k: said[k] for k in ("rows", "table_rows", "columns",
                                 "sum_columns", "blocks", "form")} == {
        "rows": 128, "table_rows": 40, "columns": columns,
        "sum_columns": columns // blocks, "blocks": blocks,
        "form": "blocked" if blocks > 1 else "whole"}
    got, want = jax.jit(both)(*args)
    assert got[1].dtype == table.dtype and float(jnp.abs(got[1]).max()) > 0
    assert not np.asarray(got[1][39]).any()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("model", ["dense", "deepseek-v2"])
def test_a_models_gradients_are_the_plain_gathers(monkeypatch, model):
    """A tiny dense stack (``llama.forward``, bfloat16 activations over
    float32 parameters) and a tiny DeepSeek-V2's first layer
    (``Stack.hidden``) at 64 columns in two blocks of 32: the loss and every parameter's gradient are
    what the plain gather's transpose gives, bit for bit."""
    from ray_tpu.models import deepseek_v2, llama
    from ray_tpu.ops import layers

    if model == "dense":
        cfg = llama.LlamaConfig.tiny(attn_impl="reference", num_layers=1,
                                     dtype=jnp.bfloat16)
        mod, loss = llama, llama.loss_fn
    else:
        cfg = deepseek_v2.DeepseekV2Config.tiny(attn_impl="reference",
                                                num_layers=1)
        mod, loss = deepseek_v2, deepseek_v2.loss_fn
    # the leaves' shapes from ``init_params``, filled here: drawing them
    # there compiles a program a leaf
    rng = np.random.default_rng(1)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.asarray(0.1 * rng.standard_normal(leaf.shape),
                                 leaf.dtype),
        jax.eval_shape(lambda: mod.init_params(cfg, jax.random.PRNGKey(0))))
    tokens = rng.integers(0, cfg.vocab_size // 2, (2, 33))

    def grads(p):
        for limit in (32, 64):      # two blocks, then the plain expression
            monkeypatch.setattr(layers, "_SUM_WHOLE", limit)
            monkeypatch.setattr(layers, "_SUM_COLUMNS", limit)
            assert layers.embed_plan(64, cfg.vocab_size, 64)["blocks"] == (
                64 // limit)
            yield jax.value_and_grad(
                lambda p_: loss(cfg, p_, {"tokens": tokens}))(p)

    blocked, whole = jax.jit(lambda p: tuple(grads(p)))(params)
    leaves = jax.tree_util.tree_leaves_with_path
    assert float(jnp.abs(blocked[1]["embed"]).max()) > 0
    for (path, a), (_, b) in zip(leaves(blocked), leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))


@pytest.mark.parametrize("pairs, count, num_experts, want", [
    (16384 * 10, 16, 256, 11520),     # train-laguna-1chip: 45 tiles for 80
    (16384 * 4, 16, 32, 36864),       # train-lfm2-1chip: 144 tiles for 256
    # train-deepseek-v2-1chip: 8 experts wander more than 16, so a sixth
    # over the share (12 tiles), where an eighth gave 11
    (8192 * 6, 8, 160, 3072),
    (96 * 8, 8, 16, 512), (64 * 10, 16, 256, 256),      # the tests above
    (16384 * 4, 32, 32, 65536),       # all held: every pair and no more
    (1000, 7, 8, 1024),               # the headroom passes all the pairs
    (1000, 1, 8, 256), (1000, 3, 16, 256), (100, 1, 64, 256),
])
def test_held_chunk_is_the_share_and_a_headroom_in_whole_tiles(
        pairs, count, num_experts, want):
    """A pass's static row count from shapes alone: whole row tiles,
    never under the held experts' balanced share (a balanced routing is
    one pass), never over all the pairs rounded up to a tile, and well
    under the twice the share that it was (PERF.md 6, PR 35)."""
    from ray_tpu.ops import moe

    chunk = moe._held_chunk(pairs, count, num_experts)
    share = pairs * count / num_experts
    tile = moe._ROW_TILE
    assert chunk == want and chunk % tile == 0
    assert min(share, pairs) <= chunk <= -(-pairs // tile) * tile
    assert chunk <= max(1.25 * share, share + tile)


@pytest.mark.parametrize("headroom, want", [
    (None, 46080), (8, 46080), (4, 51200), (3, 54784), (2, 61440)])
def test_a_configurations_headroom_sets_the_pass(headroom, want):
    """train-qwen3-next-1chip's layer (32,768 tokens, 10 of 512 experts a
    token, 64 held): the op's own part is an eighth over the share of
    40,960 rows; a configuration that says how far its loads lie from
    balance (``held_headroom``) gets that part, in whole tiles."""
    from ray_tpu.ops import moe

    chunk = moe._held_chunk(32768 * 10, 64, 512, headroom)
    assert chunk == want and chunk % moe._ROW_TILE == 0
    counts = np.zeros((1, 512), np.int64)
    counts[0, 0], counts[0, 64] = 46081, 32768 * 10 - 46081
    assert moe.rows_passed(counts, (0, 64), headroom) == \
        (2 if want == 46080 else 1) * want


def test_a_wider_pass_gives_the_same_sums_in_fewer_passes():
    """768 held rows (every token chooses experts 4..7 of 16) in three
    passes of the op's own 256 rows and in two of 512 (the share of 192
    and as much again, in whole tiles) under a headroom of one part in
    one: the result and every gradient agree, and
    ``rows_passed`` counts each."""
    from ray_tpu.ops import moe

    *args, cot = _wide_routed_inputs((4, 8))
    assert (moe._held_chunk(768, 4, 16), moe._held_chunk(768, 4, 16, 1)) \
        == (256, 512)

    def both(headroom):
        kw = dict(renormalize=True, scale=2.5, headroom=headroom)
        with jax.default_matmul_precision("highest"):
            out, _, counts = jax.jit(
                lambda *a: _held_share((4, 4), *a, 4, **kw))(*args)
            grads = jax.jit(jax.grad(
                lambda *a: (_held_share((4, 4), *a, 4, **kw)[0] * cot).sum(),
                argnums=(0, 1, 2, 3, 4)))(*args)
        passed = moe.rows_passed(np.asarray(counts)[None], (4, 4), headroom)
        return passed, (out,) + grads

    (three, narrow), (two, wide) = both(None), both(1)
    assert (three, two) == (3 * 256, 2 * 512)
    for a, b in zip(narrow, wide):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5,
            atol=1e-6 * max(10.0, float(jnp.abs(a).max())))


def test_held_pass_is_one_kept_span_of_a_traced_held_layer():
    """Tracing a layer that holds a share writes what a pass will take
    once, as a kept span (no flag, no profiler window): the pairs, the
    experts held of how many, their balanced share and the chunk. The
    layer that holds every expert has no passes and writes none."""
    from ray_tpu.ops import moe
    from ray_tpu.util import tracing

    def mine():
        return [e for e in tracing.chrome_events()
                if e["name"] == "rtpu.moe.held_pass"]

    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in (
        (96, 32), (32, 16), (8, 32, 48), (8, 32, 48), (8, 48, 32))]
    n0 = len(mine())
    jax.eval_shape(jax.grad(lambda *a: moe.routed_experts(
        *a, 8, held=(4, 8))[0].sum(), argnums=(0, 2)), *shapes)
    (ev,) = mine()[n0:]
    assert {k: ev["args"][k] for k in (
        "pairs", "count", "num_experts", "balanced_share", "chunk",
        "sum_blocks")} == {
        "pairs": 768, "count": 8, "num_experts": 16,
        "balanced_share": 384.0, "chunk": 512, "sum_blocks": 1}
    whole = [jax.ShapeDtypeStruct((16,) + s.shape[1:], f32) if n > 1 else s
             for n, s in enumerate(shapes)]
    jax.eval_shape(lambda *a: moe.routed_experts(*a, 8)[0], *whole)
    assert len(mine()) == n0 + 1


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


def test_held_experts_drop_no_row_and_compile_nothing_whatever_the_routing():
    """A router that sends every row to the held experts (four passes of
    the loop where a balanced one takes one) and one that sends none:
    the first gives the whole layer, the second nothing, a gradient flows
    in both, and it is all one compiled program."""
    from ray_tpu.ops import moe

    cfg, laguna_ref, p, u = _laguna_routed_layer()
    held = (32, 16)
    weights = [p[k][32:48] for k in ("e_gate", "e_up", "e_down")]
    # the share of 40 rows and an eighth, one row tile; 640 rows: 3 passes
    assert moe._held_chunk(64 * 10, 16, 256) == 256

    @jax.jit
    def layer(u, router):
        def loss(u, router, *w):
            out, _, counts = moe.routed_experts(
                u, router, *w, cfg.top_k, renormalize=True, held=held,
                scale=cfg.routed_scale)
            return out.sum(), (out, counts)
        (_, (out, counts)), grads = jax.value_and_grad(
            loss, argnums=(0, 2), has_aux=True)(u, router, *weights)
        return out, counts, grads

    u = jnp.abs(u)          # a positive feature steers the router
    to_held = (p["router"] * 0.01).at[:, 32:48].add(1.0)
    to_others = (p["router"] * 0.01).at[:, 100:116].add(1.0)
    with jax.default_matmul_precision("highest"):
        out, counts, (d_u, d_gate) = layer(u, to_held)
        assert int(counts[32:48].sum()) == 640      # every row is held
        whole = dict(p, router=to_held)
        want = laguna_ref.routed_layer(cfg, whole, u) - swiglu(
            u, p["s_gate"], p["s_up"], p["s_down"])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert float(jnp.abs(d_u).min(-1).max()) > 0    # rows reached
        assert float(jnp.abs(d_gate).sum((1, 2)).min()) > 0
        out, counts, (d_u, d_gate) = layer(u, to_others)
    assert int(counts[32:48].sum()) == 0 and int(counts.sum()) == 640
    assert float(jnp.abs(out).max()) == 0.0
    assert float(jnp.abs(d_u).max()) == 0.0 == float(jnp.abs(d_gate).max())
    assert layer._cache_size() == 1


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


# ------------------------------------------- gated short convolution (LFM2)


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


# ----------------------------------- a sigmoid router with a selection bias


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


# ------------------------------------------------- flash at a head of 64


@pytest.mark.parametrize("heads,kv_heads", [(4, 1), (4, 4)],
                         ids=["gqa-4", "mha"])
def test_flash_attention_head_64_forward_and_gradients(heads, kv_heads):
    """LFM2's head size, half a lane tile: forward and the three
    gradients of the causal kernels (interpret mode) at both ratios of
    query to kv heads against the reference."""
    b, s, d = 1, 128, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, heads, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv_heads, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv_heads, d))
    cot = jax.random.normal(jax.random.PRNGKey(3), (b, s, heads, d))

    def loss(fn):
        return lambda *a: (fn(*a) * cot).sum()

    flash = lambda *a: flash_attention(        # noqa: E731
        *a, causal=True, use_pallas=True, interpret=True, block_q=64,
        block_k=64)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)),
        np.asarray(attention_reference(q, k, v, causal=True)), atol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda *a: attention_reference(*a, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=1e-4)


# ---- ops/ssm.py: the chunked selective scan and the Mamba-2 mixer


def _scan_inputs(b=2, s=32, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, s, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, H)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, s, G, N)),
            jax.random.normal(k[4], (b, s, G, N)))


def _recurrence(x, dt, A, B, C):
    """``granite_ref.recurrence`` (token by token) a row of the batch at a
    time."""
    from benchmark.references import granite_ref

    out = [granite_ref.recurrence(x[i], dt[i], A, B[i], C[i])
           for i in range(x.shape[0])]
    return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])


@pytest.mark.parametrize("chunk,walk", [(4, 8), (8, 2), (32, 1)],
                         ids=["chunk4", "chunk8-walk2", "whole-sequence"])
def test_ssd_scan_matches_the_recurrence(chunk, walk, monkeypatch):
    """The chunked scan against the recurrence one position after another
    (float32, 1e-5): outputs, the last state and every input's gradient,
    at three chunk sizes, one of them the whole sequence: the result does
    not depend on the chunk nor on how many a step of the walk takes (its
    bytes, ``WALK_BYTES``, are the one way to set that)."""
    from ray_tpu.ops import ssm
    from ray_tpu.ops.ssm import ssd_scan

    args = _scan_inputs()
    b, s, H, P = args[0].shape
    monkeypatch.setattr(ssm, "WALK_BYTES", walk * b * H * chunk * chunk * 4)
    assert ssm.scan_plan(b, s, H, P, 16, 2, chunk)["walk"] == walk

    def scalar(fn):
        def f(*a):
            y, S = fn(*a)
            return (jnp.sin(y) * y).sum() + (S * S).sum()
        return f

    with jax.default_matmul_precision("highest"):
        y, S = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
        want_y, want_S = _recurrence(*args)
        got = jax.jit(jax.grad(scalar(lambda *a: ssd_scan(
            *a, chunk=chunk)), argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(scalar(_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_ssd_scan_pads_a_ragged_sequence_and_keeps_rows_apart():
    """A sequence that is not whole chunks is padded with ``dt = 0``, which
    moves neither output nor state; a row of the batch never sees
    another's state."""
    from ray_tpu.ops.ssm import ssd_scan

    x, dt, A, B, C = _scan_inputs(s=30)
    with jax.default_matmul_precision("highest"):
        y, S = ssd_scan(x, dt, A, B, C, chunk=8)
        want_y, want_S = _recurrence(x, dt, A, B, C)
        alone, _ = ssd_scan(x[1:], dt[1:], A, B[1:], C[1:], chunk=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(y[1:]), np.asarray(alone))


def test_ssd_scan_without_its_carried_state_is_another_function(monkeypatch):
    """The fault ``benchmark/tests/scan_limits.py`` plants (one chunk a
    step of the walk, each started from zeros) agrees with the scan inside
    the first chunk alone; the program has no option for it."""
    from ray_tpu.ops import ssm

    args = _scan_inputs()
    y, _ = ssm.ssd_scan(*args, chunk=8)
    honest = ssm._walk_step
    monkeypatch.setattr(ssm, "WALK_BYTES", 0)
    monkeypatch.setattr(ssm, "_walk_step",
                        lambda S, *a: honest(jnp.zeros_like(S), *a))
    cut, _ = ssm.ssd_scan(*args, chunk=8)
    np.testing.assert_allclose(np.asarray(cut[:, :8]), np.asarray(y[:, :8]),
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(cut[:, 8:] - y[:, 8:]).max()) > 0.1


def test_ssd_scan_with_bfloat16_decays_is_another_function():
    """The other fault ``scan_limits.py`` plants in ``ops/ssm.py``: running
    sums, decays and the carried state rounded to bfloat16's eight bits.
    Output and last state leave the honest scan's by a bfloat16 rounding
    and more, a hundred times the 1e-5 the honest scan keeps to the
    recurrence; afterwards the module is what it was."""
    from benchmark.tests import scan_limits
    from ray_tpu.ops import ssm

    args = _scan_inputs()
    y, S = ssm.ssd_scan(*args, chunk=8)
    honest = ssm._walk_step
    cut_y, cut_S = scan_limits.with_bfloat16_decays(
        lambda: ssm.ssd_scan(*args, chunk=8))
    assert ssm.jnp is jnp and ssm._walk_step is honest

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert 1e-3 < rel(cut_y, y) < 0.1
    assert 1e-3 < rel(cut_S, S) < 0.1
    again, _ = ssm.ssd_scan(*args, chunk=8)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(y))


@pytest.mark.parametrize("form", ["xla_walk", "pallas"])
def test_scan_plan_walks_within_its_bytes(form, monkeypatch):
    """At the published shapes a step of XLA's walk takes 8 chunks, 128 MB
    of decay matrices where all 128 chunks at once would be 2.1 GB; a
    short sequence is one chunk; the walk always divides the chunks. The
    kernels (a TPU backend, no mesh, a chunk of whole lane tiles) put no
    decay matrix in HBM: ``KERNEL_CHUNKS`` chunks a grid step, the largest
    divisor of a group's heads within ``KERNEL_HEADS`` a block, a state
    kept a step; under a mesh and for a chunk of 30 the plan is the
    walk's."""
    from ray_tpu.ops import ssm

    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = ssm.scan_plan(1, 32768, 64, 64, 128, 1, 256)
    assert plan["form"] == form
    assert plan["decay_bytes_all_chunks"] == 2 ** 31
    small = ssm.scan_plan(2, 30, 4, 8, 16, 2, 256)
    assert (small["form"], small["chunk"], small["chunks"],
            small["walk"]) == ("xla_walk", 30, 1, 1)
    if form == "pallas":
        n = ssm.KERNEL_CHUNKS
        assert (plan["chunks"], plan["walk"], plan["chunks_a_call"],
                plan["steps"], plan["states_kept"]) == (
                    128, None, n, 128 // n, 128 // n)
        assert plan["heads_a_block"] == ssm.KERNEL_HEADS
        assert plan["decay_bytes_in_hbm"] == 0
        # the kept and the last states; dt, the sums, their gradients and
        # the skip's; dB and dC
        assert plan["float32_bytes_in_hbm"] == (
            (128 // n + 1) * 64 * 64 * 128 * 4
            + (5 * 64 + 2 * 128) * 32768 * 4)
        # two groups of 6 heads: a block lies within a group
        monkeypatch.setattr(ssm, "KERNEL_HEADS", 4)
        assert ssm.scan_plan(1, 1024, 12, 64, 128, 2, 256)[
            "heads_a_block"] == 3
        # a short sequence is one grid step of all its chunks
        short = ssm.scan_plan(1, 1000, 64, 64, 128, 1, 128)
        assert (short["chunks"], short["chunks_a_call"], short["steps"]) == (
            8, min(8, n), -(-8 // n))
        sharded = ssm.scan_plan(1, 32768, 64, 64, 128, 1, 256, object())
        assert (sharded["form"], sharded["walk"]) == ("xla_walk", 8)
        return
    assert (plan["chunks"], plan["walk"], plan["steps"]) == (128, 8, 16)
    assert (plan["chunks_a_call"], plan["states_kept"],
            plan["heads_a_block"]) == (8, 16, None)
    assert plan["decay_bytes_in_hbm"] == plan["float32_bytes_in_hbm"] \
        == 8 * 64 * 256 * 256 * 4 <= ssm.WALK_BYTES
    # one chunk's matrices past the budget: still one chunk a step
    assert ssm.scan_plan(64, 32768, 64, 64, 128, 1, 256)["walk"] == 1
    # 12 chunks, room for 8: the largest divisor within it
    odd = ssm.scan_plan(1, 3072, 64, 64, 128, 1, 256)
    assert (odd["chunks"], odd["walk"], odd["steps"]) == (12, 6, 2)


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


@pytest.mark.parametrize("form,scan", [
    ("xla_taps", "xla_walk"), ("pallas", "xla_walk"), ("pallas", "pallas")])
def test_mamba2_mixer_matches_the_reference(form, scan, monkeypatch):
    """The mixer (in-projection, taps with bias and silu, scan, skip,
    gated norm, out-projection) against ``granite_ref.mamba_mixer``:
    output, the last state and every leaf's gradient, float32 at 1e-5;
    once as the CPU runs it, once through the taps' kernels with XLA's
    walk after them (a TPU with a chunk that is not whole lane tiles) and
    once through the taps' and the scan's kernels, the skip ``D x`` inside
    them, as a TPU runs the cell (the interpreter in Mosaic's place)."""
    import functools

    from benchmark.references import granite_ref
    from ray_tpu.models import granite
    from ray_tpu.ops import conv, ssm
    from ray_tpu.ops.ssm import mamba2_mixer

    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ssm, "taps_silu", functools.partial(
            conv.taps_silu, interpret=True))
    if scan == "pallas":
        monkeypatch.setattr(ssm, "scan_kernels", functools.partial(
            ssm.scan_kernels, interpret=True))
        monkeypatch.setattr(ssm, "KERNEL_LANES", 8)
        monkeypatch.setattr(ssm, "KERNEL_CHUNKS", 2)
    n0, scans0 = len(_conv_plans()), len(_conv_plans("rtpu.ssm.scan_plan"))
    cfg = granite.GraniteConfig.tiny()
    p = {k: v[0] for k, v in granite.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["mamba"].items()}
    p["m_conv_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                               p["m_conv_bias"].shape)
    p["D"] = p["D"] + 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                              p["D"].shape)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.hidden_size))
    kw = dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
              state=cfg.ssm_state, groups=cfg.ssm_groups,
              chunk=cfg.ssm_chunk, eps=cfg.rms_norm_eps)
    sz = granite_ref._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        out, last = jax.jit(lambda u, p: mamba2_mixer(u, p, **kw))(u, p)
        want, S = granite_ref.mixer(cfg, p, u[0])
        got_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            mamba2_mixer(u, p, **kw)[0]).sum(), argnums=(0, 1)))(p, u)
        want_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            granite_ref.mamba_mixer(u[0], p, sz)[0]).sum(),
            argnums=(0, 1)))(p, u)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(S),
                               rtol=1e-5, atol=1e-5)
    names = set(p) - {"op_norm", "mlp_norm", "w_gate", "w_up", "w_down"}
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree_util.tree_leaves(want_g)):
        if path[0].idx == 0 and path[1].key not in names:
            continue                      # the layer's other leaves: zeros
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=str(path))
    assert {e["args"]["form"] for e in _conv_plans()[n0:]} == {form}
    assert {e["args"]["form"] for e in _conv_plans(
        "rtpu.ssm.scan_plan")[scans0:]} == {scan}


def _conv_plans(span="rtpu.ssm.conv_plan"):
    from ray_tpu.util import tracing

    return [e for e in tracing.chrome_events() if e["name"] == span]


def test_mamba2_mixer_is_float32_inside_and_names_its_scopes():
    """bf16 activations in and out, the state float32; the optimized
    program names the five scopes under ``ssm``, forward and backward."""
    from ray_tpu.models import granite
    from ray_tpu.ops.ssm import mamba2_mixer

    cfg = granite.GraniteConfig.tiny()
    p = {k: v[0].astype(jnp.bfloat16) for k, v in granite.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["mamba"].items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.hidden_size),
                          jnp.bfloat16)
    kw = dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
              state=cfg.ssm_state, chunk=cfg.ssm_chunk)
    out, last = mamba2_mixer(u, p, **kw)
    assert out.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert last.shape == (1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    text = jax.jit(jax.grad(lambda p, u: jnp.square(mamba2_mixer(
        u, p, **kw)[0].astype(jnp.float32)).sum(),
        argnums=(0, 1))).lower(p, u).as_text(
        debug_info=True)
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out"):
        assert f"jvp(ssm)/{scope}" in text, scope
        assert f"transpose(jvp(ssm))/{scope}" in text, scope


@pytest.mark.parametrize("rows,d,tile,asks", [
    (4096, 128, 8 << 20, None),         # Mistral, OLMoE: dK/dV
    (8192, 128, 4 << 20, None),         # Laguna: forward and dQ
    (16384, 128, 8 << 20, (16384 * 128 * 8) + (12 << 20)),  # Laguna: dK/dV
    (8192, 64, 8 << 20, None),          # LFM2: dK/dV
    (32768, 64, 4 << 20, (32768 * 128 * 8) + (8 << 20)),   # Granite
    (32768, 64, 8 << 20, (32768 * 128 * 8) + (12 << 20)),
], ids=["4k-128", "8k-128-fwd", "16k-128-dkv", "8k-64", "32k-64-fwd",
        "32k-64-dkv"])
def test_flash_kernels_ask_for_vmem_past_the_default_alone(rows, d, tile,
                                                           asks):
    """The accepted cells' kernel calls carry the compiler parameters they
    always did (none, or Laguna's dK/dV limit); at 32,768 keys of 64 every
    kernel asks for what VMEM holds, a row padded to 128 lanes."""
    from ray_tpu.ops.attention import _dkv_vmem

    got = _dkv_vmem(rows, d, jnp.bfloat16, tile=tile)
    if asks is None:
        assert got == {}
    else:
        assert got["compiler_params"].vmem_limit_bytes == asks


# ---- ops/delta.py: the chunked gated delta rule and Olmo-Hybrid's mixer


def _rule_inputs(b=2, s=32, H=3, K=8, V=16, beta_from=0.0, seed=0):
    """q, k, v as the taps leave them, ``g <= 0`` and ``beta`` in
    ``(beta_from, 2)``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, s, H, K)),
            jax.random.normal(k[1], (b, s, H, K)),
            jax.random.normal(k[2], (b, s, H, V)),
            -jax.nn.softplus(jax.random.normal(k[3], (b, s, H)) - 1.0),
            beta_from + (2.0 - beta_from) * jax.nn.sigmoid(
                2.0 * jax.random.normal(k[4], (b, s, H))))


def _rule(q, k, v, g, beta, chunk):
    """``gated_delta_rule`` on q and k normed as the mixer norms them."""
    from ray_tpu.ops.delta import gated_delta_rule
    from ray_tpu.ops.layers import l2_norm

    return gated_delta_rule(l2_norm(q, scale=q.shape[-1] ** -0.5),
                            l2_norm(k), v, g, beta, chunk=chunk)


def _delta_recurrence(q, k, v, g, beta):
    """``olmo_hybrid_ref.recurrence`` (token by token, norming q and k
    itself) a row of the batch at a time."""
    from benchmark.references import olmo_hybrid_ref

    out = [olmo_hybrid_ref.recurrence(q[i], k[i], v[i], g[i], beta[i])
           for i in range(q.shape[0])]
    return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])


@pytest.mark.parametrize("beta_from", [0.0, 1.0],
                         ids=["beta-0-to-2", "beta-above-1"])
@pytest.mark.parametrize("chunk,walk,base", [
    (4, 8, 16), (8, 2, 2), (32, 1, 16), (32, 1, 4)],
    ids=["chunk4", "chunk8-walk2-base2", "whole-sequence", "whole-base4"])
def test_gated_delta_rule_matches_the_recurrence(chunk, walk, base,
                                                 beta_from, monkeypatch):
    """The chunked rule against the recurrence one position after another
    (float32, 1e-5): outputs, the last state and every input's gradient,
    at three chunk sizes, one of them the whole sequence, with ``beta``
    over (0, 2) and above 1 alone (eigenvalues below zero): the result
    depends neither on the chunk, nor on how many a step of the walk takes
    (``WALK_BYTES``), nor on where the triangular inverse stops
    substituting and joins blocks (``INVERSE_BASE``)."""
    from ray_tpu.ops import delta

    args = _rule_inputs(beta_from=beta_from)
    b, s, H, K = args[0].shape
    V = args[2].shape[-1]
    monkeypatch.setattr(delta, "INVERSE_BASE", base)
    monkeypatch.setattr(delta, "WALK_BYTES",
                        walk * b * H * 4 * (4 * chunk * chunk + V * K))
    assert delta.rule_plan(b, s, H, K, V, chunk)["walk"] == walk

    def scalar(fn):
        def f(*a):
            o, S = fn(*a)
            return (jnp.sin(o) * o).sum() + (S * S).sum()
        return f

    with jax.default_matmul_precision("highest"):
        o, S = jax.jit(lambda *a: _rule(*a, chunk))(*args)
        want_o, want_S = _delta_recurrence(*args)
        got = jax.jit(jax.grad(scalar(lambda *a: _rule(*a, chunk)),
                               argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(scalar(_delta_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_gated_delta_rule_pads_a_ragged_sequence_and_keeps_rows_apart():
    """A sequence that is not whole chunks is padded with ``g = 0`` and
    ``beta = 0``, which move neither output nor state; a row of the batch
    never sees another's state."""
    args = _rule_inputs(s=30)
    with jax.default_matmul_precision("highest"):
        o, S = _rule(*args, 8)
        want_o, want_S = _delta_recurrence(*args)
        alone, _ = _rule(*(a[1:] for a in args), 8)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    # (a batch of two and of one sum in another order: float32's last bit)
    np.testing.assert_allclose(np.asarray(o[1:]), np.asarray(alone),
                               rtol=1e-6, atol=1e-6)


def test_unit_lower_inverse_is_exact_on_repeated_keys():
    """64 equal keys at ``beta = 2``: ``A`` is all twos under the diagonal,
    its powers pass 1e17 and a sum of them cancels to nothing in float32;
    forward substitution and the joins give the inverse, whose entries
    are 1 and 2 in turn, to float32's last bits."""
    from ray_tpu.ops.delta import _unit_lower_inverse

    n = 64
    A = jnp.tril(jnp.full((n, n), 2.0, jnp.float32), -1)
    T = _unit_lower_inverse(A[None])[0]
    np.testing.assert_allclose(
        np.asarray(T @ (jnp.eye(n) + A)), np.eye(n), atol=1e-5)
    assert float(jnp.abs(T).max()) == 2.0


@pytest.mark.parametrize("fault,inside_first_chunk", [
    ("without_carry", True), ("with_half_beta", False),
    ("with_first_order_inverse", False), ("without_qk_norm", False)])
def test_gated_delta_rule_with_a_planted_fault_is_another_function(
        fault, inside_first_chunk):
    """The faults ``benchmark/tests/delta_limits.py`` plants in
    ``ops/delta.py`` (the state not carried, ``beta`` without its two, ``I
    - A`` for the inverse, q and k not normed) leave the honest rule's
    output by far more than a rounding (the first agrees inside the first
    chunk alone); the program has no option for any of them, and
    afterwards the module is what it was."""
    from benchmark.tests import delta_limits
    from ray_tpu.ops import delta

    p = {"g_A_log": jnp.zeros((3,)), "g_dt_bias": jnp.zeros((3,))}
    q, k, v, a, b_ = _rule_inputs()

    def rule():
        g, beta = delta._gates(a, b_, p)
        return delta.gated_delta_rule(
            delta.l2_norm(q, scale=8 ** -0.5), delta.l2_norm(k), v, g, beta,
            chunk=8)[0]

    honest = {n: getattr(delta, n) for n in (
        "_walk_step", "_gates", "_unit_lower_inverse", "l2_norm",
        "WALK_BYTES")}
    o = rule()
    cut = getattr(delta_limits, fault)(rule)
    assert all(getattr(delta, n) is v_ for n, v_ in honest.items())
    if inside_first_chunk:
        np.testing.assert_allclose(np.asarray(cut[:, :8]),
                                   np.asarray(o[:, :8]), rtol=1e-6, atol=1e-6)
        cut, o = cut[:, 8:], o[:, 8:]
    assert float(jnp.linalg.norm(cut - o) / jnp.linalg.norm(o)) > 0.05
    np.testing.assert_array_equal(np.asarray(rule()[:, 8:]),
                                  np.asarray(o[:, -24:]))


def test_gated_delta_rule_with_bfloat16_decays_is_another_function():
    """The other fault ``delta_limits.py`` plants: running sums, decays and
    the carried state rounded to bfloat16's eight bits. Output and last
    state leave the honest rule's by a bfloat16 rounding and more, a
    hundred times the 1e-5 the honest rule keeps to the recurrence."""
    from benchmark.tests import delta_limits
    from ray_tpu.ops import delta

    args = _rule_inputs()
    o, S = _rule(*args, 8)
    honest = delta._walk_step
    cut_o, cut_S = delta_limits.with_bfloat16_decays(lambda: _rule(*args, 8))
    assert delta.jnp is jnp and delta._walk_step is honest

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert 1e-3 < rel(cut_o, o) < 0.1
    assert 1e-3 < rel(cut_S, S) < 0.1


def test_rule_plan_walks_within_its_bytes():
    """At the published shapes (30 heads, keys of 96, values of 192) a
    step of the walk takes 8 chunks of 64, 33 MB of float32 pair
    matrices and carried states where all 512 chunks at once would be 2.1
    GB; a short sequence is one chunk; the walk always divides the
    chunks."""
    from ray_tpu.ops import delta

    plan = delta.rule_plan(1, 32768, 30, 96, 192, 64)
    one = 30 * 4 * (4 * 64 * 64 + 192 * 96)
    assert (plan["chunks"], plan["walk"], plan["steps"]) == (512, 8, 64)
    assert plan["float32_bytes_in_hbm"] == 8 * one <= delta.WALK_BYTES
    assert plan["float32_bytes_all_chunks"] == 512 * one
    # the CPU runs XLA's walk, and so does any call under a mesh
    assert plan["form"] == "xla_walk" and plan["heads_a_block"] is None
    assert plan["chunks_a_call"] == 8 and plan["states_kept"] == 64
    small = delta.rule_plan(2, 30, 4, 8, 16, 64)
    assert (small["chunk"], small["chunks"], small["walk"]) == (30, 1, 1)
    # one chunk's matrices past the budget: still one chunk a step
    assert delta.rule_plan(64, 32768, 30, 96, 192, 64)["walk"] == 1
    # 12 chunks, room for 9: the largest divisor within it
    odd = delta.rule_plan(1, 768, 30, 96, 192, 64)
    assert (odd["chunks"], odd["walk"], odd["steps"]) == (12, 6, 2)


def test_rule_plan_of_the_kernels_keeps_states_and_no_pair_matrix(
        monkeypatch):
    """On a TPU backend without a mesh the published shapes run as the
    kernels: 15 heads a block, 8 chunks a grid step, the state before each
    of the 64 steps kept for the backward (141 MB of the 149 MB of float32
    the form puts in HBM, where a step of XLA's walk put 33 MB of pair
    matrices and all chunks at once 2.1 GB); under a mesh, on the CPU, for
    a chunk that is not whole tiles or a sequence shorter than a chunk,
    XLA's walk."""
    from ray_tpu.ops import delta

    shapes = (1, 32768, 30, 96, 192, 64)
    assert delta.rule_plan(*shapes)["form"] == "xla_walk"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = delta.rule_plan(*shapes)
    assert (plan["form"], plan["heads_a_block"], plan["chunks_a_call"],
            plan["steps"], plan["states_kept"], plan["walk"]) == (
        "pallas", 15, 8, 64, 64, None)
    state = 30 * 192 * 96 * 4
    assert plan["float32_bytes_in_hbm"] == 65 * state + 3 * 30 * 32768 * 4
    assert plan["float32_bytes_all_chunks"] == 512 * 30 * 4 * (
        4 * 64 * 64 + 192 * 96)
    assert delta.rule_plan(*shapes, mesh=object())["form"] == "xla_walk"
    # 22 heads: the largest divisor within 16; 3 chunks: all in one step,
    # padded to 4 (a step's positions are whole registers of 128 lanes)
    odd = delta.rule_plan(2, 192, 22, 96, 192, 64)
    assert (odd["form"], odd["heads_a_block"], odd["chunks_a_call"],
            odd["steps"], odd["operands"]) == (
        "pallas", 11, 4, 1, "positions_last")
    # 16 key heads under 32: a step takes whole key heads with the two
    # value heads of each, 8 and 16 within 16; the kernels read q and k at
    # the key heads, the walk (under a mesh) reads copies
    grouped = delta.rule_plan(1, 32768, 32, 128, 128, 64, key_heads=16)
    assert (grouped["form"], grouped["heads_a_block"], grouped["joined"]
            ) == ("pallas", 16, "index_map")
    walked = delta.rule_plan(1, 32768, 32, 128, 128, 64, mesh=object(),
                             key_heads=16)
    assert (walked["form"], walked["joined"], walked["operands"]) == (
        "xla_walk", "repeat", None)
    assert plan["joined"] is None
    # a head that is not whole sublane tiles: the walk
    assert delta.rule_plan(1, 256, 4, 12, 16, 64)["form"] == "xla_walk"
    # 9 chunks: two steps of 8, the second padded
    assert delta.rule_plan(1, 520, 30, 96, 192, 64)["steps"] == 2
    for seq, chunk in ((30, 64), (256, 24), (256, 48)):
        assert delta.rule_plan(1, seq, 30, 96, 192, chunk)["form"] == (
            "xla_walk"), (seq, chunk)


def test_l2_norm_and_gated_rms_norm_match_their_definitions():
    from ray_tpu.ops.layers import gated_rms_norm, l2_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    gate = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 16))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    xs = np.asarray(x)
    np.testing.assert_allclose(
        np.asarray(l2_norm(x, scale=0.25)),
        0.25 * xs / np.sqrt((xs ** 2).sum(-1, keepdims=True) + 1e-6),
        rtol=1e-5, atol=1e-6)
    want = (xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-6)
            * np.asarray(w) * np.asarray(jax.nn.silu(gate)))
    np.testing.assert_allclose(np.asarray(gated_rms_norm(x, gate, w)), want,
                               rtol=1e-5, atol=1e-6)
    assert l2_norm(x.astype(jnp.bfloat16)).dtype == jnp.bfloat16
    assert gated_rms_norm(x.astype(jnp.bfloat16), gate, w
                          ).dtype == jnp.bfloat16


@pytest.mark.parametrize("form,rule", [
    ("xla_taps", "xla_walk"), ("pallas", "xla_walk"), ("pallas", "pallas")],
    ids=["xla_taps", "pallas", "pallas-rule"])
def test_gated_delta_mixer_matches_the_reference(form, rule, monkeypatch):
    """The mixer (in-projection, taps and silu, L2 norms, the rule, the
    gated norm of each head, out-projection) against
    ``olmo_hybrid_ref.delta_mixer``: output, the last state and every
    leaf's gradient, float32 at 1e-5; once as the CPU runs it, once
    through the taps' kernels with their zero bias, and once with the rule
    through its kernels too, as a TPU does (the interpreter in Mosaic's
    place; the tiny chunk of 8 is whole tiles of 4 rows there)."""
    import functools

    from benchmark.references import olmo_hybrid_ref
    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops import conv, delta, ssm
    from ray_tpu.ops.delta import gated_delta_mixer

    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ssm, "taps_silu", functools.partial(
            conv.taps_silu, interpret=True))
    if rule == "pallas":
        monkeypatch.setattr(delta, "KERNEL_BASE", 4)
        monkeypatch.setattr(delta, "rule_kernels", functools.partial(
            delta.rule_kernels, interpret=True))
    n0 = len(_conv_plans("rtpu.gdn.conv_plan"))
    r0 = len(_conv_plans("rtpu.gdn.rule_plan"))
    cfg = olmo_hybrid.OlmoHybridConfig.tiny()
    p = {k: v[0] for k, v in olmo_hybrid.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["linear"].items()}
    p["g_norm"] = p["g_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), p["g_norm"].shape)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.hidden_size))
    kw = dict(heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
              value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk,
              eps=cfg.rms_norm_eps)
    sz = olmo_hybrid_ref._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        out, last = jax.jit(lambda u, p: gated_delta_mixer(u, p, **kw))(u, p)
        want, S = olmo_hybrid_ref.mixer(cfg, p, u[0])
        got_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            gated_delta_mixer(u, p, **kw)[0]).sum(), argnums=(0, 1)))(p, u)
        want_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            olmo_hybrid_ref.delta_mixer(u[0], p, sz)[0]).sum(),
            argnums=(0, 1)))(p, u)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(S),
                               rtol=1e-5, atol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree_util.tree_leaves(want_g)):
        if path[0].idx == 0 and not path[1].key.startswith("g_"):
            continue                      # the layer's other leaves: zeros
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=str(path))
    assert {e["args"]["form"]
            for e in _conv_plans("rtpu.gdn.conv_plan")[n0:]} == {form}
    assert {e["args"]["form"]
            for e in _conv_plans("rtpu.gdn.rule_plan")[r0:]} == {rule}


@pytest.mark.parametrize("rule", ["xla_walk", "pallas"])
def test_gated_delta_mixer_is_float32_inside_and_names_its_scopes(
        rule, monkeypatch):
    """bf16 activations in and out, the state float32; in both forms of
    the rule every running sum and every decay is formed in float32
    (each ``cumsum`` and ``exp`` of the traced program, the kernels'
    bodies among them); the optimized program names the five scopes under
    ``gdn``, forward and backward."""
    import functools
    import re

    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops import conv, delta, ssm
    from ray_tpu.ops.delta import gated_delta_mixer

    if rule == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ssm, "taps_silu", functools.partial(
            conv.taps_silu, interpret=True))
        monkeypatch.setattr(delta, "KERNEL_BASE", 4)
        monkeypatch.setattr(delta, "rule_kernels", functools.partial(
            delta.rule_kernels, interpret=True))
    cfg = olmo_hybrid.OlmoHybridConfig.tiny()
    p = {k: v[0].astype(jnp.bfloat16) for k, v in olmo_hybrid.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["linear"].items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.hidden_size),
                          jnp.bfloat16)
    kw = dict(heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
              value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk)
    r0 = len(_conv_plans("rtpu.gdn.rule_plan"))
    out, last = gated_delta_mixer(u, p, **kw)
    assert out.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert last.shape == (1, cfg.linear_heads, cfg.linear_value_dim,
                          cfg.linear_key_dim)
    assert {e["args"]["form"]
            for e in _conv_plans("rtpu.gdn.rule_plan")[r0:]} == {rule}

    def loss(p, u):
        return jnp.square(gated_delta_mixer(u, p, **kw)[0].astype(
            jnp.float32)).sum()

    formed = re.findall(r"(\w+)\[[^\]]*\] = (?:exp|cumsum)\b",
                        str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
                            p, u)))
    assert len(formed) >= 5 and set(formed) == {"f32"}, formed
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, u).as_text(
        debug_info=True)
    for scope in ("gdn_in", "gdn_conv", "gdn_rule", "gdn_norm", "gdn_out"):
        assert f"jvp(gdn)/{scope}" in text, scope
        assert f"transpose(jvp(gdn))/{scope}" in text, scope


# ---- the options Qwen3-Next's table asks for (models/qwen3_next.py)


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


# ---- what nemotron_h's table asks of the ops (PR 52)

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


def test_mamba2_mixer_norms_a_group_at_a_time():
    """``norm_groups``: each group's channels divided by the root of their
    own mean square; one group is the function it was."""
    from ray_tpu.ops import ssm

    k = jax.random.split(jax.random.PRNGKey(0), 8)
    H, P, N, G, hid = 4, 8, 16, 2, 32
    d, conv = H * P, H * P + 2 * G * N
    p = {"m_in": jax.random.normal(k[0], (hid, d + conv + H)) / 6,
         "m_conv": jax.random.normal(k[1], (conv, 4)) / 2,
         "m_conv_bias": jnp.zeros((conv,)),
         "dt_bias": jnp.zeros((H,)), "A_log": jnp.zeros((H,)),
         "D": jnp.ones((H,)),
         "m_norm": 1.0 + 0.1 * jax.random.normal(k[2], (d,)),
         "m_out": jnp.eye(d, hid)}
    u = jax.random.normal(k[3], (1, 16, hid))
    sizes = dict(heads=H, head_dim=P, state=N, groups=G, chunk=8)
    one, _ = ssm.mamba2_mixer(u, p, **sizes)
    same, _ = ssm.mamba2_mixer(u, p, norm_groups=1, **sizes)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(same))
    two, _ = ssm.mamba2_mixer(u, p, norm_groups=2, **sizes)
    # undo the weights: each half of the channels has a unit mean square
    normed = np.asarray(two[0]) / np.asarray(p["m_norm"])[:hid]
    # (eps 1e-5 beside a mean square that may be small)
    np.testing.assert_allclose(np.square(normed[:, :16]).mean(-1), 1.0,
                               rtol=2e-2)
    np.testing.assert_allclose(np.square(normed[:, 16:]).mean(-1), 1.0,
                               rtol=2e-2)
    assert abs(np.square(np.asarray(one[0]) / np.asarray(p["m_norm"])[:hid]
                         )[:, :16].mean(-1) - 1.0).max() > 5e-2
