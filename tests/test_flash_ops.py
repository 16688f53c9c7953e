"""Flash attention (``ops/attention.py``) in ``interpret`` mode against the
reference: causal, windowed, across sequences of unequal length, at a
head of 64, and the plan of tiles a window visits against a brute-force
count."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.attention import attention_reference, flash_attention  # noqa: E402


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
