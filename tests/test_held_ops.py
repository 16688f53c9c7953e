"""A chip's held share of the routed experts (``ops/moe.routed_experts(held=
...)``): its passes against the loop over experts, the rows summed in
column blocks, the embedding's gradient added the same way, the pass's
width from the share and the configuration's headroom, and the kept span
a traced held layer writes."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.layers import swiglu  # noqa: E402
from tests.test_moe_ops import _experts_by_loop, _held_share, _routed_inputs, _laguna_routed_layer  # noqa: E402


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
    here = tracing.since()
    jax.eval_shape(lambda *a: moe.routed_experts(*a, 8, held=(4, 8))[0],
                   *shapes)
    (ev,) = [e for e in here.events()
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
    here = tracing.since()
    text = str(jax.make_jaxpr(both)(*args))
    (said,) = [e["args"] for e in here.events()
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
        return [e for e in here.events()
                if e["name"] == "rtpu.moe.held_pass"]

    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct(s, f32) for s in (
        (96, 32), (32, 16), (8, 32, 48), (8, 32, 48), (8, 48, 32))]
    here = tracing.since()
    jax.eval_shape(jax.grad(lambda *a: moe.routed_experts(
        *a, 8, held=(4, 8))[0].sum(), argnums=(0, 2)), *shapes)
    (ev,) = mine()
    assert {k: ev["args"][k] for k in (
        "pairs", "count", "num_experts", "balanced_share", "chunk",
        "sum_blocks")} == {
        "pairs": 768, "count": 8, "num_experts": 16,
        "balanced_share": 384.0, "chunk": 512, "sum_blocks": 1}
    whole = [jax.ShapeDtypeStruct((16,) + s.shape[1:], f32) if n > 1 else s
             for n, s in enumerate(shapes)]
    jax.eval_shape(lambda *a: moe.routed_experts(*a, 8)[0], *whole)
    assert len(mine()) == 1


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
