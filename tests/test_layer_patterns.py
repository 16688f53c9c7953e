"""The one layer loop (``llama.run_layers``) and what stands around it: a
scan against the unrolled loop, patterns of unequal layers walked by runs
of one kind, the attention block bit-equal for its callers, a per-head
q-k norm, and the blocked head and loss against the whole one."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402


@pytest.mark.parametrize("remat", [False, True], ids=["remat-off", "remat-on"])
@pytest.mark.parametrize("model", ["mixtral", "olmoe", "laguna"])
def test_moe_unrolled_matches_scan(model, remat):
    """``scan_layers=False`` is ``llama.run_layers``' branch for every
    forward of the family: the routed models' loss, gradients and
    per-layer router outputs (stacked as the scan stacks them) equal the
    scan's, with and without a checkpoint around each layer."""
    from dataclasses import replace

    from ray_tpu.models import laguna, mixtral, olmoe

    # laguna: three kinds of layer, walked by its pattern (a scan over
    # the three sliding layers between two single ones)
    mod, cls = {"mixtral": (mixtral, mixtral.MixtralConfig),
                "olmoe": (olmoe, olmoe.OlmoeConfig),
                "laguna": (laguna, laguna.LagunaConfig)}[model]
    scanned = cls.tiny(attn_impl="reference", remat=remat)
    params = mod.init_params(scanned, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                scanned.vocab_size)

    def run(cfg):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: mod.loss_fn(cfg, p, {"tokens": tokens})))(params)
        _, router = jax.jit(lambda p: mod.forward(
            cfg, p, tokens[:, :-1]))(params)
        return loss, grads, router

    want = run(scanned)
    got = run(replace(scanned, scan_layers=False))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    if model == "olmoe":
        assert got[2]["counts"].shape == (scanned.num_layers,
                                          scanned.num_experts)


def test_per_head_qk_norm_is_not_the_whole_vector_norm():
    """``attention_block`` tells LFM2's norm (a weight of a head's size:
    over each head's dims) from OLMoE's (over the whole q and k vectors)
    by the weight's shape; both against their equations."""
    from ray_tpu.ops.layers import apply_rope, rope_frequencies
    from ray_tpu.ops.attention import attention_reference

    cfg = llama.LlamaConfig.tiny(attn_impl="reference")
    h, hd, H, KV = cfg.hidden_size, cfg.head_dim_, cfg.num_heads, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    p = {"attn_norm": jnp.ones((h,)), "wq": jax.random.normal(ks[0], (h, h)) / 8,
         "wk": jax.random.normal(ks[1], (h, KV * hd)) / 8,
         "wv": jax.random.normal(ks[2], (h, KV * hd)) / 8,
         "wo": jax.random.normal(ks[3], (h, h)) / 8}
    x = jax.random.normal(ks[4], (2, 16, h))
    cos, sin = rope_frequencies(hd, 16, cfg.rope_theta)

    def by_hand(q_w, k_w, per_head):
        def norm(v, w):
            return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                                + cfg.rms_norm_eps) * w
        u = norm(x, 1.0)
        q, k, v = u @ p["wq"], u @ p["wk"], u @ p["wv"]
        if not per_head:
            q, k = norm(q, q_w), norm(k, k_w)
        q, k, v = (a.reshape(2, 16, -1, hd) for a in (q, k, v))
        if per_head:
            q, k = norm(q, q_w), norm(k, k_w)
        attn = attention_reference(apply_rope(q, cos, sin),
                                   apply_rope(k, cos, sin), v)
        return x + attn.reshape(2, 16, h) @ p["wo"]

    outs = {}
    with jax.default_matmul_precision("highest"):
        for per_head in (True, False):
            q_w = 1 + 0.3 * jax.random.normal(
                ks[5], (hd if per_head else H * hd,))
            k_w = 1 + 0.3 * jax.random.normal(
                ks[6], (hd if per_head else KV * hd,))
            got = llama.attention_block(
                cfg, x, {**p, "q_norm": q_w, "k_norm": k_w}, cos, sin)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(by_hand(q_w, k_w, per_head)),
                rtol=1e-5, atol=1e-5)
            outs[per_head] = got
        # with all weights 1 the two norms still differ
        ones = {True: (jnp.ones((hd,)),) * 2,
                False: (jnp.ones((H * hd,)), jnp.ones((KV * hd,)))}
        a, b = (llama.attention_block(
            cfg, x, {**p, "q_norm": ones[k][0], "k_norm": ones[k][1]},
            cos, sin) for k in (True, False))
    assert float(jnp.abs(a - b).max()) > 1e-3


def test_layer_patterns_are_walked_by_runs_of_one_kind():
    """``run_layers`` walks a pattern of kinds: a run of one kind is one
    scan, a layer alone between others is walked; Laguna-S-2.1's 48
    layers are 117.6 B parameters."""
    from ray_tpu.models import laguna

    cfg = laguna.LagunaConfig.laguna_s_2_1()
    assert cfg.pattern[:5] == ("full_dense", "sliding_moe", "sliding_moe",
                               "sliding_moe", "full_moe")
    assert len(cfg.pattern) == 48
    shapes = jax.eval_shape(lambda k: laguna.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert round(llama.num_params(shapes) / 1e9, 1) == 117.6

    # runs of one, two and three layers of two kinds: stacks by kind,
    # outputs back in each kind's order
    def fn(scale):
        return lambda x, p: (x * scale + p["w"], x.sum())
    layers = {"a": {"w": jnp.arange(4.0)}, "b": {"w": 10 + jnp.arange(8.0)}}
    pattern = ("b", "a", "b", "b", "a", "a", "a", "b", "b", "b", "b", "b")
    x0 = jnp.ones(())
    for scan in (True, False):
        x, ys = llama.run_layers({"a": fn(2.0), "b": fn(0.5)}, x0, layers,
                                 level="full", scan=scan, pattern=pattern)
        want, seen = x0, {"a": [], "b": []}
        at = {"a": 0, "b": 0}
        for kind in pattern:
            seen[kind].append(want)
            want = want * {"a": 2.0, "b": 0.5}[kind] + layers[kind]["w"][
                at[kind]]
            at[kind] += 1
        np.testing.assert_allclose(float(x), float(want), rtol=1e-6)
        for kind in "ab":
            np.testing.assert_allclose(np.asarray(ys[kind]),
                                       np.asarray(seen[kind]), rtol=1e-6)


def _attention_block_before(cfg, x, p, cos, sin, mesh=None,
                    seq_axis=None, window=None):
    """Pre-norm attention sub-block with residual: x + wo(attend(qkv)).
    Shared by every model in the family (llama dense, mixtral, olmoe and
    laguna MoE). The number of query heads is the layer's own, read from
    its ``wq`` (Laguna's window layers have more than its full ones);
    ``window``: the layer sees that many keys back (``flash_attention``);
    a ``wg`` in ``p`` is a per-head output gate, ``sigmoid(norm(x) @ wg)``
    on each head's output before ``wo`` (arXiv:2505.06708, headwise);
    ``q_norm`` and ``k_norm`` are an RMSNorm of q and k before rope, over
    the whole vector or, with a weight of a head's size, over each head."""
    # The named scopes here and below (embed, attn_qkv, flash, attn_out,
    # mlp, head_loss) are metadata only: they name the device time of a
    # step in a profiler trace and change no instruction.
    b, s, _ = x.shape
    hd = cfg.head_dim_
    with jax.named_scope("attn_qkv"):
        h1 = llama.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q = jnp.dot(h1, p["wq"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        k = jnp.dot(h1, p["wk"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        v = jnp.dot(h1, p["wv"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        if "bq" in p:  # Qwen2-style qkv biases (structure is trace-static)
            q = q + p["bq"].astype(cfg.dtype)
            k = k + p["bk"].astype(cfg.dtype)
            v = v + p["bv"].astype(cfg.dtype)
        # a q/k norm's weight says what it is over: [hd] each head's dims
        # (LFM2), else the whole q and k vectors (OLMoE)
        per_head = "q_norm" in p and p["q_norm"].shape[-1] == hd
        if "q_norm" in p and not per_head:
            q = llama.rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = llama.rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        heads = p["wq"].shape[-1] // hd
        q = q.reshape(b, s, heads, hd)
        k = k.reshape(b, s, cfg.num_kv_heads, hd)
        v = v.reshape(b, s, cfg.num_kv_heads, hd)
        if per_head:
            q = llama.rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = llama.rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        # named for a remat level that keeps them (REMAT_LADDER; no-ops
        # otherwise): the backward then skips the q/k/v matmuls and rope
        q = llama.checkpoint_name(q, "q_rope")
        k = llama.checkpoint_name(k, "k_rope")
        v = llama.checkpoint_name(v, "v_proj")
        if "wg" in p:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(jnp.dot(
                    h1, p["wg"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32))
    # a window layer's kernel calls are ``flash_win`` inside ``flash``: a
    # reader that knows ``flash`` alone still finds them there
    with jax.named_scope("flash"):
        if window is None:
            attn = llama._attend(cfg, q, k, v, mesh=mesh, seq_axis=seq_axis)
        else:
            with jax.named_scope("flash_win"):
                attn = llama._attend(cfg, q, k, v, mesh=mesh, seq_axis=seq_axis,
                               window=window)
    with jax.named_scope("attn_out"):
        if "wg" in p:
            with jax.named_scope("attn_gate"):
                attn = (attn.astype(jnp.float32) * gate[..., None]
                        ).astype(cfg.dtype)
        attn = attn.reshape(b, s, heads * hd)
        attn_out = jnp.dot(
            attn, p["wo"].astype(cfg.dtype),
            preferred_element_type=jnp.float32).astype(cfg.dtype)
        return llama.checkpoint_name(x + attn_out, "attn_resid")


def _attention_caller(caller):
    """(cfg, one layer's weights, window) as ``caller``'s model hands them
    to ``attention_block``."""
    from ray_tpu.models import laguna, lfm2, olmoe

    if caller in ("llama", "qwen2-bias"):
        cfg = llama.LlamaConfig.tiny(attn_impl="reference",
                                     attn_qkv_bias=caller == "qwen2-bias")
        layers = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
        if caller == "qwen2-bias":
            layers = {k: v + 0.1 if k in ("bq", "bk", "bv") else v
                      for k, v in layers.items()}
        return cfg, {k: v[0] for k, v in layers.items()}, None
    if caller == "olmoe":
        cfg = olmoe.OlmoeConfig.tiny(attn_impl="reference")
        layers = olmoe.init_params(cfg, jax.random.PRNGKey(0))["layers"]
        return cfg, {k: v[0] for k, v in layers.items()}, None
    if caller == "lfm2":
        cfg = lfm2.Lfm2Config.tiny(attn_impl="reference")
        layers = lfm2.init_params(cfg, jax.random.PRNGKey(0))["layers"]
        return cfg, {k: v[0] for k, v in layers["attn_moe"].items()}, None
    cfg = laguna.LagunaConfig.tiny(attn_impl="reference")
    layers = laguna.init_params(cfg, jax.random.PRNGKey(0))["layers"]
    kind = next(k for k in layers if k.startswith("sliding"))
    return (cfg, {k: v[0] for k, v in layers[kind].items()},
            cfg.sliding_window)


@pytest.mark.parametrize("caller", ["llama", "qwen2-bias", "olmoe", "lfm2",
                                    "laguna-window"])
def test_attention_block_is_bit_equal_for_its_callers(caller):
    """Every caller from before the rope became optional (plain, with qkv
    biases, a q/k norm over the whole vector, one over each head, a gated
    window layer): the block's output and its program are what
    ``_attention_block_before``, the function as it stood, gives."""
    cfg, p, window = _attention_caller(caller)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    cos, sin = llama.rope_frequencies(cfg.head_dim_, 32, cfg.rope_theta,
                                      dtype=cfg.dtype)
    now = jax.jit(lambda x, p: llama.attention_block(
        cfg, x, p, cos, sin, window=window))
    before = jax.jit(lambda x, p: _attention_block_before(
        cfg, x, p, cos, sin, window=window))
    np.testing.assert_array_equal(np.asarray(now(x, p)),
                                  np.asarray(before(x, p)))
    strip = lambda t: __import__("re").sub(r"loc\(.*?\)|#loc.*", "", t)
    assert strip(now.lower(x, p).as_text()) == strip(
        before.lower(x, p).as_text())


def _head_case(tied):
    """The tiny llama with a last norm that is not all ones, hidden states
    and targets for the blocked head's tests; ``top`` holds the leaves a
    head's gradient reaches."""
    from dataclasses import replace

    cfg = replace(llama.LlamaConfig.tiny(), tie_embeddings=tied)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    params["final_norm"] = params["final_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), params["final_norm"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.hidden_size))
    targets = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                                 cfg.vocab_size)
    top = {k: params[k] for k in ("final_norm",
                                  "embed" if tied else "lm_head")}
    return cfg, params, top, x, targets


def _assert_head_gradients_close(got_g, want_g):
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("path", ["rows", "sum", "sum-masked"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
def test_blocked_head_and_loss_match_the_whole_one(tied, path):
    """The blocked head against ``_final_head`` + ``cross_entropy_loss``
    on the same hidden states: the loss and the gradients with respect to
    the hidden states, the last norm and the head, at three block sizes
    (1e-5: the blocks' head gradients are added in another order).
    ``rows``: the mean of ``blocked_token_nll``'s positions, which a
    checkpointed block's backward rebuilds; ``sum``: the training loss,
    ``blocked_cross_entropy``, whose rule takes a block's gradients while
    its logits stand, with a mask that zeroes a third of the positions
    and without."""
    cfg, params, top, x, targets = _head_case(tied)
    mask = ((jnp.arange(48).reshape(2, 24) % 3 != 1).astype(jnp.float32)
            if path == "sum-masked" else None)

    def whole(top, x):
        return llama.cross_entropy_loss(
            llama._final_head(cfg, {**params, **top}, x) / 8.0, targets,
            mask)

    def blocked(block):
        if path == "rows":
            return lambda top, x: llama.blocked_token_nll(
                cfg, {**params, **top}, x, targets, block=block,
                logits_divisor=8.0).mean()
        return lambda top, x: llama.blocked_cross_entropy(
            cfg, {**params, **top}, x, targets, mask, block=block,
            logits_divisor=8.0)

    want, want_g = jax.value_and_grad(whole, argnums=(0, 1))(top, x)
    for block in (48, 16, 1):
        got, got_g = jax.jit(jax.value_and_grad(
            blocked(block), argnums=(0, 1)))(top, x)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        _assert_head_gradients_close(got_g, want_g)
    with pytest.raises(ValueError, match="not whole blocks"):
        blocked(5)(top, x)
    assert llama.head_block(32768, 100352) == 2048
    assert llama.head_block(30, 256) == 30


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "lm_head"])
def test_blocked_head_loss_under_a_cotangent_other_than_one(tied):
    """The rule's kept gradients are scaled by what comes back: three
    times the loss plus another term of the hidden states gives the whole
    head's gradients of the same function; the weights' gradient is the
    rows' loss, the targets take none."""
    from ray_tpu.ops.layers import blocked_head_loss, blocked_head_nll

    cfg, params, top, x, targets = _head_case(tied)

    def whole(top, x):
        return 3.0 * llama.cross_entropy_loss(
            llama._final_head(cfg, {**params, **top}, x) / 8.0, targets
        ) + jnp.sum(jnp.sin(x))

    def blocked(top, x):
        return 3.0 * llama.blocked_cross_entropy(
            cfg, {**params, **top}, x, targets, block=16,
            logits_divisor=8.0) + jnp.sum(jnp.sin(x))

    want, want_g = jax.value_and_grad(whole, argnums=(0, 1))(top, x)
    got, got_g = jax.jit(jax.value_and_grad(blocked, argnums=(0, 1)))(top, x)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    _assert_head_gradients_close(got_g, want_g)

    rows, head = x.reshape(48, -1), params["embed"].T
    weights = jax.random.uniform(jax.random.PRNGKey(4), (48,))
    d_weights = jax.grad(lambda w: 2.0 * blocked_head_loss(
        rows, head, targets.reshape(48), w, block=16))(weights)
    np.testing.assert_allclose(
        np.asarray(d_weights), 2.0 * np.asarray(blocked_head_nll(
            rows, head, targets.reshape(48), block=16)), rtol=1e-6)


@pytest.mark.parametrize("blocks", [1, 3, 48])
def test_blocked_head_loss_runs_three_products_a_block(blocks):
    """The static witness of the rule: a block's body holds three
    ``dot_general`` under ``value_and_grad`` (the logits, ``dx`` and the
    head's share) where the checkpointed rows hold four, one where nothing
    is differentiated, and no product outside the blocks' scan."""
    cfg, params, top, x, targets = _head_case(True)

    def products(fn, scans=1):
        text = str(jax.make_jaxpr(fn)(top, x))
        assert text.count(" scan[") == scans
        return text.count("dot_general")

    def loss(top, x):
        return llama.blocked_cross_entropy(
            cfg, {**params, **top}, x, targets, block=48 // blocks)

    def rows(top, x):
        return llama.blocked_token_nll(
            cfg, {**params, **top}, x, targets, block=48 // blocks).mean()

    assert products(jax.value_and_grad(loss, argnums=(0, 1))) == 3
    assert products(loss) == 1
    assert products(jax.value_and_grad(rows, argnums=(0, 1)), scans=2) == 4
