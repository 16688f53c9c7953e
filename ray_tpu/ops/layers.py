"""Transformer building blocks: RMSNorm (plain and gated a head at a time), an
L2 norm, a LayerNorm, RoPE (one position stream or several), SwiGLU, a
squared-ReLU MLP, a head and loss over blocks of tokens; and ``Part``, the
record a layer's mixer or MLP is to ``models/stack.py``.

Pure-jax implementations — XLA fuses these elementwise chains into the
surrounding matmuls on TPU (the guide's rule: don't hand-schedule what the
compiler already fuses). Pallas is reserved for ops XLA can't fuse well
(attention — see ops/attention.py).

The reference framework has no kernel library (it delegates to torch); these
ops underpin the model zoo (models/llama.py etc.).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.util import tracing


class Leaf(NamedTuple):
    """One parameter of one layer. ``start``: a fan-in (truncated normal
    over its root), "ones", "zeros", "zeros_float32" (whatever the
    parameters' dtype), "dt" (log-uniform in 0.001-0.1, stored through the
    inverse softplus) or ``(lo, hi)`` (uniform in that range, stored as its
    log); ``models/stack.draw`` reads it. ``axes``: the logical axes
    ``parallel/sharding.py`` maps onto a mesh."""
    shape: Tuple[int, ...]
    start: Any
    axes: Tuple[Optional[str], ...]


class Ctx(NamedTuple):
    """What a forward hands every part's body beside the layer's own
    input: the mesh the arrays are sharded over, what each part's ``once``
    made (keyed by that function), whether the routers' logits and
    choices are asked for, and whether an index's inputs and its choice
    of keys are."""
    mesh: Any
    once: Dict[Callable, Any]
    keep_router_logits: bool = False
    keep_index_choice: bool = False


@dataclass(frozen=True)
class Part:
    """A layer's mixer or its MLP, kept beside the code that runs it. A
    model is a table ``kind -> its parts in order`` (``(mixer, mlp)``, or
    the one part of a layer that has one sum) of these and
    ``models/stack.py`` walks it; what two models that share a part differ
    in is an argument of the part's maker, which names a field of the
    config, or the field itself.

    ``leaves(cfg)``: name -> ``Leaf``, in the order the keys of
    ``init_params`` are dealt. ``body(cfg, x, p, ctx) -> (x, said)``: the
    sublayer with its norm and its residual; ``said`` is ``{}`` or
    ``{reports: what this layer reports}``. ``keeps(cfg, shape, tokens,
    mesh)``: what ``llama.describe_stack`` adds up for ``remat_plan``,
    from the shapes ``shape[name]`` of one layer's leaves and the tokens a
    device holds (``kept``). ``once(cfg, tokens)``: what the body wants
    made once a forward, in the ``embed`` scope (rope tables), found at
    ``ctx.once[once]``. ``terms(cfg, reports stacked in layer order) ->
    (what they add to the loss or None, the entries of ``loss_terms``'
    second result)``."""
    leaves: Callable[[Any], Dict[str, Leaf]]
    body: Callable
    keeps: Callable
    once: Optional[Callable] = None
    reports: Optional[str] = None
    terms: Optional[Callable] = None


def kept(first: int = 0, qkv: int = 0, mlp: int = 0, resid: int = 0,
         width: int = 0, rows: int = 0) -> Dict[str, Any]:
    """What a part's ``keeps`` returns: the bytes each rung of
    ``llama.REMAT_LADDER`` keeps in one layer (``first``: what the
    mixer's kind has on the first rung, an attention layer's kernel output
    and log-sum-exp, a latent layer's two latents, an index layer's
    choice, a scan layer's in-projection), the elements a token its
    backward holds (the recomputed forward and the gradients of the
    widest of it) and the bytes it holds that are not a token's."""
    return {"rungs": (first, qkv, mlp, resid), "width": width, "rows": rows}


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             zero_centred: bool = False) -> jax.Array:
    """RMSNorm in fp32 accumulation, cast back to input dtype.
    ``zero_centred``: the scale is ``1 + weight`` (float32), a weight drawn
    as zeros (Qwen3-Next: a decay then pulls the scale to 1, not to 0)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    scale = weight.astype(jnp.float32)
    if zero_centred:
        scale = 1.0 + scale
    return (normed * scale).astype(dtype)


def norm_start(cfg) -> str:
    """How a norm's weight starts (``Leaf.start``): "zeros" where the
    config's norms are zero-centred, "ones" elsewhere."""
    return "zeros" if cfg.zero_centred_norm else "ones"


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    """LayerNorm over the last axis in float32 (an index key's)."""
    xf = x.astype(jnp.float32)
    xf = xf - xf.mean(-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
    return (xf * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def l2_norm(x: jax.Array, eps: float = 1e-6, scale: float = 1.0) -> jax.Array:
    """``scale * x / sqrt(sum(x^2) + eps)`` over the last axis (a head's
    dims), float32 inside, one rounding back to the input dtype: the q and
    k of a delta-rule layer (``ops/delta.py``)."""
    xf = x.astype(jnp.float32)
    norm = jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (xf * (norm * scale)).astype(x.dtype)


def gated_rms_norm(x: jax.Array, gate: jax.Array, weight: jax.Array,
                   eps: float = 1e-6) -> jax.Array:
    """``RMSNorm(x; weight) * silu(gate)`` over the last axis (x and gate
    [..., heads, dim], weight [dim]: each head normed alone, then gated),
    float32 inside, cast back to the input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return (normed * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float = 10_000.0,
                     dtype=jnp.float32, scaling: Optional[dict] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables: [max_seq_len, head_dim//2].

    ``scaling``: optional Llama-3.x long-context frequency scaling (the
    HF ``rope_scaling`` dict with rope_type="llama3"): low-frequency
    components are divided by ``factor`` (stretching their period to the
    extended context), high-frequency components are untouched, and the
    band between ``low_freq_factor`` and ``high_freq_factor`` wavelengths
    interpolates smoothly — matching transformers'
    modeling_rope_utils._compute_llama3_parameters.
    """
    import math

    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    attention_factor = 1.0
    if scaling:
        rope_type = scaling.get("rope_type") or scaling.get("type")
        if rope_type == "llama3":
            factor = float(scaling["factor"])
            low = float(scaling.get("low_freq_factor", 1.0))
            high = float(scaling.get("high_freq_factor", 4.0))
            old_len = float(scaling.get(
                "original_max_position_embeddings", 8192))
            wavelen = 2.0 * jnp.pi / inv_freq
            # short wavelengths (high freq): keep; long wavelengths (low
            # freq): divide by factor; the band between interpolates
            smooth = (old_len / wavelen - low) / (high - low)
            scaled = ((1.0 - smooth) * (inv_freq / factor)
                      + smooth * inv_freq)
            inv_freq = jnp.where(
                wavelen < old_len / high, inv_freq,
                jnp.where(wavelen > old_len / low, inv_freq / factor,
                          scaled))
        elif rope_type == "linear":
            # position interpolation (transformers
            # _compute_linear_scaling_rope): all frequencies divide by
            # the factor
            inv_freq = inv_freq / float(scaling["factor"])
        elif rope_type == "yarn":
            # NTK-by-parts (YaRN, arXiv:2309.00071) — mirrors
            # transformers' _compute_yarn_parameters exactly: low-freq
            # dims interpolate (1/factor), high-freq dims extrapolate
            # (untouched), a linear ramp blends between, and the cos/sin
            # tables scale by the attention factor (mscale).
            factor = float(scaling["factor"])
            beta_fast = float(scaling.get("beta_fast") or 32)
            beta_slow = float(scaling.get("beta_slow") or 1)
            old_len = float(
                scaling.get("original_max_position_embeddings")
                or max_seq_len)
            mscale = scaling.get("mscale")
            mscale_all_dim = scaling.get("mscale_all_dim")

            def get_mscale(scale, ms=1.0):
                if scale <= 1:
                    return 1.0
                return 0.1 * ms * math.log(scale) + 1.0

            attention_factor = scaling.get("attention_factor")
            if attention_factor is None:
                if mscale and mscale_all_dim:
                    attention_factor = float(
                        get_mscale(factor, mscale)
                        / get_mscale(factor, mscale_all_dim))
                else:
                    attention_factor = get_mscale(factor)

            def correction_dim(num_rotations):
                return (head_dim * math.log(
                    old_len / (num_rotations * 2 * math.pi))
                    ) / (2 * math.log(theta))

            low = correction_dim(beta_fast)
            high = correction_dim(beta_slow)
            if scaling.get("truncate", True):
                low, high = math.floor(low), math.ceil(high)
            low, high = max(low, 0), min(high, head_dim - 1)
            if low == high:
                high += 0.001  # prevent singularity
            ramp = jnp.clip(
                (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                / (high - low), 0.0, 1.0)
            extrapolation_factor = 1.0 - ramp
            inv_freq = ((inv_freq / factor)
                        * (1.0 - extrapolation_factor)
                        + inv_freq * extrapolation_factor)
        else:
            raise ValueError(
                f"unsupported rope_scaling type {rope_type!r} "
                f"(implemented: 'llama3', 'linear', 'yarn')")
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    # attention factor (yarn mscale) scales the tables in float32 first,
    # like transformers' cos() * attention_scaling before the cast
    return ((jnp.cos(freqs) * attention_factor).astype(dtype),
            (jnp.sin(freqs) * attention_factor).astype(dtype))


def mrope_frequencies(head_dim: int, positions: jax.Array,
                      sections: Tuple[int, ...], theta: float = 10_000.0,
                      dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """RoPE tables from the batch's own positions in several streams
    (Qwen2-VL's multimodal rope, chunked): positions ``[streams, b, s]``
    (temporal, height, width), ``sections`` the count of the ``head_dim //
    2`` frequency pairs each stream turns, in order (pair ``i`` of [16, 24,
    24] turns by stream 0 for ``i < 16``, by stream 1 for ``16 <= i < 40``,
    by stream 2 beyond). -> (cos, sin) ``[b, s, head_dim // 2]``, which
    ``apply_rope`` takes as they are. Where the streams are all ``0 .. s -
    1`` (text) the tables are ``rope_frequencies``' bit for bit. The scope
    ``mrope``; one kept span as it is traced, ``rtpu.mrope.plan``."""
    half = head_dim // 2
    if sum(sections) != half or len(sections) != positions.shape[0]:
        raise ValueError(
            f"sections {tuple(sections)} do not split the {half} frequency "
            f"pairs of a head of {head_dim} over {positions.shape[0]} "
            "position streams")
    with tracing.span("rtpu.mrope.plan", keep=True, streams=len(sections),
                      sections=list(sections), head_dim=head_dim,
                      theta=theta, table_shape=list(positions.shape[1:])
                      + [half]):
        pass
    with jax.named_scope("mrope"):
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                      / head_dim))
        stream = [n for n, pairs in enumerate(sections) for _ in range(pairs)]
        # [b, s, half]: pair i's position is its stream's
        pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[
            ..., jnp.asarray(stream)]
        freqs = pos * inv_freq
        return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """Apply rotary embeddings.

    x: [..., seq, heads, head_dim]; cos/sin: [max_seq, rotated // 2], or
    ``mrope_frequencies``' [batch, seq, rotated // 2], a table row for each
    position of the batch already;
    positions: [..., seq] absolute positions (defaults to arange).
    The first ``2 * cos.shape[-1]`` dims of a head are rotated (as two
    halves) and the rest pass: a partial rotary factor is the width the
    tables were made for.
    """
    rotated = 2 * cos.shape[-1]
    if rotated < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotated], cos, sin, positions),
             x[..., rotated:]], axis=-1)
    seq = x.shape[-3]
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    elif positions is None:
        c = cos[:seq][:, None, :]
        s = sin[:seq][:, None, :]
    else:
        c = cos[positions][..., None, :]
        s = sin[positions][..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    cf = c.astype(jnp.float32)
    sf = s.astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cf - x2f * sf, x2f * cf + x1f * sf], axis=-1
    )
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, act: str = "silu") -> jax.Array:
    """Gated MLP: down( act(x @ gate) * (x @ up) ).

    ``act`` selects the gate nonlinearity: "silu" (llama's SwiGLU),
    "gelu_tanh" (gemma's GeGLU — HF hidden_act gelu_pytorch_tanh), or
    "gelu" (exact erf GELU). Unknown names raise — a typo'd activation
    must not silently train the wrong model.
    All matmuls in input dtype (bf16 on TPU) with fp32 accumulation via
    preferred_element_type.
    """
    try:
        act_fn = {"silu": jax.nn.silu,
                  "gelu_tanh": partial(jax.nn.gelu, approximate=True),
                  "gelu": partial(jax.nn.gelu, approximate=False)}[act]
    except KeyError:
        raise ValueError(f"unknown gated-MLP activation {act!r} "
                         "(silu | gelu_tanh | gelu)") from None
    # accumulate in f32 INSIDE the dot, but store the [b, s, ffn]
    # intermediates in the input dtype: keeping gate/up in f32 doubled
    # the MLP's HBM activation traffic and measured ~7% of the whole
    # 1B train step on v5e (profile: three f32[8,2048,5504] fusions per
    # layer). The activation itself is bounded, so bf16 is safe — and
    # XLA folds the convert into the matmul epilogue.
    # The two products are named for a layer's remat policy
    # (models/llama.py REMAT_LADDER; inert elsewhere): kept, the backward
    # recomputes act(gate) * up from them and neither matmul.
    gate = checkpoint_name(
        jnp.dot(x, w_gate,
                preferred_element_type=jnp.float32).astype(x.dtype),
        "mlp_gate")
    up = checkpoint_name(
        jnp.dot(x, w_up,
                preferred_element_type=jnp.float32).astype(x.dtype),
        "mlp_up")
    h = act_fn(gate) * up
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu_kept(tokens: int, width: int, itemsize: int) -> Dict[str, Any]:
    """A SwiGLU of ``width``: the MLP rung keeps its two products; the
    backward holds the three ``[T, width]`` arrays and the gradients of
    two."""
    return kept(mlp=2 * tokens * width * itemsize, width=5 * width)


def relu2_mlp(x: jax.Array, w_up: jax.Array, w_down: jax.Array
              ) -> jax.Array:
    """Squared-ReLU MLP: down( relu(x @ up)^2 ), two matrices and no gate
    (``nemotron_h``'s ``mlp_hidden_act`` "relu2"). As ``swiglu``: float32
    accumulation inside the dots, the ``[.., width]`` product stored in the
    input dtype and named for the MLP rung of a layer's remat level."""
    up = checkpoint_name(
        jnp.dot(x, w_up, preferred_element_type=jnp.float32).astype(x.dtype),
        "mlp_up")
    return jnp.dot(jnp.square(jax.nn.relu(up)), w_down,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def relu2_kept(tokens: int, width: int, itemsize: int) -> Dict[str, Any]:
    """A squared-ReLU MLP of ``width``: the MLP rung keeps its one product;
    the backward holds the product, its square and the gradient of one."""
    return kept(mlp=tokens * width * itemsize, width=3 * width)


def swiglu_part(norm: str = "pre", resid: Optional[str] = None) -> Part:
    """A dense SwiGLU of ``cfg.intermediate_size`` as a layer's MLP:
    ``x + r * swiglu(RMSNorm(x))`` (``norm="pre"``, llama's order; ``resid``
    names the config's field ``r`` where it is not 1: Granite's
    ``residual_multiplier``) or ``x + RMSNorm(swiglu(x))`` (``"post"``,
    OLMo 2's)."""
    def leaves(cfg):
        h, f = cfg.hidden_size, cfg.intermediate_size
        mats = {"w_gate": Leaf((h, f), h, ("embed", "mlp")),
                "w_up": Leaf((h, f), h, ("embed", "mlp")),
                "w_down": Leaf((f, h), f, ("mlp", "embed"))}
        if norm == "pre":
            return {"mlp_norm": Leaf((h,), "ones", ("embed",)), **mats}
        return {**mats, "mlp_post_norm": Leaf((h,), "ones", ("embed",))}

    def body(cfg, x, p, ctx):
        dt = cfg.dtype
        with jax.named_scope("mlp"):
            h2 = (rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
                  if norm == "pre" else x)
            mlp = swiglu(h2, p["w_gate"].astype(dt), p["w_up"].astype(dt),
                         p["w_down"].astype(dt), act=cfg.mlp_act)
            if norm == "post":
                mlp = rms_norm(mlp, p["mlp_post_norm"], cfg.rms_norm_eps)
            if resid is not None:
                mlp = mlp * jnp.asarray(getattr(cfg, resid), dt)
            return x + mlp, {}

    def keeps(cfg, shape, tokens, mesh):
        return swiglu_kept(tokens, shape["w_gate"][-1],
                           jnp.dtype(cfg.dtype).itemsize)

    return Part(leaves, body, keeps)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """Expand KV heads for grouped-query attention.

    x: [batch, seq, kv_heads, head_dim] → [batch, seq, kv_heads*n_rep, hd].
    """
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, h, n_rep, d)
    ).reshape(b, s, h * n_rep, d)


# float32 logits one block of ``blocked_head_nll`` may hold
HEAD_BLOCK_BYTES = 1 << 30


def head_block(tokens: int, vocab: int) -> int:
    """Tokens a block of ``blocked_head_nll``: the largest divisor of
    ``tokens`` whose float32 logits are within ``HEAD_BLOCK_BYTES``."""
    most = max(1, HEAD_BLOCK_BYTES // (4 * vocab))
    return max(n for n in range(1, min(tokens, most) + 1) if tokens % n == 0)


def _block_nll(xb: jax.Array, head: jax.Array, tb: jax.Array,
               logits_divisor: float
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One block of the blocked head: xb [block, h], tb [block] -> its
    float32 logits [block, vocab], their log-sum-exp [block] and the rows'
    loss [block]. Both walks below run this and nothing else on a block's
    logits in the forward."""
    logits = jnp.dot(xb, head, preferred_element_type=jnp.float32)
    if logits_divisor != 1.0:
        logits = logits / logits_divisor
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return logits, lse, lse - jnp.take_along_axis(
        logits, tb[:, None], axis=-1)[:, 0]


def _head_blocks(block: Optional[int], x: jax.Array, head: jax.Array,
                 *rows: jax.Array) -> Tuple[jax.Array, ...]:
    """x [T, h] and each of ``rows`` [T] as ``lax.scan`` walks them:
    [T / block, block, ...]."""
    T = x.shape[0]
    block = block or head_block(T, head.shape[-1])
    if T % block:
        raise ValueError(f"{T} tokens are not whole blocks of {block}")
    return tuple(a.reshape(T // block, block, *a.shape[1:])
                 for a in (x, *rows))


def blocked_head_nll(x: jax.Array, head: jax.Array, targets: jax.Array,
                     block: Optional[int] = None,
                     logits_divisor: float = 1.0) -> jax.Array:
    """x [T, h] (normed), head [h, vocab], targets [T] -> the next-token
    loss of every row [T] float32, a block of rows at a time: a block's
    logits ``[block, vocab]`` live inside one step of a ``lax.scan`` under
    ``jax.checkpoint``, so whoever differentiates the rows' losses with
    weights of their own pays for the logits a second time in the backward,
    block by block, and has each block's gradient of the head added to the
    sum so far in the head's own dtype. A training step's loss is
    ``blocked_head_loss``, which does not. 100,352 rows at 32,768 positions
    would be 13 GB of float32 whole."""

    @jax.checkpoint
    def one_block(_, xt):
        return None, _block_nll(xt[0], head, xt[1], logits_divisor)[2]

    _, nll = jax.lax.scan(one_block, None,
                          _head_blocks(block, x, head, targets))
    return nll.reshape(x.shape[0])


def blocked_head_loss(x: jax.Array, head: jax.Array, targets: jax.Array,
                      weights: jax.Array, block: Optional[int] = None,
                      logits_divisor: float = 1.0) -> jax.Array:
    """``sum(weights * blocked_head_nll(x, head, targets))``, float32, as a
    training step differentiates it: x [T, h] (normed), head [h, vocab],
    targets [T], weights [T] float32 (``1 / T`` for a mean, a mask over its
    sum). The loss is linear in the rows' losses and the weights are known
    in the forward, so the rule that ``jax.grad`` runs takes both gradients
    of a block while its float32 logits stand:
    ``d_logits = weights * (softmax - onehot) / logits_divisor``,
    ``dx = d_logits @ head^T`` and the block's share of
    ``d_head = xb^T @ d_logits``, three ``[block, vocab]`` products a block
    where a checkpointed block runs four and the log-sum-exp's passes
    twice; the backward only scales what was kept by the cotangent.
    ``dx [T, h]`` is kept in float32 and ``d_head [h, vocab]`` in the
    head's dtype, each block's float32 share added to the sum so far and
    rounded once (a float32 sum reads and writes 822 MB a block at
    Granite's widths and cost its product half again its time on a v5e:
    PERF.md 6, PR 45). Not differentiated, one product a block and no
    gradient. ``targets`` takes no gradient, ``weights`` the rows'
    losses."""

    @jax.custom_vjp
    def loss(head, x_blocks, targets, weights):
        def one_block(total, xtw):
            xb, tb, wb = xtw
            nll = _block_nll(xb, head, tb, logits_divisor)[2]
            return total + jnp.sum(wb * nll), None
        return jax.lax.scan(one_block, jnp.zeros((), jnp.float32),
                            (x_blocks, targets, weights))[0]

    def forward(head, x_blocks, targets, weights):
        def one_block(carry, xtw):
            total, d_head = carry
            # the block's rows as an array of their own before the products
            # read them: sliced from the stack inside each product, XLA
            # tiles the logits' and d_head's worse on a v5e (4.4 and 5.5 ms
            # a block at Granite's widths for 5.1 and 6.3: PERF.md 6, PR 45)
            xb, tb, wb = jax.lax.optimization_barrier(xtw)
            logits, lse, nll = _block_nll(xb, head, tb, logits_divisor)
            soft = jnp.exp(logits - lse[:, None])
            hit = jax.lax.broadcasted_iota(
                jnp.int32, soft.shape, 1) == tb[:, None]
            d_logits = (jnp.where(hit, soft - 1.0, soft)
                        * (wb / logits_divisor)[:, None])
            # the float32 operand against the head's dtype, as jax's own
            # transpose of the forward product has it
            dx = jax.lax.dot_general(
                d_logits, head, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            d_head = (d_head + jax.lax.dot_general(
                xb, d_logits, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)).astype(head.dtype)
            return (total + jnp.sum(wb * nll), d_head), (dx, nll)

        (total, d_head), (dx, nll) = jax.lax.scan(
            one_block, (jnp.zeros((), jnp.float32), jnp.zeros_like(head)),
            (x_blocks, targets, weights))
        return total, (dx, d_head, nll)

    def backward(kept, g):
        dx, d_head, nll = kept
        return ((g * d_head).astype(head.dtype), (g * dx).astype(x.dtype),
                None, g * nll)

    loss.defvjp(forward, backward)
    return loss(head, *_head_blocks(block, x, head, targets,
                                    weights.astype(jnp.float32)))


def _divisor_tile(size: int, limit: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``limit``; ``size`` itself where it fits or has no such divisor."""
    if size <= limit:
        return size
    return next((t for t in range(limit - limit % 128, 0, -128)
                 if size % t == 0), min(size, limit))


# How wide the tokens' float32 sums are where a pass adds its rows to them.
# XLA's scatter-add of rows on a v5e is no cliff in the width but a sawtooth
# (``tools/scatter_sweep.py``, PR 44: 3,072 rows into ``[8192, w]`` float32,
# the table donated, ms a 1,024 columns without the call's 0.6 ms): 0.24 at
# 1,024 and 1,280, 0.31 at 2,048, then 1.45 at 2,560 and back to 0.28 at
# 2,816; 0.34 at 3,072, 1.38 at 3,840, 0.25 at 4,096; 0.57, 0.80, 1.14 and
# **3.05 at 5,120** (15.8 ms the call, 30.5 into 16,384 rows, 22.6 with
# 36,864 rows: a cost of the table, not of a row), 0.37 at 5,376; 0.49 at
# 6,144, 3.02 at 7,680, 0.37 at 8,192. The same 5,120 columns in four sums
# of 1,280 read 2.2 ms for 15.9, in two of 2,560 8.4; 6,144 in six of 1,024
# 2.4 for 3.6, 8,192 in eight 2.9 for 3.7. So: one sum up to 4,096 columns,
# the widest width read fast whole, which keeps the statement that Laguna's
# 3,072 and LFM2's 2,048 columns compile (2.5 ms for 11,520 rows, 3.7 for
# 36,864); past it blocks of at most 1,280 columns, under which every width
# read fast. The layer at DeepSeek-V2's shape (8,192 x 5,120, 8 of 160
# experts): forward 18.8 -> 5.2 ms, backward 25.0 -> 11.1.
_SUM_WHOLE = 4096
_SUM_COLUMNS = 1280


def _sum_columns(h: int) -> int:
    """Columns of a block of the tokens' ``[n, h]`` sums: ``h`` itself up to
    ``_SUM_WHOLE``; past it the largest divisor of ``h`` in whole 128-lane
    tiles that is at most ``_SUM_COLUMNS`` (``h`` where it has none)."""
    if h <= _SUM_WHOLE:
        return h
    width = _divisor_tile(h, _SUM_COLUMNS)
    return h if h % width else width


def _add_rows(sums, tokens, rows):
    """``sums`` with ``rows [chunk, h]`` float32 added at ``tokens
    [chunk]``, block by block of columns (one block: ``sums.at[tokens]
    .add(rows)``, the slice of all columns traces to nothing)."""
    width = sums[0].shape[1]
    return tuple(block.at[tokens].add(rows[:, j * width:(j + 1) * width])
                 for j, block in enumerate(sums))


def _join_sums(sums, dtype):
    """The blocks side by side, rounded once to ``dtype``."""
    return jnp.concatenate([block.astype(dtype) for block in sums], axis=1)


def embed_plan(rows: int, table_rows: int, columns: int,
               mesh=None) -> Dict[str, Any]:
    """What ``embed_rows`` does with ``rows`` tokens into a ``[table_rows,
    columns]`` table, and in which ``form``: "blocked" where the width is
    past ``_SUM_WHOLE`` with a divisor for ``_sum_columns`` and no mesh is
    given (the gradient's rows are added into ``blocks`` tables of
    ``sum_columns`` columns), "whole" elsewhere (jax's own transpose, one
    scatter-add of all columns)."""
    width = columns if mesh is not None else _sum_columns(columns)
    return {"rows": rows, "table_rows": table_rows, "columns": columns,
            "sum_columns": width, "blocks": columns // width,
            "form": "whole" if width == columns else "blocked"}


# A token gather's gradient is XLA's scatter-add of the cotangent's rows into
# a table of zeros, on the same sawtooth (``tools/scatter_sweep.py``'s
# ``embedding`` part, v5e, PR 51: the gradient alone, its join and cast with
# it, bfloat16 table and sums, ms whole -> in blocks of ``_sum_columns``):
# 8,192 tokens into ``[12800, 5120]`` **24.1 -> 3.3** and 16,384 into
# ``[19008, 5120]`` **37.0 -> 5.7** (four blocks of 1,280); 7,680 columns
# 36.2 -> 4.4 and 54.4 -> 7.7 (six of 1,280), 6,144 5.1 -> 3.4 and 9.0 -> 6.0
# (six of 1,024); at 4,096 the whole sum is the faster, 2.4 and 3.7 against
# 2.6 and 4.3 in four of 1,024, so ``_SUM_WHOLE`` stands for this op too. The
# teeth under it, which the rule leaves whole (no cell's text may move for
# them here): 3,840 columns 8.5 -> 2.6 and 13.0 -> 4.3 in three of 1,280, 2,560
# 6.3 -> 1.9 and 9.3 -> 3.0 in two. Float32 sums rounded once at the join read
# 0.4-1.4 ms slower than bfloat16 ones in every blocked reading (3.8 and 6.5
# at 5,120), so the sums stay in the cotangent's dtype, as jax's transpose
# holds its one.
def embed_rows(table: jax.Array, tokens: jax.Array, dtype,
               mesh=None) -> jax.Array:
    """``table.astype(dtype)[tokens]``: table [rows, columns], tokens any
    shape of ints -> [*tokens.shape, columns]. Where ``embed_plan`` says
    "whole" (a width within ``_SUM_WHOLE``, one without a divisor, any width
    under a ``mesh``) it is that expression and nothing around it. Past it
    the gather has a transpose of its own: the cotangent's rows, every
    token's, a repeated token's each time, are added in the cotangent's
    dtype (what jax's transpose holds its one sum in) into ``blocks`` tables
    of zeros of ``sum_columns`` columns, joined and converted to the table's
    dtype, under the scope ``embed``. The kept span ``rtpu.embed.plan`` of a
    traced call says which."""
    plan = embed_plan(tokens.size, *table.shape, mesh)
    with tracing.span("rtpu.embed.plan", keep=True, **plan):
        pass

    def plain(table, tokens):
        return table.astype(dtype)[tokens]

    if plan["form"] == "whole":
        return plain(table, tokens)

    def backward(tokens, ct):
        with jax.named_scope("embed"):
            sums = tuple(
                jnp.zeros((plan["table_rows"], plan["sum_columns"]), ct.dtype)
                for _ in range(plan["blocks"]))
            sums = _add_rows(sums, tokens.reshape(-1),
                             ct.reshape(-1, plan["columns"]))
            return _join_sums(sums, table.dtype), None

    gather = jax.custom_vjp(plain)
    gather.defvjp(lambda table, tokens: (plain(table, tokens), tokens),
                  backward)
    return gather(table, tokens)
