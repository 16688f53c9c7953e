"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B, ``olmo_hybrid``): three gated
delta-rule layers to one full-attention layer, in OLMo 2's block.

``h0 = embed[tokens]``; every layer norms a sublayer's output and not its
input (OLMo 2 and 3): ``h = h + RMSNorm(Mixer(h))`` then ``h = h +
RMSNorm(SwiGLU(h))``; ``logits = RMSNorm(h_L) @ lm_head``, untied.

- ``Mixer`` of a ``linear`` layer (``layer_types`` ``linear_attention``) is
  ``ops/delta.gated_delta_mixer``: an in-projection to ``z | q k v | a |
  b``, a causal depthwise convolution of ``linear_conv_taps`` taps and a
  silu over ``q k v`` (no bias), an L2 norm of each head's q and k, the
  gated delta rule in chunks (state ``[linear_value_dim, linear_key_dim]``
  a head, float32; ``beta = 2 sigmoid(b)``: ``linear_allow_neg_eigval``),
  an RMSNorm of each head's output gated by ``silu(z)``, an
  out-projection.
- ``Mixer`` of a ``full`` layer (``full_attention``) is
  ``llama.attention_block`` with an RMSNorm over the whole q and k vectors
  (OLMo 2, OLMoE) and no position embedding (``rope_theta`` null: q and k
  are not rotated).

``loss_terms`` never builds the logits whole (``llama.blocked_token_nll``);
``forward`` builds them, for sizes at which they fit. Parameters are
stacked by kind (``linear``, ``full``; ``llama.run_layers`` walks
``cfg.pattern``). The initialisation is the delta-net's published one:
``A`` uniform in 0-16 (stored as its log), ``dt`` log-uniform in 0.001-0.1
stored through the inverse softplus as ``g_dt_bias``, norms 1. Training
only: the serving engines keep no rule state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral
from ray_tpu.ops.delta import gated_delta_mixer
from ray_tpu.ops.layers import rms_norm, swiglu


@dataclass(frozen=True)
class OlmoHybridConfig(llama.LlamaConfig):
    # one entry a layer: True = full attention, False = gated delta rule
    attention_layers: Tuple[bool, ...] = (False, False, False, True)
    linear_heads: int = 30          # linear_num_key_heads = .._value_heads
    linear_key_dim: int = 96        # linear_key_head_dim
    linear_value_dim: int = 192     # linear_value_head_dim
    linear_conv_taps: int = 4       # linear_conv_kernel_dim
    rule_chunk: int = 64            # positions a chunk of the rule
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        if len(self.attention_layers) != self.num_layers:
            raise ValueError(
                f"attention_layers names {len(self.attention_layers)} "
                f"layers, num_layers is {self.num_layers}")
        if self.tie_embeddings:
            raise ValueError("the head is a matrix of its own")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple("full" if attn else "linear"
                     for attn in self.attention_layers)

    @property
    def linear_conv_dim(self) -> int:
        """The channels the taps run over: q, k and v."""
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)

    @classmethod
    def olmo_hybrid_7b(cls, **kw) -> "OlmoHybridConfig":
        """allenai/Olmo-Hybrid-7B's config.json: 32 layers, every fourth
        full attention, 7.43 B parameters. ``attention_layers`` names a
        cut of the stack."""
        sizes = dict(vocab_size=100_352, hidden_size=3840,
                     intermediate_size=11_008, num_layers=32, num_heads=30,
                     num_kv_heads=30, head_dim=128, max_seq_len=65_536,
                     attention_layers=tuple(
                         l % 4 == 3 for l in range(kw.get("num_layers", 32))))
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "OlmoHybridConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=4, num_heads=4, num_kv_heads=4, head_dim=16,
                  max_seq_len=64, linear_heads=4, linear_key_dim=16,
                  linear_value_dim=32, rule_chunk=8, dtype=jnp.float32,
                  remat=False)
        return replace(cfg, **kw)


# the name ``benchmark/cells/train_hybrid.load_model`` and
# ``tools/step_program.py`` make of the module's
Olmo_hybridConfig = OlmoHybridConfig


def _kind_shapes(cfg: OlmoHybridConfig, kind: str) -> Dict[str, Tuple]:
    """name -> (shape of one layer's parameter, how it starts: a fan-in
    for a truncated normal, 0 = ones, or the name of a delta-net rule)."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    if kind == "full":
        hd = cfg.head_dim_
        qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
        shapes = {"wq": ((h, qd), h), "wk": ((h, kvd), h),
                  "wv": ((h, kvd), h), "q_norm": ((qd,), 0),
                  "k_norm": ((kvd,), 0), "wo": ((qd, h), qd),
                  "attn_post_norm": ((h,), 0)}
    else:
        H, conv = cfg.linear_heads, cfg.linear_conv_dim
        hv = H * cfg.linear_value_dim
        taps = cfg.linear_conv_taps
        shapes = {"g_in": ((h, hv + conv + 2 * H), h),
                  "g_conv": ((conv, taps), taps),
                  "g_dt_bias": ((H,), "dt"), "g_A_log": ((H,), "A"),
                  "g_norm": ((cfg.linear_value_dim,), 0),
                  "g_out": ((hv, h), hv), "op_post_norm": ((h,), 0)}
    shapes.update(w_gate=((h, f), h), w_up=((h, f), h), w_down=((f, h), f),
                  mlp_post_norm=((h,), 0))
    return shapes


_AXES = {"attn_post_norm": ("embed",), "op_post_norm": ("embed",),
         "mlp_post_norm": ("embed",),
         "wq": ("embed", "qkv"), "wk": ("embed", "qkv"),
         "wv": ("embed", "qkv"), "wo": ("qkv", "embed"),
         "q_norm": ("qkv",), "k_norm": ("qkv",),
         "g_in": ("embed", "mlp"), "g_conv": ("mlp", None),
         "g_dt_bias": (None,), "g_A_log": (None,), "g_norm": (None,),
         "g_out": ("mlp", "embed"),
         "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
         "w_down": ("mlp", "embed")}


def logical_axes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    return {"embed": ("vocab", "embed"),
            "layers": {kind: {name: ("layer",) + _AXES[name]
                              for name in _kind_shapes(cfg, kind)}
                       for kind in dict.fromkeys(cfg.pattern)},
            "final_norm": ("embed",),
            "lm_head": ("embed", "vocab")}


def init_params(cfg: OlmoHybridConfig, key: jax.Array) -> Dict[str, Any]:
    """Matrices and taps truncated normal (fan-in scaled) in
    ``cfg.param_dtype``, norms at 1; ``A`` uniform in 0-16 as ``g_A_log``,
    ``dt`` log-uniform in 0.001-0.1 (not under 1e-4) as ``g_dt_bias = dt +
    log(-expm1(-dt))``, the inverse of the softplus (the delta-net's
    published initialisation); a kind's layers stacked in their order."""
    def draw(k, shape, how):
        if how == "A":
            return jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 0.0, 16.0)).astype(cfg.param_dtype)
        if how == "dt":
            dt = jnp.maximum(1e-4, jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(0.001), math.log(0.1))))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.param_dtype)
        if not how:
            return jnp.ones(shape, cfg.param_dtype)
        return (jax.random.truncated_normal(k, -3, 3, shape, jnp.float32)
                * (1.0 / math.sqrt(how))).astype(cfg.param_dtype)

    h, v = cfg.hidden_size, cfg.vocab_size
    layers = {}
    for n, kind in enumerate(dict.fromkeys(cfg.pattern)):
        depth = cfg.pattern.count(kind)
        shapes = _kind_shapes(cfg, kind)
        keys = jax.random.split(jax.random.fold_in(key, n + 1), len(shapes))
        layers[kind] = {name: draw(k, (depth,) + shape, how)
                        for k, (name, (shape, how))
                        in zip(keys, shapes.items())}
    return {"embed": draw(jax.random.fold_in(key, 0), (v, h), h),
            "layers": layers,
            "final_norm": jnp.ones((h,), cfg.param_dtype),
            "lm_head": draw(jax.random.fold_in(key, 99), (h, v), h)}


def _layer(cfg: OlmoHybridConfig, kind: str, x, p, mesh=None):
    """One layer -> (x, its rule's state after the last position [b, H,
    V, K] float32; None for a full layer)."""
    dt = cfg.dtype
    if kind == "full":
        x = llama.attention_block(cfg, x, p, None, None, mesh=mesh)
        S = None
    else:
        out, S = gated_delta_mixer(
            x, p, heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
            value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk,
            eps=cfg.rms_norm_eps, mesh=mesh)
        x = x + rms_norm(out, p["op_post_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mlp"):
        mlp = swiglu(x, p["w_gate"].astype(dt), p["w_up"].astype(dt),
                     p["w_down"].astype(dt))
        return x + rms_norm(mlp, p["mlp_post_norm"], cfg.rms_norm_eps), S


def hidden(cfg: OlmoHybridConfig, params, tokens: jax.Array, mesh=None
           ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s] -> (the last layer's output [b, s, hidden], the
    linear layers' states after the last position [Ll, b, H, V, K]
    float32)."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    pattern = cfg.pattern

    def layer_of(kind):
        return lambda x_, p_: _layer(cfg, kind, x_, p_, mesh=mesh)

    level = llama.resolve_remat(
        cfg, params, tokens, mesh, param_shardings, pattern=pattern,
        head_tokens=llama.head_block(tokens.size, cfg.vocab_size),
        rule=(cfg.linear_key_dim, cfg.rule_chunk, mesh)
    ) if cfg.remat else None
    x, ys = llama.run_layers(
        {kind: layer_of(kind) for kind in params["layers"]}, x,
        params["layers"], level=level, scan=cfg.scan_layers, pattern=pattern)
    return x, ys["linear"]


def forward(cfg: OlmoHybridConfig, params, tokens: jax.Array, mesh=None
            ) -> jax.Array:
    """tokens [b, s] -> logits [b, s, vocab] float32, whole."""
    x, _ = hidden(cfg, params, tokens, mesh=mesh)
    return llama._final_head(cfg, params, x)


def token_nll(cfg: OlmoHybridConfig, params, tokens: jax.Array, mesh=None,
              head_block: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s + 1] -> (the next-token loss of every position [b, s]
    float32 through the blocked head, the linear layers' last states as
    ``hidden`` gives them)."""
    x, states = hidden(cfg, params, tokens[:, :-1], mesh=mesh)
    return llama.blocked_token_nll(cfg, params, x, tokens[:, 1:],
                                   block=head_block), states


def loss_terms(cfg: OlmoHybridConfig, params, batch: Dict[str, jax.Array],
               mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(cross entropy, it again and the counter ``gdn_state_abs_max``, the
    largest ``|S|`` any linear layer's state holds after the last
    position): made for ``jax.value_and_grad(..., has_aux=True)``."""
    nll, states = token_nll(cfg, params, batch["tokens"], mesh=mesh)
    mask = batch.get("mask")
    if mask is None:
        ce = nll.mean()
    else:
        mask = mask[:, 1:]
        ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return ce, {"cross_entropy": ce,
                "gdn_state_abs_max": jnp.abs(states).max()}


def loss_fn(cfg: OlmoHybridConfig, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    return loss_terms(cfg, params, batch, mesh=mesh)[0]


def param_shardings(cfg: OlmoHybridConfig, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(mixtral.without_layer_axis(logical_axes(cfg)),
                             mesh)
