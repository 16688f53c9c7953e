"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro, ``granitemoehybrid``
with no routed experts): Mamba-2 layers with an attention layer among
every ten, and the Granite family's four multipliers.

``h0 = embedding_multiplier * embed[tokens]``; every layer, with ``r =
residual_multiplier``, is ``h = h + r * Mixer(norm(h))`` then ``h = h + r *
SwiGLU(norm(h))`` (one SwiGLU of ``shared_intermediate_size`` in every
layer: ``num_local_experts`` is 0); ``logits = norm(h_L) @ embed.T /
logits_scaling``.

- ``Mixer`` of a ``mamba`` layer is ``ops/ssm.mamba2_mixer``: an
  in-projection to ``z | x B C | dt``, a causal depthwise convolution of
  ``ssm_conv_taps`` taps with bias and a silu over ``x B C``, the
  selective scan in chunks (state ``[ssm_head_dim, ssm_state]`` a head,
  float32), the skip ``D x``, a gated RMSNorm over all ``ssm_heads *
  ssm_head_dim`` channels, an out-projection.
- ``Mixer`` of an ``attention`` layer is ``llama.attention_block`` with no
  position embedding (``position_embedding_type`` "nope": q and k are not
  rotated), scores scaled by ``attention_multiplier`` and not by the head
  size.

The head is the embedding, tied. ``loss_terms`` never builds the logits
whole: ``llama.blocked_token_nll`` walks blocks of tokens (100,352 rows
at 32,768 positions would be 13 GB of float32). ``forward`` builds them,
for sizes at which they fit. Parameters are stacked by kind (``mamba``,
``attention``; ``llama.run_layers`` walks ``cfg.pattern``). The
initialisation is Mamba-2's published one: ``A`` uniform in 1-16 (stored
as its log), ``dt`` log-uniform in 0.001-0.1 stored through the inverse
softplus as ``dt_bias``, ``D`` and the norms 1. Training only: the serving
engines keep no scan state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral
from ray_tpu.ops.layers import rms_norm, swiglu
from ray_tpu.ops.ssm import mamba2_mixer

# granite-4.0-h-micro's layer_types: attention at these four of its 40
ATTENTION_LAYERS_MICRO = (5, 15, 25, 35)


@dataclass(frozen=True)
class GraniteConfig(llama.LlamaConfig):
    # one entry a layer: True = attention, False = Mamba-2
    attention_layers: Tuple[bool, ...] = (False, True)
    ssm_heads: int = 64                     # mamba_n_heads
    ssm_head_dim: int = 64                  # mamba_d_head
    ssm_state: int = 128                    # mamba_d_state
    ssm_groups: int = 1                     # mamba_n_groups
    ssm_conv_taps: int = 4                  # mamba_d_conv
    ssm_chunk: int = 256                    # mamba_chunk_size
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    tie_embeddings: bool = True

    def __post_init__(self):
        super().__post_init__()
        if len(self.attention_layers) != self.num_layers:
            raise ValueError(
                f"attention_layers names {len(self.attention_layers)} "
                f"layers, num_layers is {self.num_layers}")
        if not self.tie_embeddings:
            raise ValueError("the head is the embedding: there is no other")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_groups does not divide ssm_heads")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple("attention" if attn else "mamba"
                     for attn in self.attention_layers)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @classmethod
    def granite_4_0_h_micro(cls, **kw) -> "GraniteConfig":
        """ibm-granite/granite-4.0-h-micro's config.json: 40 layers, 36
        Mamba-2 and 4 attention, 3.19 B parameters. ``attention_layers``
        names a cut of the stack."""
        sizes = dict(vocab_size=100_352, hidden_size=2048,
                     intermediate_size=8192, num_layers=40, num_heads=32,
                     num_kv_heads=8, head_dim=64, max_seq_len=131_072,
                     rms_norm_eps=1e-5,
                     attention_layers=tuple(
                         l in ATTENTION_LAYERS_MICRO for l in range(40)))
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "GraniteConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_seq_len=64,
                  attention_layers=(False, False, True, False),
                  ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
                  attention_multiplier=0.0625, rms_norm_eps=1e-5,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


def _kind_shapes(cfg: GraniteConfig, kind: str) -> Dict[str, Tuple]:
    """name -> (shape of one layer's parameter, how it starts: a fan-in
    for a truncated normal, 0 = ones, or the name of a Mamba-2 rule)."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    if kind == "attention":
        hd = cfg.head_dim_
        qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
        shapes = {"attn_norm": ((h,), 0), "wq": ((h, qd), h),
                  "wk": ((h, kvd), h), "wv": ((h, kvd), h),
                  "wo": ((qd, h), qd)}
    else:
        d, conv, H = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads
        taps = cfg.ssm_conv_taps
        shapes = {"op_norm": ((h,), 0), "m_in": ((h, d + conv + H), h),
                  "m_conv": ((conv, taps), taps),
                  "m_conv_bias": ((conv,), "zeros"),
                  "dt_bias": ((H,), "dt"), "A_log": ((H,), "A"),
                  "D": ((H,), 0), "m_norm": ((d,), 0),
                  "m_out": ((d, h), d)}
    shapes.update(mlp_norm=((h,), 0), w_gate=((h, f), h), w_up=((h, f), h),
                  w_down=((f, h), f))
    return shapes


_AXES = {"attn_norm": ("embed",), "op_norm": ("embed",),
         "mlp_norm": ("embed",),
         "wq": ("embed", "qkv"), "wk": ("embed", "qkv"),
         "wv": ("embed", "qkv"), "wo": ("qkv", "embed"),
         "m_in": ("embed", "mlp"), "m_conv": ("mlp", None),
         "m_conv_bias": ("mlp",), "dt_bias": (None,), "A_log": (None,),
         "D": (None,), "m_norm": ("mlp",), "m_out": ("mlp", "embed"),
         "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
         "w_down": ("mlp", "embed")}


def logical_axes(cfg: GraniteConfig) -> Dict[str, Any]:
    return {"embed": ("vocab", "embed"),
            "layers": {kind: {name: ("layer",) + _AXES[name]
                              for name in _kind_shapes(cfg, kind)}
                       for kind in dict.fromkeys(cfg.pattern)},
            "final_norm": ("embed",)}


def init_params(cfg: GraniteConfig, key: jax.Array) -> Dict[str, Any]:
    """Matrices and taps truncated normal (fan-in scaled) in
    ``cfg.param_dtype``, norms and ``D`` at 1, the taps' bias at 0; ``A``
    uniform in 1-16 as ``A_log``, ``dt`` log-uniform in 0.001-0.1 (not
    under 1e-4) as ``dt_bias = dt + log(-expm1(-dt))``, the inverse of the
    softplus (Mamba-2's published initialisation); a kind's layers
    stacked in their order."""
    def draw(k, shape, how):
        if how == "zeros":
            return jnp.zeros(shape, cfg.param_dtype)
        if how == "A":
            return jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)).astype(cfg.param_dtype)
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
            "final_norm": jnp.ones((h,), cfg.param_dtype)}


def _layer(cfg: GraniteConfig, kind: str, x, p, mesh=None):
    """One layer -> (x, its scan's state after the last position [b, H,
    P, N] float32; None for an attention layer)."""
    dt = cfg.dtype
    r = cfg.residual_multiplier
    if kind == "attention":
        x = llama.attention_block(cfg, x, p, None, None, mesh=mesh,
                                  sm_scale=cfg.attention_multiplier,
                                  resid_scale=r)
        S = None
    else:
        out, S = mamba2_mixer(
            rms_norm(x, p["op_norm"], cfg.rms_norm_eps), p,
            heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, groups=cfg.ssm_groups, chunk=cfg.ssm_chunk,
            eps=cfg.rms_norm_eps, mesh=mesh)
        x = x + out * jnp.asarray(r, dt)
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        mlp = swiglu(h2, p["w_gate"].astype(dt), p["w_up"].astype(dt),
                     p["w_down"].astype(dt))
        return x + mlp * jnp.asarray(r, dt), S


def hidden(cfg: GraniteConfig, params, tokens: jax.Array, mesh=None
           ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s] -> (the last layer's output [b, s, hidden], the scan
    layers' states after the last position [Lm, b, H, P, N] float32)."""
    with jax.named_scope("embed"):
        x = (params["embed"].astype(cfg.dtype)[tokens]
             * jnp.asarray(cfg.embedding_multiplier, cfg.dtype))
    pattern = cfg.pattern

    def layer_of(kind):
        return lambda x_, p_: _layer(cfg, kind, x_, p_, mesh=mesh)

    level = llama.resolve_remat(
        cfg, params, tokens, mesh, param_shardings, pattern=pattern,
        head_tokens=llama.head_block(tokens.size, cfg.vocab_size),
        scan=(cfg.ssm_groups, cfg.ssm_chunk, mesh)) if cfg.remat else None
    x, ys = llama.run_layers(
        {kind: layer_of(kind) for kind in params["layers"]}, x,
        params["layers"], level=level, scan=cfg.scan_layers, pattern=pattern)
    return x, ys["mamba"]


def forward(cfg: GraniteConfig, params, tokens: jax.Array, mesh=None
            ) -> jax.Array:
    """tokens [b, s] -> logits [b, s, vocab] float32, whole."""
    x, _ = hidden(cfg, params, tokens, mesh=mesh)
    return llama._final_head(cfg, params, x) / cfg.logits_scaling


def token_nll(cfg: GraniteConfig, params, tokens: jax.Array, mesh=None,
              head_block: Optional[int] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s + 1] -> (the next-token loss of every position [b, s]
    float32 through the blocked head, the scan layers' last states as
    ``hidden`` gives them)."""
    x, states = hidden(cfg, params, tokens[:, :-1], mesh=mesh)
    return llama.blocked_token_nll(
        cfg, params, x, tokens[:, 1:], block=head_block,
        logits_divisor=cfg.logits_scaling), states


def loss_terms(cfg: GraniteConfig, params, batch: Dict[str, jax.Array],
               mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(cross entropy, it again and the counter ``ssm_state_abs_max``, the
    largest ``|S|`` any scan layer's state holds after the last position):
    made for ``jax.value_and_grad(..., has_aux=True)``."""
    nll, states = token_nll(cfg, params, batch["tokens"], mesh=mesh)
    mask = batch.get("mask")
    if mask is None:
        ce = nll.mean()
    else:
        mask = mask[:, 1:]
        ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return ce, {"cross_entropy": ce,
                "ssm_state_abs_max": jnp.abs(states).max()}


def loss_fn(cfg: GraniteConfig, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    return loss_terms(cfg, params, batch, mesh=mesh)[0]


def param_shardings(cfg: GraniteConfig, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(mixtral.without_layer_axis(logical_axes(cfg)),
                             mesh)
