"""Mixtral-style sparse MoE transformer, TPU-first.

"Mixtral 8x7B MoE, expert-parallel" is one of the north-star
configurations; the reference delegates the model to torch, and this is
the JAX-native design:

- Llama backbone (same attention stack, rms_norm/rope/GQA) with the dense
  MLP replaced by a top-k routed mixture of SwiGLU experts.
- Sorted, dropless dispatch (``ops/moe.routed_experts``): the (token,
  choice) pairs are sorted by expert and each expert multiplies its own
  ragged group of rows. Every shape is static, no row is ever dropped,
  as in the published model.
- Switch load-balancing auxiliary loss keeps routing uniform.

Parity oracle: with num_experts=1, top_k=1 the MoE layer reduces exactly
to the dense SwiGLU MLP (tested).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.ops.layers import embed_rows, rms_norm, rope_frequencies
from ray_tpu.ops.moe import routed_experts


@dataclass(frozen=True)
class MixtralConfig(llama.LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    router_aux_coef: float = 0.01

    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MixtralConfig":
        cfg = cls(hidden_size=4096, intermediate_size=14336, num_layers=32,
                  num_heads=32, num_kv_heads=8, vocab_size=32000,
                  num_experts=8, top_k=2)
        return replace(cfg, **kw)

    @classmethod
    def tiny(cls, **kw) -> "MixtralConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2,
                  max_seq_len=128, dtype=jnp.float32, remat=False,
                  num_experts=4, top_k=2)
        return replace(cfg, **kw)


def logical_axes(cfg: MixtralConfig) -> Dict[str, Any]:
    """Parameter logical axes; expert dims map to the ep mesh axis."""
    base = llama.logical_axes(cfg)
    L = ("layer",)
    base["layers"].pop("w_gate")
    base["layers"].pop("w_up")
    base["layers"].pop("w_down")
    base["layers"].update({
        "router": L + ("embed", "expert"),
        "e_gate": L + ("expert", "embed", "mlp"),
        "e_up": L + ("expert", "embed", "mlp"),
        "e_down": L + ("expert", "mlp", "embed"),
    })
    return base


def without_layer_axis(axes):
    return jax.tree_util.tree_map(
        lambda t: tuple(None if a == "layer" else a for a in t),
        axes, is_leaf=lambda x: isinstance(x, tuple))


def init_params(cfg: MixtralConfig, key: jax.Array) -> Dict[str, Any]:
    params = llama.init_params(cfg, key)
    h, ffn, L, E = (cfg.hidden_size, cfg.intermediate_size,
                    cfg.num_layers, cfg.num_experts)
    for name in ("w_gate", "w_up", "w_down"):
        params["layers"].pop(name)
    keys = jax.random.split(jax.random.fold_in(key, 7), 4)

    def norm_init(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -3, 3, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(cfg.param_dtype)

    params["layers"].update({
        "router": norm_init(keys[0], (L, h, E), h),
        "e_gate": norm_init(keys[1], (L, E, h, ffn), h),
        "e_up": norm_init(keys[2], (L, E, h, ffn), h),
        "e_down": norm_init(keys[3], (L, E, ffn, h), ffn),
    })
    return params


def moe_layer(cfg: MixtralConfig, p, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Routed expert MLP. x: [b, s, h] -> (out [b, s, h], aux_loss)."""
    b, s, h = x.shape
    n = b * s
    E = cfg.num_experts
    # renormalized gate weights (Mixtral convention); no row is dropped
    out, logits, _ = routed_experts(
        x.reshape(n, h), p["router"], p["e_gate"], p["e_up"], p["e_down"],
        cfg.top_k, renormalize=True)
    # Switch aux loss: fraction of tokens routed * mean router prob per
    # expert (computed on the top-1 assignment)
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[jnp.argmax(probs, -1)].add(1.0) / n
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return out.reshape(b, s, h), aux


def _layer(cfg: MixtralConfig, x, p, cos, sin, mesh=None):
    """One decoder block: shared llama attention + MoE MLP."""
    x = llama.attention_block(cfg, x, p, cos, sin, mesh=mesh)
    h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    moe_out, aux = moe_layer(cfg, p, h2)
    return x + moe_out, aux


def forward(cfg: MixtralConfig, params, tokens: jax.Array, mesh=None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s] -> (logits [b, s, vocab] fp32, aux_loss scalar)."""
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, cfg.dtype, mesh)
        cos, sin = rope_frequencies(cfg.head_dim_, tokens.shape[1],
                                    cfg.rope_theta, dtype=cfg.dtype,
                                    scaling=cfg.rope_scaling_dict)
    x, auxes = llama.run_layers(
        lambda x_, p_: _layer(cfg, x_, p_, cos, sin, mesh=mesh),
        x, params["layers"], level=llama.remat_level_without_plan(cfg),
        scan=cfg.scan_layers)
    return llama._final_head(cfg, params, x), auxes.sum()


def loss_fn(cfg: MixtralConfig, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    tokens = batch["tokens"]
    logits, aux = forward(cfg, params, tokens[:, :-1], mesh=mesh)
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    return llama.cross_entropy_loss(logits, tokens[:, 1:], mask) + aux


def param_shardings(cfg: MixtralConfig, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(without_layer_axis(logical_axes(cfg)), mesh)
