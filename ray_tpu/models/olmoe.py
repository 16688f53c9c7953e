"""OLMoE (allenai/OLMoE-1B-7B): a decoder of many small experts.

The Llama attention stack (``llama.attention_block``) with an RMSNorm
over the whole q and k projection vectors before the split into heads,
and every MLP a dropless routed mixture (``ops/moe.routed_experts``): 64
SwiGLU experts of width 1024, 8 per token, no shared expert, softmax over
all 64 router logits in float32, the 8 gate weights **not** renormalised.

Loss = cross entropy + ``router_aux_coef`` x load-balancing loss
(transformers' ``load_balancing_loss_func``: E * sum_e f_e * P_e over all
layers' tokens, f from the top-k choices) + ``router_z_coef`` x router
z-loss (mean over tokens and layers of ``logsumexp(router_logits)**2``;
OLMoE paper, arXiv:2409.02060, section 3; transformers leaves it out).
A batch's ``mask`` weights the cross entropy only.

Parameters are Mixtral's pytree (``router``, ``e_gate``, ``e_up``,
``e_down`` stacked ``[L, E, ...]``) plus ``q_norm`` and ``k_norm``.
Training only: the serving engines do not know the model (ROADMAP D6).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral
from ray_tpu.ops.layers import embed_rows, rms_norm, rope_frequencies
from ray_tpu.ops.moe import (routed_experts_on, routed_part, router_losses,
                             router_stats)


@dataclass(frozen=True)
class OlmoeConfig(mixtral.MixtralConfig):
    # ``intermediate_size`` is the width of ONE expert
    num_experts: int = 64
    top_k: int = 8
    norm_topk_prob: bool = False
    qk_norm: bool = True
    router_aux_coef: float = 0.01
    router_z_coef: float = 0.001

    @classmethod
    def olmoe_1b_7b(cls, **kw) -> "OlmoeConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct's config.json: 6.92 B
        parameters, 1.28 B of them in a token's matmuls."""
        cfg = cls(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                  num_layers=16, num_heads=16, num_kv_heads=16,
                  max_seq_len=4096, rope_theta=10_000.0, rms_norm_eps=1e-5)
        return replace(cfg, **kw)

    @classmethod
    def tiny(cls, **kw) -> "OlmoeConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=32,
                  num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=128,
                  dtype=jnp.float32, remat=False, num_experts=8, top_k=2)
        return replace(cfg, **kw)


def logical_axes(cfg: OlmoeConfig) -> Dict[str, Any]:
    axes = mixtral.logical_axes(cfg)
    if cfg.qk_norm:
        axes["layers"].update({"q_norm": ("layer", "qkv"),
                               "k_norm": ("layer", "qkv")})
    return axes


def init_params(cfg: OlmoeConfig, key: jax.Array) -> Dict[str, Any]:
    params = mixtral.init_params(cfg, key)
    if cfg.qk_norm:
        hd, L = cfg.head_dim_, cfg.num_layers
        params["layers"]["q_norm"] = jnp.ones((L, cfg.num_heads * hd),
                                              cfg.param_dtype)
        params["layers"]["k_norm"] = jnp.ones((L, cfg.num_kv_heads * hd),
                                              cfg.param_dtype)
    return params


# the stack's one kind, as ``forward`` describes it to the plan
LAYER_KINDS = {"layer": (llama.attention_part(qk_norm="whole"),
                         routed_part(balance=True,
                                     width="intermediate_size"))}


def _layer(cfg: OlmoeConfig, x, p, cos, sin, mesh=None,
           keep_router_logits: bool = False):
    x = llama.attention_block(cfg, x, p, cos, sin, mesh=mesh)
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        out, logits, counts = routed_experts_on(
            mesh, h2, p["router"], p["e_gate"], p["e_up"], p["e_down"],
            cfg.top_k, renormalize=cfg.norm_topk_prob)
        router = router_stats(logits, counts)
        if keep_router_logits:
            router["logits"] = logits
        return x + out, router


def forward(cfg: OlmoeConfig, params, tokens: jax.Array, mesh=None,
            keep_router_logits: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens [b, s] -> (logits [b, s, vocab] float32, router): per layer
    ``counts [L, E]`` (rows each expert multiplied), ``prob [L, E]``
    (mean router probability), ``z [L]`` (mean squared logsumexp of the
    router logits) and, asked for, ``logits [L, b * s, E]``."""
    with jax.named_scope("embed"):
        x = embed_rows(params["embed"], tokens, cfg.dtype, mesh)
        cos, sin = rope_frequencies(cfg.head_dim_, tokens.shape[1],
                                    cfg.rope_theta, dtype=cfg.dtype,
                                    scaling=cfg.rope_scaling_dict)
    level = llama.resolve_remat(cfg, LAYER_KINDS, params, tokens, mesh,
                                param_shardings) if cfg.remat else None
    x, router = llama.run_layers(
        lambda x_, p_: _layer(cfg, x_, p_, cos, sin, mesh=mesh,
                              keep_router_logits=keep_router_logits),
        x, params["layers"], level=level, scan=cfg.scan_layers)
    return llama._final_head(cfg, params, x), router


def loss_terms(cfg: OlmoeConfig, params, batch: Dict[str, jax.Array],
               mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(loss, its three terms and the per-layer expert counts [L, E]):
    made for ``jax.value_and_grad(..., has_aux=True)``."""
    tokens = batch["tokens"]
    logits, router = forward(cfg, params, tokens[:, :-1], mesh=mesh)
    mask = batch.get("mask")
    ce = llama.cross_entropy_loss(logits, tokens[:, 1:],
                                  None if mask is None else mask[:, 1:])
    balance, z = router_losses(cfg, router)
    loss = ce + cfg.router_aux_coef * balance + cfg.router_z_coef * z
    return loss, {"cross_entropy": ce, "load_balance": balance,
                  "router_z": z, "expert_counts": router["counts"]}


def loss_fn(cfg: OlmoeConfig, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    return loss_terms(cfg, params, batch, mesh=mesh)[0]


def param_shardings(cfg: OlmoeConfig, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(mixtral.without_layer_axis(logical_axes(cfg)),
                             mesh)
