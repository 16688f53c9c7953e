"""GPT-2 family (decoder-only, learned positions, LayerNorm, GELU MLP).

Covers the reference north-star config "GPT-2-125M on wikitext-2". Same
TPU-first structure as llama.py: stacked layers walked by its
``run_layers``, logical axis names, bf16/fp32 mix, optional remat.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import cross_entropy_loss, run_layers
from ray_tpu.ops.attention import attention_reference, flash_attention


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50_257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def gpt2_125m(cls, **kw) -> "GPT2Config":
        return replace(cls(), **kw)

    @classmethod
    def tiny(cls, **kw) -> "GPT2Config":
        return replace(
            cls(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=128, dtype=jnp.float32, remat=False), **kw)


def logical_axes(cfg: GPT2Config) -> Dict[str, Any]:
    L = ("layer",)
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "layers": {
            "ln1_g": L + ("embed",), "ln1_b": L + ("embed",),
            "w_qkv": L + ("embed", "qkv"), "b_qkv": L + ("qkv",),
            "w_proj": L + ("qkv", "embed"), "b_proj": L + ("embed",),
            "ln2_g": L + ("embed",), "ln2_b": L + ("embed",),
            "w_fc": L + ("embed", "mlp"), "b_fc": L + ("mlp",),
            "w_out": L + ("mlp", "embed"), "b_out": L + ("embed",),
        },
        "lnf_g": ("embed",), "lnf_b": ("embed",),
    }


def logical_axes_without_layer(cfg: GPT2Config):
    return jax.tree_util.tree_map(
        lambda t: tuple(None if a == "layer" else a for a in t),
        logical_axes(cfg), is_leaf=lambda x: isinstance(x, tuple))


def init_params(cfg: GPT2Config, key: jax.Array) -> Dict[str, Any]:
    h, L = cfg.hidden_size, cfg.num_layers
    keys = jax.random.split(key, 6)

    def ninit(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            cfg.param_dtype)

    return {
        "wte": ninit(keys[0], (cfg.vocab_size, h)),
        "wpe": ninit(keys[1], (cfg.max_seq_len, h), 0.01),
        "layers": {
            "ln1_g": jnp.ones((L, h), cfg.param_dtype),
            "ln1_b": jnp.zeros((L, h), cfg.param_dtype),
            "w_qkv": ninit(keys[2], (L, h, 3 * h)),
            "b_qkv": jnp.zeros((L, 3 * h), cfg.param_dtype),
            "w_proj": ninit(keys[3], (L, h, h), 0.02 / math.sqrt(2 * L)),
            "b_proj": jnp.zeros((L, h), cfg.param_dtype),
            "ln2_g": jnp.ones((L, h), cfg.param_dtype),
            "ln2_b": jnp.zeros((L, h), cfg.param_dtype),
            "w_fc": ninit(keys[4], (L, h, 4 * h)),
            "b_fc": jnp.zeros((L, 4 * h), cfg.param_dtype),
            "w_out": ninit(keys[5], (L, 4 * h, h), 0.02 / math.sqrt(2 * L)),
            "b_out": jnp.zeros((L, h), cfg.param_dtype),
        },
        "lnf_g": jnp.ones((h,), cfg.param_dtype),
        "lnf_b": jnp.zeros((h,), cfg.param_dtype),
    }


def _layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _attend(cfg: GPT2Config, q, k, v):
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "flash":
        return flash_attention(q, k, v, causal=True)
    return attention_reference(q, k, v, causal=True)


def _layer(cfg: GPT2Config, x, p):
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim

    h1 = _layer_norm(x, p["ln1_g"], p["ln1_b"], cfg.ln_eps)
    qkv = (jnp.dot(h1, p["w_qkv"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)
           + p["b_qkv"].astype(jnp.float32)).astype(cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nh, hd)
    v = v.reshape(b, s, nh, hd)
    attn = _attend(cfg, q, k, v).reshape(b, s, h)
    proj = (jnp.dot(attn, p["w_proj"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32)
            + p["b_proj"].astype(jnp.float32)).astype(cfg.dtype)
    x = x + proj

    h2 = _layer_norm(x, p["ln2_g"], p["ln2_b"], cfg.ln_eps)
    fc = (jnp.dot(h2, p["w_fc"].astype(cfg.dtype),
                  preferred_element_type=jnp.float32)
          + p["b_fc"].astype(jnp.float32))
    act = jax.nn.gelu(fc).astype(cfg.dtype)
    out = (jnp.dot(act, p["w_out"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)
           + p["b_out"].astype(jnp.float32)).astype(cfg.dtype)
    return x + out


def forward(cfg: GPT2Config, params, tokens: jax.Array) -> jax.Array:
    """tokens [b, s] → logits [b, s, vocab] (tied embeddings, as GPT-2)."""
    b, s = tokens.shape
    x = (params["wte"].astype(cfg.dtype)[tokens]
         + params["wpe"].astype(cfg.dtype)[:s][None])

    x, _ = run_layers(lambda x_, p_: (_layer(cfg, x_, p_), None), x,
                      params["layers"], level="full" if cfg.remat else None,
                      scan=True)
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.ln_eps)
    return jnp.dot(x, params["wte"].T.astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(cfg: GPT2Config, params, batch) -> jax.Array:
    tokens = batch["tokens"]
    logits = forward(cfg, params, tokens[:, :-1])
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    return cross_entropy_loss(logits, tokens[:, 1:], mask)


def param_shardings(cfg: GPT2Config, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(logical_axes_without_layer(cfg), mesh)
