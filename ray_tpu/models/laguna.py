"""Laguna (poolside/Laguna-S-2.1): window and full attention layers of
unequal width in one stack, a shared expert beside a routed mixture.

Three kinds of layer, all from the family's pieces:

- attention (``llama.attention_block``): 8 kv heads of 128; a **full**
  layer has 48 query heads, causal attention and rope on the first half of
  each head (theta 500,000, yarn); a **sliding** layer has 72 query heads,
  sees ``sliding_window`` keys back and rotates the whole head (theta
  10,000). Every layer gates each head's output by ``sigmoid(norm(x) @
  wg)`` before ``wo``. One full layer in four.
- MLP: the layers in ``mlp_only_layers`` (layer 0) a dense SwiGLU of
  ``intermediate_size``; every other layer a shared SwiGLU expert
  (``ops/layers.swiglu``), added ungated, beside a routed mixture
  (``ops/moe.routed_experts``): softmax over all ``num_experts`` router
  logits in float32, the ``top_k`` largest, renormalised and scaled by
  ``routed_scale``.

``experts_held=(first, count)`` is this chip's share of each routed layer
under expert parallelism: the router keeps its ``num_experts`` outputs,
the expert weights are ``[count, ...]``, and a layer adds what its held
experts give (``ops/moe.py``). ``None`` holds them all.

Loss = cross entropy + ``router_aux_coef`` x ``olmoe.router_losses``'
load-balancing term over all experts and routed layers (no z-loss).

Parameters are stacked by kind (``LAYER_KINDS``; ``llama.run_layers``
walks ``cfg.pattern``): ``params["layers"][kind][name]`` is ``[layers of
that kind, ...]``. Training only: the serving engines know no window.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral, olmoe
from ray_tpu.ops import moe
from ray_tpu.ops.layers import rms_norm, rope_frequencies, swiglu

# kind -> (sliding window attention, routed MLP)
LAYER_KINDS = {"full_dense": (False, False), "sliding_dense": (True, False),
               "full_moe": (False, True), "sliding_moe": (True, True)}

YARN_S_2_1 = (("rope_type", "yarn"), ("factor", 128.0),
              ("original_max_position_embeddings", 8192),
              ("beta_fast", 32.0), ("beta_slow", 1.0),
              ("attention_factor", 1.4852030263919618))


@dataclass(frozen=True)
class LagunaConfig(llama.LlamaConfig):
    # ``num_heads``, ``rope_theta``, ``rope_scaling`` are the full
    # layers'; ``intermediate_size`` is the dense MLP's width
    num_heads_sliding: int = 72
    sliding_window: int = 512
    rope_theta_sliding: float = 10_000.0
    partial_rotary_factor: float = 0.5      # of a full layer's head
    # one entry a layer: True = sliding-window attention
    sliding_layers: Tuple[bool, ...] = (False, True, True, True)
    mlp_only_layers: Tuple[int, ...] = (0,)
    num_experts: int = 256                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 10
    routed_scale: float = 2.5
    moe_intermediate_size: int = 1024
    shared_intermediate_size: int = 1024
    router_aux_coef: float = 0.001

    def __post_init__(self):
        super().__post_init__()
        if len(self.sliding_layers) != self.num_layers:
            raise ValueError(
                f"sliding_layers names {len(self.sliding_layers)} layers, "
                f"num_layers is {self.num_layers}")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple(
            ("sliding" if win else "full")
            + ("_dense" if l in self.mlp_only_layers else "_moe")
            for l, win in enumerate(self.sliding_layers))

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def laguna_s_2_1(cls, **kw) -> "LagunaConfig":
        """poolside/Laguna-S-2.1's config.json: 48 layers, F S S S twelve
        times, 117.6 B parameters. ``num_layers`` cuts the stack from its
        end."""
        depth = kw.get("num_layers", 48)
        sizes = dict(vocab_size=100_352, hidden_size=3072,
                     intermediate_size=12_288, num_layers=depth,
                     num_heads=48, num_kv_heads=8, head_dim=128,
                     max_seq_len=1_048_576, rope_theta=500_000.0,
                     rope_scaling=YARN_S_2_1, rms_norm_eps=1e-6,
                     sliding_layers=tuple(l % 4 != 0 for l in range(depth)))
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=5, num_heads=4, num_heads_sliding=6,
                  num_kv_heads=2, head_dim=16, max_seq_len=64,
                  sliding_window=8,
                  sliding_layers=(False, True, True, True, False),
                  rope_scaling=(("rope_type", "yarn"), ("factor", 4.0),
                                ("original_max_position_embeddings", 16),
                                ("beta_fast", 32.0), ("beta_slow", 1.0),
                                ("attention_factor", 1.1)),
                  num_experts=16, top_k=4, moe_intermediate_size=32,
                  shared_intermediate_size=32, rms_norm_eps=1e-6,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


def _kind_shapes(cfg: LagunaConfig, kind: str) -> Dict[str, Tuple]:
    """name -> (shape of one layer's parameter, fan-in; 0 = ones)."""
    sliding, routed = LAYER_KINDS[kind]
    h, hd = cfg.hidden_size, cfg.head_dim_
    heads = cfg.num_heads_sliding if sliding else cfg.num_heads
    kvd = cfg.num_kv_heads * hd
    shapes = {"attn_norm": ((h,), 0), "wq": ((h, heads * hd), h),
              "wk": ((h, kvd), h), "wv": ((h, kvd), h),
              "wo": ((heads * hd, h), heads * hd), "wg": ((h, heads), h),
              "mlp_norm": ((h,), 0)}
    if not routed:
        f = cfg.intermediate_size
        shapes.update(w_gate=((h, f), h), w_up=((h, f), h),
                      w_down=((f, h), f))
        return shapes
    E, f, sf = (cfg.experts_here, cfg.moe_intermediate_size,
                cfg.shared_intermediate_size)
    shapes.update(router=((h, cfg.num_experts), h),
                  e_gate=((E, h, f), h), e_up=((E, h, f), h),
                  e_down=((E, f, h), f), s_gate=((h, sf), h),
                  s_up=((h, sf), h), s_down=((sf, h), sf))
    return shapes


_AXES = {"attn_norm": ("embed",), "mlp_norm": ("embed",),
         "wq": ("embed", "qkv"), "wk": ("embed", "qkv"),
         "wv": ("embed", "qkv"), "wo": ("qkv", "embed"),
         "wg": ("embed", None),
         "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
         "w_down": ("mlp", "embed"), "s_gate": ("embed", "mlp"),
         "s_up": ("embed", "mlp"), "s_down": ("mlp", "embed"),
         "router": ("embed", None),
         "e_gate": ("expert", "embed", "mlp"),
         "e_up": ("expert", "embed", "mlp"),
         "e_down": ("expert", "mlp", "embed")}


def logical_axes(cfg: LagunaConfig) -> Dict[str, Any]:
    return {"embed": ("vocab", "embed"),
            "layers": {kind: {name: ("layer",) + _AXES[name]
                              for name in _kind_shapes(cfg, kind)}
                       for kind in dict.fromkeys(cfg.pattern)},
            "final_norm": ("embed",), "lm_head": ("embed", "vocab")}


def init_params(cfg: LagunaConfig, key: jax.Array) -> Dict[str, Any]:
    """Truncated-normal init (fan-in scaled) in ``cfg.param_dtype``; a
    kind's layers stacked in their order."""
    def draw(k, shape, fan_in):
        if not fan_in:
            return jnp.ones(shape, cfg.param_dtype)
        return (jax.random.truncated_normal(k, -3, 3, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(cfg.param_dtype)

    h, v = cfg.hidden_size, cfg.vocab_size
    layers = {}
    for n, kind in enumerate(dict.fromkeys(cfg.pattern)):
        depth = cfg.pattern.count(kind)
        shapes = _kind_shapes(cfg, kind)
        keys = jax.random.split(jax.random.fold_in(key, n + 1), len(shapes))
        layers[kind] = {name: draw(k, (depth,) + shape, fan_in)
                        for k, (name, (shape, fan_in))
                        in zip(keys, shapes.items())}
    return {"embed": draw(jax.random.fold_in(key, 0), (v, h), h),
            "layers": layers,
            "final_norm": jnp.ones((h,), cfg.param_dtype),
            "lm_head": draw(jax.random.fold_in(key, 99), (h, v), h)}


def _layer(cfg: LagunaConfig, kind: str, x, p, cos, sin, mesh=None,
           keep_router_logits: bool = False):
    sliding, routed = LAYER_KINDS[kind]
    x = llama.attention_block(
        cfg, x, p, cos, sin, mesh=mesh,
        window=cfg.sliding_window if sliding else None)
    dt = cfg.dtype
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        if not routed:
            return x + swiglu(h2, p["w_gate"].astype(dt), p["w_up"].astype(dt),
                              p["w_down"].astype(dt)), None
        with jax.named_scope("moe_shared"):
            shared = swiglu(h2, p["s_gate"].astype(dt), p["s_up"].astype(dt),
                            p["s_down"].astype(dt))
        out, logits, counts = moe.routed_experts_on(
            mesh, h2, p["router"], p["e_gate"], p["e_up"], p["e_down"],
            cfg.top_k, renormalize=True, held=cfg.experts_held,
            scale=cfg.routed_scale)
        router = olmoe.router_stats(logits, counts)
        if keep_router_logits:
            router["logits"] = logits
        return x + shared + out, router


def forward(cfg: LagunaConfig, params, tokens: jax.Array, mesh=None,
            keep_router_logits: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens [b, s] -> (logits [b, s, vocab] float32, router): of the
    routed layers in their order, ``counts [Lr, E]`` (rows routed to each
    expert, held or not), ``prob [Lr, E]``, ``z [Lr]`` and, asked for,
    ``logits [Lr, b * s, E]``."""
    s = tokens.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        rope = {False: rope_frequencies(
                    int(cfg.head_dim_ * cfg.partial_rotary_factor), s,
                    cfg.rope_theta, dtype=cfg.dtype,
                    scaling=cfg.rope_scaling_dict),
                True: rope_frequencies(cfg.head_dim_, s,
                                       cfg.rope_theta_sliding,
                                       dtype=cfg.dtype)}
    pattern = cfg.pattern

    def layer_of(kind):
        cos, sin = rope[LAYER_KINDS[kind][0]]
        return lambda x_, p_: _layer(cfg, kind, x_, p_, cos, sin, mesh=mesh,
                                     keep_router_logits=keep_router_logits)

    level = llama.resolve_remat(
        cfg, params, tokens, mesh, param_shardings, pattern=pattern,
        top_k=cfg.top_k, held=cfg.experts_held) if cfg.remat else None
    x, ys = llama.run_layers(
        {kind: layer_of(kind) for kind in params["layers"]}, x,
        params["layers"], level=level, scan=cfg.scan_layers, pattern=pattern)
    # the routed layers' stats, from stacks by kind into layer order
    taken, rows = dict.fromkeys(ys, 0), []
    for kind in pattern:
        if LAYER_KINDS[kind][1]:
            at = taken[kind]
            rows.append(jax.tree_util.tree_map(lambda a: a[at], ys[kind]))
            taken[kind] += 1
    router = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *rows)
    return llama._final_head(cfg, params, x), router


def loss_terms(cfg: LagunaConfig, params, batch: Dict[str, jax.Array],
               mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(loss, its terms and the routed layers' expert counts [Lr, E]):
    made for ``jax.value_and_grad(..., has_aux=True)``."""
    tokens = batch["tokens"]
    logits, router = forward(cfg, params, tokens[:, :-1], mesh=mesh)
    mask = batch.get("mask")
    ce = llama.cross_entropy_loss(logits, tokens[:, 1:],
                                  None if mask is None else mask[:, 1:])
    balance, _ = olmoe.router_losses(cfg, router)
    return ce + cfg.router_aux_coef * balance, {
        "cross_entropy": ce, "load_balance": balance,
        "expert_counts": router["counts"]}


def loss_fn(cfg: LagunaConfig, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    return loss_terms(cfg, params, batch, mesh=mesh)[0]


def rows_held(cfg: LagunaConfig, expert_counts) -> Any:
    """Of ``expert_counts [Lr, E]``, the rows the held experts multiplied
    (the ``moe_rows_held`` counter; all of them where all are held)."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    return expert_counts[:, first:first + count].sum()


def rows_passed(cfg: LagunaConfig, expert_counts) -> int:
    """Of ``expert_counts [Lr, E]`` on the host, the rows the passes over
    the held experts' rows took (the ``moe_rows_passed`` counter,
    ``ops/moe.rows_passed``); ``rows_held`` over it is the passes' fill."""
    return moe.rows_passed(expert_counts, cfg.experts_held)


def param_shardings(cfg: LagunaConfig, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(mixtral.without_layer_axis(logical_axes(cfg)),
                             mesh)
