"""LFM2-MoE (LiquidAI/LFM2-8B-A1B): gated short convolutions among
attention layers, a sigmoid router balanced by a bias no optimizer owns.

Every layer is ``x + Op(norm(x))`` then ``x + FF(norm(x))``:

- ``Op`` of a ``conv`` layer is ``ops/conv.gated_short_conv``: an
  in-projection to three thirds B, C, X, a causal depthwise convolution
  of ``conv_taps`` taps over ``B * X``, gated by ``C``, an out-projection.
  ``Op`` of a ``full_attention`` layer is ``llama.attention_block``: GQA
  with an RMSNorm over each head's dims of q and k before rope (one
  weight ``[head_dim]`` each), rope over the whole head.
- ``FF`` of the first ``num_dense_layers`` layers is a dense SwiGLU of
  ``intermediate_size``; of every other layer a routed mixture
  (``ops/moe.routed_experts``) with no shared expert: ``s = sigmoid(u @
  router)`` in float32 over all ``num_experts``, the ``top_k`` experts
  with the largest ``s + b``, gate weights ``s / (sum of the chosen s +
  1e-6)`` times ``routed_scale``.
- ``b`` (``router_bias [num_experts]`` float32, zeros at first) takes
  part in the choice alone and gets no gradient. It is in the parameter
  tree and not the optimizer's: ``trainable(params)`` is what an
  optimizer is given, ``with_trainable`` puts its result back, and after
  each step ``update_router_bias`` moves ``b`` from that step's expert
  counts, ``b_i += bias_update_rate * sign(mean(c) - c_i)`` (loss-free
  balancing, arXiv:2408.15664). There is no auxiliary router loss.

``experts_held=(first, count)`` is this chip's share of each routed
layer under expert parallelism, as in ``models/laguna.py``. The head is
the embedding, tied (the family has no other). Parameters are stacked by
kind (``LAYER_KINDS``; ``llama.run_layers`` walks ``cfg.pattern``).
Training only: the serving engines keep no convolution state.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixtral
from ray_tpu.ops import moe
from ray_tpu.ops.conv import gated_short_conv
from ray_tpu.ops.layers import rms_norm, rope_frequencies, swiglu

# kind -> (the operator is attention, the MLP is routed); the published
# stack has no attention layer among its dense ones
LAYER_KINDS = {"conv_dense": (False, False), "conv_moe": (False, True),
               "attn_moe": (True, True)}
# LFM2-8B-A1B's layer_types: attention at these six of its 24 layers
ATTENTION_LAYERS_8B = (2, 6, 10, 14, 18, 21)


@dataclass(frozen=True)
class Lfm2Config(llama.LlamaConfig):
    # one entry a layer: True = attention, False = short convolution
    attention_layers: Tuple[bool, ...] = (False, False, True, False)
    num_dense_layers: int = 2               # leading layers with a dense MLP
    conv_taps: int = 3                      # conv_L_cache
    num_experts: int = 32                   # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 4
    routed_scale: float = 1.0
    moe_intermediate_size: int = 1792
    renorm_eps: float = 1e-6
    bias_update_rate: float = 0.001
    tie_embeddings: bool = True

    def __post_init__(self):
        super().__post_init__()
        if len(self.attention_layers) != self.num_layers:
            raise ValueError(
                f"attention_layers names {len(self.attention_layers)} "
                f"layers, num_layers is {self.num_layers}")
        if not self.tie_embeddings:
            raise ValueError("the head is the embedding: there is no other")
        if any(self.attention_layers[:self.num_dense_layers]):
            raise ValueError("an attention layer with a dense MLP: the "
                             "stack has none, and neither has LAYER_KINDS")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple(("attn" if attn else "conv")
                     + ("_dense" if l < self.num_dense_layers else "_moe")
                     for l, attn in enumerate(self.attention_layers))

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def lfm2_8b_a1b(cls, **kw) -> "Lfm2Config":
        """LiquidAI/LFM2-8B-A1B's config.json: 24 layers, 18 conv and 6
        attention, 8.34 B parameters. ``attention_layers`` and
        ``num_dense_layers`` name a cut of the stack."""
        sizes = dict(vocab_size=65_536, hidden_size=2048,
                     intermediate_size=7168, num_layers=24, num_heads=32,
                     num_kv_heads=8, max_seq_len=128_000,
                     rope_theta=1_000_000.0, rms_norm_eps=1e-5,
                     attention_layers=tuple(
                         l in ATTENTION_LAYERS_8B for l in range(24)))
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "Lfm2Config":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=5, num_heads=4, num_kv_heads=2, max_seq_len=64,
                  attention_layers=(False, True, False, False, False),
                  num_dense_layers=1, num_experts=8, top_k=2,
                  moe_intermediate_size=32, rms_norm_eps=1e-5,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


def _kind_shapes(cfg: Lfm2Config, kind: str) -> Dict[str, Tuple]:
    """name -> (shape of one layer's parameter, fan-in; 0 = ones, -1 =
    float32 zeros)."""
    attn, routed = LAYER_KINDS[kind]
    h, hd = cfg.hidden_size, cfg.head_dim_
    if attn:
        qd, kvd = cfg.num_heads * hd, cfg.num_kv_heads * hd
        shapes = {"attn_norm": ((h,), 0), "wq": ((h, qd), h),
                  "wk": ((h, kvd), h), "wv": ((h, kvd), h),
                  "wo": ((qd, h), qd), "q_norm": ((hd,), 0),
                  "k_norm": ((hd,), 0)}
    else:
        shapes = {"op_norm": ((h,), 0), "w_in": ((h, 3 * h), h),
                  "w_conv": ((h, cfg.conv_taps), cfg.conv_taps),
                  "w_out": ((h, h), h)}
    shapes["mlp_norm"] = ((h,), 0)
    if not routed:
        f = cfg.intermediate_size
        shapes.update(w_gate=((h, f), h), w_up=((h, f), h),
                      w_down=((f, h), f))
        return shapes
    E, f = cfg.experts_here, cfg.moe_intermediate_size
    shapes.update(router=((h, cfg.num_experts), h),
                  router_bias=((cfg.num_experts,), -1),
                  e_gate=((E, h, f), h), e_up=((E, h, f), h),
                  e_down=((E, f, h), f))
    return shapes


_AXES = {"attn_norm": ("embed",), "op_norm": ("embed",),
         "mlp_norm": ("embed",), "q_norm": (None,), "k_norm": (None,),
         "wq": ("embed", "qkv"), "wk": ("embed", "qkv"),
         "wv": ("embed", "qkv"), "wo": ("qkv", "embed"),
         "w_in": ("embed", "mlp"), "w_conv": ("mlp", None),
         "w_out": ("mlp", "embed"),
         "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
         "w_down": ("mlp", "embed"),
         "router": ("embed", None), "router_bias": (None,),
         "e_gate": ("expert", "embed", "mlp"),
         "e_up": ("expert", "embed", "mlp"),
         "e_down": ("expert", "mlp", "embed")}


def logical_axes(cfg: Lfm2Config) -> Dict[str, Any]:
    return {"embed": ("vocab", "embed"),
            "layers": {kind: {name: ("layer",) + _AXES[name]
                              for name in _kind_shapes(cfg, kind)}
                       for kind in dict.fromkeys(cfg.pattern)},
            "final_norm": ("embed",)}


def init_params(cfg: Lfm2Config, key: jax.Array) -> Dict[str, Any]:
    """Truncated-normal init (fan-in scaled) in ``cfg.param_dtype``, norms
    at 1, the routers' bias at 0 in float32; a kind's layers stacked in
    their order."""
    def draw(k, shape, fan_in):
        if fan_in < 0:
            return jnp.zeros(shape, jnp.float32)
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
            "final_norm": jnp.ones((h,), cfg.param_dtype)}


def trainable(params: Dict[str, Any]) -> Dict[str, Any]:
    """The leaves an optimizer owns: every one but the routers' bias."""
    return {**params, "layers": {
        kind: {k: v for k, v in leaves.items() if k != "router_bias"}
        for kind, leaves in params["layers"].items()}}


def with_trainable(params: Dict[str, Any], trained: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """``params`` with ``trained`` (like ``trainable(params)``) in place
    of the leaves an optimizer owns."""
    return {**trained, "layers": {
        kind: {**params["layers"][kind], **leaves}
        for kind, leaves in trained["layers"].items()}}


def update_router_bias(cfg: Lfm2Config, params: Dict[str, Any],
                       expert_counts: jax.Array) -> Dict[str, Any]:
    """``params`` after a step whose routed layers, in their order, sent
    ``expert_counts [Lr, E]`` rows to each expert: every router's bias
    moves ``bias_update_rate`` toward the experts that got fewer rows than
    the mean, away from those that got more. On a mesh the counts are the
    whole batch's (``forward`` sums them over the batch axes)."""
    with jax.named_scope("moe_route"), jax.named_scope("moe_bias_update"):
        c = expert_counts.astype(jnp.float32)
        move = cfg.bias_update_rate * jnp.sign(
            c.mean(-1, keepdims=True) - c)                      # [Lr, E]
        at = _routed_rows(cfg.pattern)
        return {**params, "layers": {
            kind: ({**leaves, "router_bias": leaves["router_bias"]
                    + move[jnp.asarray(at[kind])]}
                   if kind in at else leaves)
            for kind, leaves in params["layers"].items()}}


def _routed_rows(pattern: Tuple[str, ...]) -> Dict[str, list]:
    """kind -> of the routed layers in their order, those of that kind."""
    at: Dict[str, list] = {}
    routed = [kind for kind in pattern if LAYER_KINDS[kind][1]]
    for row, kind in enumerate(routed):
        at.setdefault(kind, []).append(row)
    return at


def router_bias_abs_max(params: Dict[str, Any]) -> jax.Array:
    """The counter ``moe_router_bias_abs_max``: the largest ``|b|`` of
    any router."""
    return jnp.max(jnp.stack([
        jnp.abs(leaves["router_bias"]).max()
        for leaves in params["layers"].values() if "router_bias" in leaves]))


def _layer(cfg: Lfm2Config, kind: str, x, p, cos, sin, mesh=None,
           keep_router_logits: bool = False):
    attn, routed = LAYER_KINDS[kind]
    dt = cfg.dtype
    if attn:
        x = llama.attention_block(cfg, x, p, cos, sin, mesh=mesh)
    else:
        x = x + gated_short_conv(
            rms_norm(x, p["op_norm"], cfg.rms_norm_eps), p["w_in"],
            p["w_conv"], p["w_out"])
    with jax.named_scope("mlp"):
        h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        if not routed:
            return x + swiglu(h2, p["w_gate"].astype(dt), p["w_up"].astype(dt),
                              p["w_down"].astype(dt)), None
        out, logits, counts, *chosen = moe.routed_experts_on(
            mesh, h2, p["router"], p["e_gate"], p["e_up"], p["e_down"],
            cfg.top_k, renormalize=True, select_bias=p["router_bias"],
            held=cfg.experts_held, scale=cfg.routed_scale, score="sigmoid",
            renorm_eps=cfg.renorm_eps, keep_choices=keep_router_logits)
        router = {"counts": counts}
        if keep_router_logits:
            router["logits"], router["chosen"] = logits, chosen[0]
        return x + out, router


def forward(cfg: Lfm2Config, params, tokens: jax.Array, mesh=None,
            keep_router_logits: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens [b, s] -> (logits [b, s, vocab] float32, router): of the
    routed layers in their order, ``counts [Lr, E]`` (rows routed to each
    expert, held or not) and, asked for, ``logits [Lr, b * s, E]`` (before
    the sigmoid) and ``chosen [Lr, b * s, K]`` (``route``'s own choices)."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        cos, sin = rope_frequencies(cfg.head_dim_, tokens.shape[1],
                                    cfg.rope_theta, dtype=cfg.dtype)
    pattern = cfg.pattern

    def layer_of(kind):
        return lambda x_, p_: _layer(cfg, kind, x_, p_, cos, sin, mesh=mesh,
                                     keep_router_logits=keep_router_logits)

    level = llama.resolve_remat(
        cfg, params, tokens, mesh, param_shardings, pattern=pattern,
        top_k=cfg.top_k, held=cfg.experts_held) if cfg.remat else None
    x, ys = llama.run_layers(
        {kind: layer_of(kind) for kind in params["layers"]}, x,
        params["layers"], level=level, scan=cfg.scan_layers, pattern=pattern)
    # the routed layers' stats, from stacks by kind into layer order
    at = _routed_rows(pattern)
    order = sorted((row, kind, n) for kind, rows in at.items()
                   for n, row in enumerate(rows))
    router = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a),
        *(jax.tree_util.tree_map(lambda a: a[n], ys[kind])
          for _, kind, n in order))
    return llama._final_head(cfg, params, x), router


def loss_terms(cfg: Lfm2Config, params, batch: Dict[str, jax.Array],
               mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """(cross entropy, it again and the routed layers' expert counts
    [Lr, E]): made for ``jax.value_and_grad(..., has_aux=True)``."""
    tokens = batch["tokens"]
    logits, router = forward(cfg, params, tokens[:, :-1], mesh=mesh)
    mask = batch.get("mask")
    ce = llama.cross_entropy_loss(logits, tokens[:, 1:],
                                  None if mask is None else mask[:, 1:])
    return ce, {"cross_entropy": ce, "expert_counts": router["counts"]}


def loss_fn(cfg: Lfm2Config, params, batch: Dict[str, jax.Array],
            mesh=None) -> jax.Array:
    return loss_terms(cfg, params, batch, mesh=mesh)[0]


def rows_held(cfg: Lfm2Config, expert_counts) -> Any:
    """Of ``expert_counts [Lr, E]``, the rows the held experts multiplied
    (the ``moe_rows_held`` counter; all of them where all are held)."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    return expert_counts[:, first:first + count].sum()


def rows_passed(cfg: Lfm2Config, expert_counts) -> int:
    """Of ``expert_counts [Lr, E]`` on the host, the rows the passes over
    the held experts' rows took (the ``moe_rows_passed`` counter,
    ``ops/moe.rows_passed``); ``rows_held`` over it is the passes' fill."""
    return moe.rows_passed(expert_counts, cfg.experts_held)


def param_shardings(cfg: Lfm2Config, mesh):
    from ray_tpu.parallel.sharding import shard_pytree_like

    return shard_pytree_like(mixtral.without_layer_axis(logical_axes(cfg)),
                             mesh)
