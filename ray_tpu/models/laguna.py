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

Loss = cross entropy + ``router_aux_coef`` x ``ops/moe.router_losses``'
load-balancing term over all experts and routed layers (no z-loss).

The model is the table ``LAYER_KINDS`` and ``models/stack.py`` walks it.
Training only: the serving engines know no window.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.layers import rope_frequencies, swiglu_part
from ray_tpu.ops.moe import routed_part

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


def _rope_full(cfg: LagunaConfig, tokens):
    """A full layer rotates the first ``partial_rotary_factor`` of each
    head, at the config's ``rope_theta`` and ``rope_scaling``."""
    return rope_frequencies(
        int(cfg.head_dim_ * cfg.partial_rotary_factor), tokens.shape[1],
        cfg.rope_theta, dtype=cfg.dtype, scaling=cfg.rope_scaling_dict)


def _rope_sliding(cfg: LagunaConfig, tokens):
    """A sliding layer rotates the whole head, unscaled."""
    return rope_frequencies(cfg.head_dim_, tokens.shape[1],
                            cfg.rope_theta_sliding, dtype=cfg.dtype)


_FULL = llama.attention_part(gate=True, rope=_rope_full)
_SLIDING = llama.attention_part(heads="num_heads_sliding",
                                window="sliding_window", gate=True,
                                rope=_rope_sliding)
_DENSE = swiglu_part()
_ROUTED = routed_part(shared=True, balance=True)
LAYER_KINDS = {"full_dense": (_FULL, _DENSE),
               "sliding_dense": (_SLIDING, _DENSE),
               "full_moe": (_FULL, _ROUTED), "sliding_moe": (_SLIDING, _ROUTED)}
STACK = stack.Stack(LAYER_KINDS, reports="router")

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
