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
the embedding, tied (the family has no other). The model is the table
``LAYER_KINDS`` and ``models/stack.py`` walks it; what is here beside it
is the bias's. Training only: the serving engines keep no convolution
state.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.conv import short_conv_part
from ray_tpu.ops.layers import swiglu_part
from ray_tpu.ops.moe import routed_part

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


_CONV = short_conv_part()
_ROUTED = routed_part(score="sigmoid", bias=True, renorm_eps="renorm_eps")
# the published stack has no attention layer among its dense ones
LAYER_KINDS = {"conv_dense": (_CONV, swiglu_part()),
               "conv_moe": (_CONV, _ROUTED),
               "attn_moe": (llama.attention_part(qk_norm="head"), _ROUTED)}
STACK = stack.Stack(LAYER_KINDS, reports="router")

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
# the bias's: what an optimizer is given and gives back, the move after a
# step, the counter (``models/stack.py``)
trainable, with_trainable = stack.trainable, stack.with_trainable
update_router_bias = STACK.update_router_bias
router_bias_abs_max = stack.router_bias_abs_max
