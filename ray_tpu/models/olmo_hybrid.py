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

``loss_terms`` never builds the logits whole
(``llama.blocked_cross_entropy``: blocks of tokens, a block's gradients
taken while its logits stand; ``token_nll`` walks the same blocks for every
position's loss); ``forward`` builds them, for sizes at which they fit. The
model is the table ``LAYER_KINDS`` (``linear``, ``full``) and
``models/stack.py`` walks it; the initialisation is the delta-net's
published one (``ops/delta.gated_delta_part``). Training only: the serving
engines keep no rule state.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.delta import gated_delta_part
from ray_tpu.ops.layers import swiglu_part


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


_MLP = swiglu_part(norm="post")
LAYER_KINDS = {
    "linear": (gated_delta_part(), _MLP),
    "full": (llama.attention_part(rope=None, qk_norm="whole", norm="post"),
             _MLP)}
STACK = stack.Stack(LAYER_KINDS, reports="gdn_state", blocked_head=True)

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
