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
  float32), the skip ``D x``, a gated RMSNorm over a group's channels
  (Granite's norm has one group: all ``ssm_heads * ssm_head_dim``
  channels; ``mamba2_part(norm_groups=)`` for more), an out-projection.
- ``Mixer`` of an ``attention`` layer is ``llama.attention_block`` with no
  position embedding (``position_embedding_type`` "nope": q and k are not
  rotated), scores scaled by ``attention_multiplier`` and not by the head
  size.

The head is the embedding, tied. ``loss_terms`` never builds the logits
whole: ``llama.blocked_cross_entropy`` walks blocks of tokens (100,352 rows
at 32,768 positions would be 13 GB of float32) and takes a block's
gradients while its logits stand; ``token_nll`` walks the same blocks for
every position's loss (``llama.blocked_token_nll``). ``forward`` builds the
logits, for sizes at which they fit. The model is the table ``LAYER_KINDS``
(``mamba``, ``attention``) and ``models/stack.py`` walks it; the
initialisation is Mamba-2's published one (``ops/ssm.mamba2_part``).
Training only: the serving engines keep no scan state and no taps
(``llama_decode.py``, ``llama_paged.py``: ROADMAP R8a).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.layers import swiglu_part
from ray_tpu.ops.ssm import mamba2_part

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


_R = "residual_multiplier"
_MLP = swiglu_part(resid=_R)
LAYER_KINDS = {
    "mamba": (mamba2_part(resid=_R), _MLP),
    "attention": (llama.attention_part(rope=None, scale="attention_multiplier",
                                       resid=_R), _MLP)}
STACK = stack.Stack(LAYER_KINDS, reports="ssm_state", blocked_head=True,
                    embed_scale="embedding_multiplier",
                    logits_divisor="logits_scaling")

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
