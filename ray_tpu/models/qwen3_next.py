"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct, ``qwen3_next``): three
gated delta-rule layers to one gated full-attention layer, a routed mixture
beside a gated shared expert in every layer, llama's pre-norm block with
zero-centred norms.

``h0 = embed[tokens]``; ``h = h + Mixer(N(h))`` then ``h = h + MoE(N(h))``;
``logits = N(h_L) @ lm_head``, untied. ``N(x) = x * rsqrt(mean(x^2) + eps) *
(1 + w)``, float32 inside, ``w`` drawn as zeros (``zero_centred_norm``:
``ops/layers.rms_norm``).

- ``Mixer`` of a ``linear`` layer (``layer_types`` ``linear_attention``) is
  ``ops/delta.gated_delta_mixer`` with ``linear_key_heads`` heads of q and k
  under ``linear_heads`` value heads (16 under 32: value head ``i`` reads key
  head ``i // 2``) and ``beta = sigmoid(b)`` with no factor two; the rule's
  gated head norm keeps its plain weight.
- ``Mixer`` of a ``full`` layer is ``llama.attention_block``: ``wq`` gives
  each head its query and then an elementwise gate of the same size
  (``sigmoid`` of it on that head's output before ``wo``); a zero-centred
  RMSNorm of each head's q and k; rope (rotate-half) on the first
  ``partial_rotary_factor`` of a head, unscaled.
- ``MoE`` (``ops/moe.routed_part``): softmax over all ``num_experts`` router
  logits in float32, the ``top_k`` largest, renormalised, no routed scale;
  beside them a shared SwiGLU times ``sigmoid(u . s_sigmoid)``.

``experts_held=(first, count)`` is this chip's share of each routed layer
under expert parallelism (``models/laguna.py``'s docstring). Loss = cross
entropy + ``router_aux_coef`` x ``ops/moe.router_losses``' load-balancing
term over all experts and layers. No multi-token prediction module: the
published ``config.json`` has no key for one. The model is the table
``LAYER_KINDS`` and ``models/stack.py`` walks it; ``forward`` and
``token_nll`` hand back ``{"gdn_state": .., "router": ..}``. Training only:
the serving engines keep no rule state.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.delta import gated_delta_part
from ray_tpu.ops.layers import rope_frequencies
from ray_tpu.ops.moe import routed_part


@dataclass(frozen=True)
class Qwen3NextConfig(llama.LlamaConfig):
    # one entry a layer: True = full attention, False = gated delta rule
    attention_layers: Tuple[bool, ...] = (False, False, False, True)
    partial_rotary_factor: float = 0.25     # of a full layer's head
    linear_heads: int = 32                  # linear_num_value_heads
    linear_key_heads: int = 16              # linear_num_key_heads
    linear_key_dim: int = 128               # linear_key_head_dim
    linear_value_dim: int = 128             # linear_value_head_dim
    linear_conv_taps: int = 4               # linear_conv_kernel_dim
    rule_chunk: int = 64                    # positions a chunk of the rule
    num_experts: int = 512                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    # a pass of the held rows is their balanced share and one part in this
    # many of it (``ops/moe._held_chunk``); None: the op's own part
    held_headroom: Optional[int] = None
    top_k: int = 10
    routed_scale: float = 1.0
    moe_intermediate_size: int = 512
    shared_intermediate_size: int = 512
    router_aux_coef: float = 0.001
    rms_norm_eps: float = 1e-6
    zero_centred_norm: bool = True
    tie_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        if len(self.attention_layers) != self.num_layers:
            raise ValueError(
                f"attention_layers names {len(self.attention_layers)} "
                f"layers, num_layers is {self.num_layers}")
        if self.linear_heads % self.linear_key_heads:
            raise ValueError(
                f"{self.linear_key_heads} key heads do not divide "
                f"{self.linear_heads} value heads")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple("full" if attn else "linear"
                     for attn in self.attention_layers)

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def qwen3_next_80b_a3b(cls, **kw) -> "Qwen3NextConfig":
        """Qwen/Qwen3-Next-80B-A3B-Instruct's config.json: 48 layers, every
        fourth full attention (``full_attention_interval`` 4), 512 experts
        of 512 in every layer. ``num_layers`` cuts the stack from its
        end."""
        depth = kw.get("num_layers", 48)
        sizes = dict(vocab_size=151_936, hidden_size=2048,
                     intermediate_size=5120, num_layers=depth, num_heads=16,
                     num_kv_heads=2, head_dim=256, max_seq_len=262_144,
                     rope_theta=10_000_000.0,
                     attention_layers=tuple(l % 4 == 3 for l in range(depth)))
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_seq_len=64, linear_heads=4, linear_key_heads=2,
                  linear_key_dim=16, linear_value_dim=16, rule_chunk=8,
                  num_experts=16, top_k=4, moe_intermediate_size=32,
                  shared_intermediate_size=32, dtype=jnp.float32,
                  remat=False)
        return replace(cfg, **kw)


# the name ``benchmark/cells/train_hybrid.load_model`` and
# ``tools/step_program.py`` make of the module's
Qwen3_nextConfig = Qwen3NextConfig


def _rope(cfg: Qwen3NextConfig, tokens):
    """A full layer rotates the first ``partial_rotary_factor`` of each
    head at the config's ``rope_theta``, unscaled."""
    return rope_frequencies(
        int(cfg.head_dim_ * cfg.partial_rotary_factor), tokens.shape[1],
        cfg.rope_theta, dtype=cfg.dtype)


_ROUTED = routed_part(shared="gated", balance=True)
LAYER_KINDS = {
    "linear": (gated_delta_part(norm="pre", key_heads="linear_key_heads",
                                beta_scale=1.0), _ROUTED),
    "full": (llama.attention_part(gate="elementwise", rope=_rope,
                                  qk_norm="head"), _ROUTED)}
STACK = stack.Stack(LAYER_KINDS, reports=("gdn_state", "router"),
                    blocked_head=True)

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
