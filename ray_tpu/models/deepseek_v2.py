"""DeepSeek-V2 (deepseek-ai/DeepSeek-V2, arXiv:2405.04434): multi-head
latent attention in every layer, a routed mixture beside shared experts.

Two kinds of layer:

- attention (``ops/mla.latent_attention_part``): queries through a latent
  of ``q_lora_rank``, keys and values through one of ``kv_lora_rank``, each
  with its RMSNorm; ``num_heads`` heads with keys of ``qk_nope_head_dim`` +
  ``qk_rope_head_dim`` (the rope dims one vector shared by all heads) and
  values of ``v_head_dim``; rope theta 10,000 under yarn, the scores
  scaled by ``mla.softmax_scale``.
- MLP: the first ``first_k_dense_replace`` layers a dense SwiGLU of
  ``intermediate_size``; every other layer (``moe_layer_freq`` 1)
  ``n_shared_experts`` shared experts, run as one SwiGLU of
  ``shared_intermediate_size``, added ungated, beside a routed mixture
  (``ops/moe.routed_part``): softmax over all ``num_experts`` router logits
  in float32, the experts as ``n_group`` groups of neighbours, the
  ``topk_group`` groups with the largest best score kept, the ``top_k``
  largest scores inside them, the weights the scores themselves (not
  renormalised) times ``routed_scale``.

``experts_held=(first, count)`` is this chip's share of each routed layer
under expert parallelism (``ops/moe.py``); ``heads_of`` says that
``num_heads`` are its share of each attention layer's ``heads_of`` heads
under tensor parallelism (``ops/mla.py``); ``None`` holds them all.

Loss = cross entropy + ``router_aux_coef`` x ``ops/moe.sequence_balance``
(the published ``seq_aux`` term: per sequence and per layer).

The model is the table ``LAYER_KINDS`` and ``models/stack.py`` walks it.
Training only: the serving engines know no latent cache.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.layers import swiglu_part
from ray_tpu.ops.mla import latent_attention_part
from ray_tpu.ops.moe import routed_part

YARN_V2 = (("type", "yarn"), ("factor", 40.0),
           ("original_max_position_embeddings", 4096),
           ("beta_fast", 32.0), ("beta_slow", 1.0),
           ("mscale", 0.707), ("mscale_all_dim", 0.707))


@dataclass(frozen=True)
class DeepseekV2Config(llama.LlamaConfig):
    # ``num_kv_heads`` and ``head_dim`` have no meaning under latent
    # attention and are not read
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    heads_of: Optional[int] = None          # num_heads are a share of these
    first_k_dense_replace: int = 1
    num_experts: int = 160                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scale: float = 16.0
    moe_intermediate_size: int = 1536
    shared_intermediate_size: int = 3072    # n_shared_experts x 1,536
    router_aux_coef: float = 0.001

    def __post_init__(self):
        super().__post_init__()
        if self.num_experts % self.n_group:
            raise ValueError(f"{self.num_experts} experts are not "
                             f"{self.n_group} groups of equal size")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple("mla_dense" if l < self.first_k_dense_replace
                     else "mla_moe" for l in range(self.num_layers))

    @classmethod
    def deepseek_v2(cls, **kw) -> "DeepseekV2Config":
        """deepseek-ai/DeepSeek-V2's config.json: 60 layers, the first
        dense, 236 B parameters. ``num_layers`` cuts the stack from its
        end."""
        sizes = dict(vocab_size=102_400, hidden_size=5120,
                     intermediate_size=12_288, num_layers=60, num_heads=128,
                     num_kv_heads=128, max_seq_len=163_840,
                     rope_theta=10_000.0, rope_scaling=YARN_V2,
                     rms_norm_eps=1e-6)
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV2Config":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=3, num_heads=4, num_kv_heads=4, max_seq_len=64,
                  q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=12,
                  rope_theta=10_000.0,
                  rope_scaling=(("type", "yarn"), ("factor", 4.0),
                                ("original_max_position_embeddings", 16),
                                ("beta_fast", 32.0), ("beta_slow", 1.0),
                                ("mscale", 0.707), ("mscale_all_dim", 0.707)),
                  num_experts=16, top_k=3, n_group=4, topk_group=2,
                  routed_scale=4.0, moe_intermediate_size=32,
                  shared_intermediate_size=64, rms_norm_eps=1e-6,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


_MLA = latent_attention_part()
LAYER_KINDS = {
    "mla_dense": (_MLA, swiglu_part()),
    "mla_moe": (_MLA, routed_part(shared=True, balance="sequence",
                                  renormalize=False, groups=True))}
STACK = stack.Stack(LAYER_KINDS, reports="router")

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
# the name ``<module>.capitalize() + "Config"`` that the tools and the
# benchmark's runners look a module's config up by
Deepseek_v2Config = DeepseekV2Config
