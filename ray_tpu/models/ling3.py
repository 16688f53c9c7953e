"""Ling-3.0-flash-VL's language model (inclusionAI/Ling-3.0-flash-VL,
``config.json``): groups of six layers, five Kimi Delta Attention layers
(KDA, arXiv:2510.26692: a delta rule whose decay is a vector over the key's
channels) to one gated latent-attention layer with no query latent, two
leading dense SwiGLUs and then a routed mixture in every layer: sigmoid
scores, a selection bias, a group limit, one shared expert.

``h0 = embed[tokens]``; ``h = h + Mixer(N(h))`` then ``h = h + MLP(N(h))``;
``logits = N(h_L) @ lm_head``, untied. ``N`` an RMSNorm, eps 1e-6, plain
weight. Layer ``i`` (its published index, ``cfg.layer_ids``) is a latent
layer where ``(i + 1) % layer_group_size == 0`` and a KDA layer elsewhere,
dense where ``i < first_k_dense_replace`` and routed elsewhere.

- ``Mixer`` of a ``kda`` layer is ``ops/delta.kda_mixer``: ``q =
  L2(silu(taps(u W_q)))``, ``k`` likewise, ``v = silu(taps(u W_v))``
  (causal depthwise taps of ``linear_conv_taps``, no bias: ASSUMED),
  every head of ``linear_heads`` its own q, k and v; ``g = kda_lower_bound x
  sigmoid(exp(A_log[h]) (u W_f + dt_bias))`` float32, a decay a key channel
  in (-5, 0) (ASSUMED: the lower-bounded gate that ``kda_safe_gate`` and
  ``kda_lower_bound`` name, ``W_f`` one full-rank matrix as ``no_kda_lora``
  says); ``beta = sigmoid(u W_b)``; the state ``S [V, K]`` float32, zero
  before the sequence, ``S_t = S_{t-1} Diag(exp g_t) (I - beta_t k_t k_t^T)
  + beta_t v_t k_t^T``, ``o_t = S_t q_t K^-1/2``; ``y = (N_head(o)
  sigmoid(u W_g)[h]) W_o``, ``N_head`` an RMSNorm over a head's V with one
  weight, the gate one number a head (ASSUMED: ``head_wise`` names no
  mixer, so both mixers get the head-wise gate).
- ``Mixer`` of an ``mla`` layer is ``ops/mla.latent_attention_part(gate=
  True)`` with ``q_lora_rank`` None: ``q = u W_q`` straight from the
  layer's normed input, the key/value latent of ``kv_lora_rank`` with its
  RMSNorm, one shared rotated key of ``qk_rope_head_dim`` (theta 6e6,
  unscaled), causal softmax at ``(d_n + d_r)^-1/2``, each head's output
  times ``sigmoid(u W_g)[h]``. ``use_qk_norm`` is read as the norms the
  two mixers have and adds none a head (ASSUMED).
- ``MLP`` of a routed layer (``ops/moe.routed_part``): ``s = sigmoid(u
  W_r)`` float32 over all ``num_experts``; for the choice alone ``s + b``
  (``router_bias``, float32, no gradient, no optimizer state): the experts
  are ``n_group`` groups of neighbours, a group's score the sum of its two
  largest ``s + b``, the ``topk_group`` best groups kept, the ``top_k``
  largest ``s + b`` inside them chosen; weights ``s`` at the chosen over
  their sum + 1e-20, times ``routed_scale`` 2.5; one ungated shared SwiGLU
  beside them. ``update_router_bias`` moves ``b`` after each step by
  ``bias_update_rate`` (0.001, ASSUMED) x ``sign(mean(c) - c)`` (loss-free
  balancing); no auxiliary loss (ASSUMED: the row has no coefficient).

Not built (the row's ``config`` has no key for either): the vision tower
(ids of patch tokens alone) and the multi-token prediction module. The
SwiGLU clamps (``expert_swiglu_limit_list``) read 0, "no clamp", in every
held layer. ``experts_held=(first, count)`` is this chip's share of each
routed layer under expert parallelism (``models/laguna.py``'s docstring).
The model is the table ``LAYER_KINDS`` and ``models/stack.py`` walks it;
``forward`` and ``token_nll`` hand back ``{"kda": {"state",
"log_decay_min"}, "router": ..}``. Training only: the serving engines keep
no rule state.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.delta import kda_part
from ray_tpu.ops.layers import rope_frequencies, swiglu_part
from ray_tpu.ops.mla import latent_attention_part
from ray_tpu.ops.moe import routed_part


@dataclass(frozen=True)
class Ling3Config(llama.LlamaConfig):
    # the published indices of the layers this stack runs, in order; None:
    # 0 .. num_layers - 1 (a cut may skip a layer: 0, then 2-7)
    layer_ids: Optional[Tuple[int, ...]] = None
    layer_group_size: int = 6               # the last of a group is latent
    first_k_dense_replace: int = 2
    # the latent layers; ``num_kv_heads`` and ``head_dim`` are not read
    q_lora_rank: Optional[int] = None       # no query latent
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    heads_of: Optional[int] = None
    # the KDA layers
    linear_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    linear_conv_taps: int = 4               # short_conv_kernel_size
    rule_chunk: int = 64                    # positions a chunk of the rule
    kda_lower_bound: float = -5.0
    # the routed layers
    num_experts: int = 512                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    held_headroom: Optional[int] = None
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scale: float = 2.5
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 768
    renorm_eps: float = 1e-20
    bias_update_rate: float = 0.001
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = False

    def __post_init__(self):
        super().__post_init__()
        if len(self.layers_run) != self.num_layers:
            raise ValueError(
                f"layer_ids names {len(self.layers_run)} layers, num_layers "
                f"is {self.num_layers}")
        if self.num_experts % self.n_group:
            raise ValueError(f"{self.num_experts} experts are not "
                             f"{self.n_group} groups of equal size")

    @property
    def layers_run(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_layers)) if self.layer_ids is None
                else tuple(self.layer_ids))

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer run, in order."""
        return tuple(
            ("mla" if (i + 1) % self.layer_group_size == 0 else "kda")
            + ("+dense" if i < self.first_k_dense_replace else "+moe")
            for i in self.layers_run)

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def ling_3_flash(cls, **kw) -> "Ling3Config":
        """inclusionAI/Ling-3.0-flash-VL's config.json: 42 layers in groups
        of six, the first two dense, 512 experts of 768 in the other 40.
        ``layer_ids`` names a cut of the stack."""
        ids = kw.get("layer_ids")
        sizes = dict(vocab_size=157_184, hidden_size=2560,
                     intermediate_size=6144,
                     num_layers=len(ids) if ids is not None else 42,
                     num_heads=32, num_kv_heads=32, max_seq_len=131_072,
                     rope_theta=6_000_000.0)
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "Ling3Config":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=4, num_heads=4, num_kv_heads=4, max_seq_len=64,
                  layer_group_size=3, first_k_dense_replace=1,
                  kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=12, rope_theta=10_000.0,
                  linear_heads=4, linear_key_dim=16, linear_value_dim=16,
                  rule_chunk=8, num_experts=16, top_k=3, n_group=4,
                  topk_group=2, moe_intermediate_size=32,
                  shared_intermediate_size=32, dtype=jnp.float32,
                  remat=False)
        return replace(cfg, **kw)


def _rope(cfg: Ling3Config, tokens):
    return rope_frequencies(cfg.qk_rope_head_dim, tokens.shape[1],
                            cfg.rope_theta, dtype=cfg.dtype)


_KDA = kda_part()
_MLA = latent_attention_part(rope=_rope, gate=True)
_ROUTED = routed_part(shared=True, score="sigmoid", bias=True,
                      renorm_eps="renorm_eps", groups=True,
                      group_score="top2")
LAYER_KINDS = {"kda+dense": (_KDA, swiglu_part()),
               "kda+moe": (_KDA, _ROUTED),
               "mla+dense": (_MLA, swiglu_part()),
               "mla+moe": (_MLA, _ROUTED)}
STACK = stack.Stack(LAYER_KINDS, reports=("kda", "router"),
                    blocked_head=True)

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
# the routers' bias: kept from the optimizer and moved by the step's
# expert counts (``models/stack.py``), as LFM2's and dots3's is
trainable, with_trainable = stack.trainable, stack.with_trainable
update_router_bias = STACK.update_router_bias
router_bias_abs_max = stack.router_bias_abs_max
