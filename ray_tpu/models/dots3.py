"""dots3-note-prev's language model (dots-studio/dots3-note-prev,
``model_type`` ``dots3_note``): latent attention in every layer, in the
full layers over the keys a learned index chooses, in the window layers
over a band with ranks and heads of their own; a head gate; a sigmoid
router with a bias no optimizer owns beside one shared expert. The vision
and audio towers and the multi-token-prediction module of the published
model have no key in its language ``config`` and are not built.

``u = RMSNorm(x)``, eps 1e-5, in every block. Latent attention as
``ops/mla.py`` states it (``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb``; ``[c_kv
| k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv)``, ``[k_n | v] = c_kv W_kvb``;
the rope dims of ``q`` and the one shared ``k_r`` rotated), with:

- **the rescale** (``apply_mla_qkv_lora_rescale``): ``q <- q x (hidden /
  q_rank) ** 0.5`` after ``W_qb`` and ``c_kv <- c_kv x (hidden / kv_rank)
  ** 0.5`` after its norm, the rope key unscaled. ASSUMED: the config names
  a switch and no rule; this is the one public convention with such a
  switch (LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``).
- **the head gate** (``attention_gate_type`` / ``swa_attention_gate_type``
  ``headwise``): ``g = sigmoid(u W_g)`` ``[heads]``, ``o[t, h] <- g[t, h]
  o[t, h]`` before ``W_o``.
- **full layers** (``layer_types`` ``full_attention``: layers 0, 1, 5, 9,
  ...; 13 of 46): q rank 1024, kv rank 512 (factors 5 ** 0.5 and 10 **
  0.5), 128 heads, keys 128 + 64, values 128, theta 80,000,000 unscaled,
  softmax scale 192 ** -0.5. **The index** (``index_n_heads`` 64,
  ``index_head_dim`` 128, ``index_topk`` 2,048; the equations of
  DeepSeek-V3.2-Exp's ``Indexer``): ``q_i = c_q W_iq`` [64, 128] from the
  normed, unscaled query latent; ``k_i = LayerNorm(u W_ik)`` [128], one key
  a position, weight and bias, eps 1e-5 (ASSUMED: LayerNorm's default);
  the first 64 dims of each rotated with the layer's tables, de-interleaved
  as the layer's rope dims are (ASSUMED); ``w = (u W_iw) x 64 ** -0.5 x 128
  ** -0.5`` [64] float32; ``I[t, s] = sum_j w[t, j] ReLU(q_i[t, j] .
  k_i[s])`` for ``s <= t``; ``S_t`` = the ``min(t + 1, 2048)`` keys of
  largest ``I[t, s]``, ties to the lower position; ``o[t, h] = sum_{s in
  S_t} softmax_{s in S_t}(q[t, h] . [k_n[s, h] | k_r[s]] x 192 ** -0.5)
  v[s, h]`` (``ops/dsa.py``). The published inference code's Hadamard
  rotation and fp8 quantisation of ``q_i``, ``k_i`` are left out: an
  orthogonal map changes no dot product and the model trains in bf16.
- **window layers** (``sliding_attention``, 33 of 46): ``swa_q_lora_rank``
  1024, ``swa_kv_lora_rank`` 1024 (factors 5 ** 0.5 both), 64 heads, keys
  192 + 64 shared, values 128, theta 50,000, softmax scale 256 ** -0.5,
  each query seeing its own position and the 512 before it
  (``sliding_window_size`` 513 keys: ASSUMED to count the query's own), no
  index.
- **MLP**: layer 0 a SwiGLU of 13,824; every other layer one shared SwiGLU
  of 1,536 added ungated beside the router: ``s = sigmoid(x_n W_r)`` over
  256 in float32, the 8 largest of ``s + b`` (``topk_method`` ``noaux_tc``,
  one group), weights ``s_i / sum_chosen s`` (``norm_topk_prob``) x
  ``routed_scaling_factor`` 1. ``b`` (``router_bias`` float32, zeros at
  first) is a leaf no optimizer owns: ``trainable`` / ``with_trainable``
  keep it from one, ``update_router_bias`` moves it after each step, ``b_i
  += 0.001 sign(mean(c) - c_i)`` (arXiv:2408.15664; rule and rate ASSUMED,
  LFM2's). No router loss: the config has no coefficient.
- **Loss**: cross entropy, plus for each full layer ``index_loss_coef`` (1)
  x ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])``, ``p_t`` the
  attention's probabilities over ``S_t`` summed over the heads held here and
  L1-normalised, under ``stop_gradient``; the index's inputs ``u`` and
  ``c_q`` under ``stop_gradient`` too, so the term trains ``W_iq``,
  ``W_ik``, the LayerNorm and ``W_iw`` alone and the cross entropy gives
  those leaves nothing. ASSUMED: DeepSeek-V3.2's sparse training stage; the
  config gives no recipe.

``heads_of`` / ``swa_heads_of`` say that ``num_heads`` / ``swa_num_heads``
are this chip's share of a layer's heads under tensor parallelism
(``ops/mla.py``; the index is not divided); ``experts_held=(first,
count)`` its share of each routed layer under expert parallelism
(``ops/moe.py``). The model is the table ``LAYER_KINDS`` and
``models/stack.py`` walks it; what is here beside it is the bias's.
Training only.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.layers import rope_frequencies, swiglu_part
from ray_tpu.ops.mla import latent_attention_part
from ray_tpu.ops.moe import routed_part

# dots3-note-prev's layer_types: two full layers, then one in four
FULL_LAYERS_46 = (0,) + tuple(range(1, 46, 4))


@dataclass(frozen=True)
class Dots3Config(llama.LlamaConfig):
    # ``num_heads``, ``q_lora_rank`` ... ``rope_theta`` are the full
    # layers', the ``swa_`` fields the window layers'; ``num_kv_heads``
    # and ``head_dim`` have no meaning under latent attention
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    heads_of: Optional[int] = None          # num_heads are a share of these
    swa_num_heads: int = 64
    swa_heads_of: Optional[int] = None
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50_000.0
    sliding_window: int = 513               # keys, the query's own among them
    # one entry a layer: True = window attention
    sliding_layers: Tuple[bool, ...] = (False, False, True, True, True)
    first_k_dense_replace: int = 1
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-5
    index_loss_coef: float = 1.0
    # how ``ops/dsa.sparse_attention`` walks its queries; no equation's
    # (the block is the most a walk takes: ``dsa.walk_plan`` fits it to
    # the sequence and to what its calls hold in VMEM)
    index_block: int = 256
    index_tiers: int = 4
    num_experts: int = 256                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 8
    routed_scale: float = 1.0
    moe_intermediate_size: int = 1536
    shared_intermediate_size: int = 1536
    bias_update_rate: float = 0.001

    def __post_init__(self):
        super().__post_init__()
        if len(self.sliding_layers) != self.num_layers:
            raise ValueError(
                f"sliding_layers names {len(self.sliding_layers)} layers, "
                f"num_layers is {self.num_layers}")
        if any(self.sliding_layers[:self.first_k_dense_replace]):
            raise ValueError("a window layer with a dense MLP: the stack "
                             "has none, and neither has LAYER_KINDS")

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order."""
        return tuple(
            "sliding_moe" if win else
            "full_dense" if l < self.first_k_dense_replace else "full_moe"
            for l, win in enumerate(self.sliding_layers))

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def dots3_note_prev(cls, **kw) -> "Dots3Config":
        """dots-studio/dots3-note-prev's config.json: 46 layers, 13 full
        and 33 window, the first dense, 279.6 B parameters in the language
        model. ``num_layers`` cuts the stack from its end."""
        depth = kw.get("num_layers", 46)
        sizes = dict(vocab_size=152_064, hidden_size=5120,
                     intermediate_size=13_824, num_layers=depth,
                     num_heads=128, num_kv_heads=128,
                     max_seq_len=524_288, rope_theta=80_000_000.0,
                     rms_norm_eps=1e-5,
                     sliding_layers=tuple(l not in FULL_LAYERS_46
                                          for l in range(depth)))
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "Dots3Config":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=4, num_heads=4, num_kv_heads=4, max_seq_len=64,
                  q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=12, rope_theta=80_000.0,
                  swa_num_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
                  swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
                  swa_v_head_dim=12, swa_rope_theta=500.0, sliding_window=5,
                  sliding_layers=(False, False, True, True),
                  index_heads=4, index_head_dim=16, index_topk=8,
                  index_block=16, index_tiers=3,
                  num_experts=16, top_k=3, moe_intermediate_size=32,
                  shared_intermediate_size=32, rms_norm_eps=1e-5,
                  dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


def _rope_full(cfg: Dots3Config, tokens):
    return rope_frequencies(cfg.qk_rope_head_dim, tokens.shape[1],
                            cfg.rope_theta, dtype=cfg.dtype)


def _rope_sliding(cfg: Dots3Config, tokens):
    return rope_frequencies(cfg.swa_qk_rope_head_dim, tokens.shape[1],
                            cfg.swa_rope_theta, dtype=cfg.dtype)


_FULL = latent_attention_part(rope=_rope_full, gate=True, rescale=True,
                              index=True)
_SLIDING = latent_attention_part(prefix="swa_", rope=_rope_sliding,
                                 window="sliding_window", gate=True,
                                 rescale=True)
_ROUTED = routed_part(shared=True, score="sigmoid", bias=True)
LAYER_KINDS = {"full_dense": (_FULL, swiglu_part()),
               "full_moe": (_FULL, _ROUTED),
               "sliding_moe": (_SLIDING, _ROUTED)}
STACK = stack.Stack(LAYER_KINDS, reports="router", blocked_head=True)

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
# the routers' bias: kept from the optimizer and moved by the step's
# expert counts (``models/stack.py``), as LFM2's is
trainable, with_trainable = stack.trainable, stack.with_trainable
update_router_bias = STACK.update_router_bias
router_bias_abs_max = stack.router_bias_abs_max

INDEX_LEAVES = ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")


def forward_reports(cfg: Dots3Config, params, tokens, mesh=None):
    """tokens [b, s] -> (logits [b, s, vocab] float32, whole; everything
    the layers report with the routers' logits and the index's inputs and
    choices kept: ``said["router"]``, ``said["dsa"]``). For a check at
    sizes where the logits fit."""
    x, said = STACK.hidden(cfg, params, tokens, mesh=mesh,
                           keep_router_logits=True, keep_index_choice=True)
    return llama._final_head(cfg, params, x), said
