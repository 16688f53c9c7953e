"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B,
``model_type`` ``KeyeVL2``): 48 layers of one kind, grouped-query attention
over the keys a learned index chooses, rope in three position streams read
from the batch, a softmax router over 128 experts in every layer. The vision
tower (a SigLIP-class ViT) has no key in the model's language ``config`` and
is not built; what the language model owes it is kept: positions in three
streams, and no loss on image positions (the batch's ``mask``).

Every layer (``rms_norm_eps`` 1e-6, no biases):

- ``u = RMSNorm(x)``; ``q = RMSNorm_head(u W_q)`` [32, 128], ``k =
  RMSNorm_head(u W_k)`` [4, 128], ``v = u W_v`` [4, 128] (Qwen3's norm over
  each head's dims, before rope).
- **Rope in three streams** (``rope_scaling.mrope_section`` [16, 24, 24] of
  the 64 frequency pairs, theta 10,000,000; Qwen2-VL's rule, ASSUMED chunked
  and not interleaved: the config has no ``mrope_interleaved``): pair ``i``
  turns by ``pos_t`` for ``i < 16``, by ``pos_h`` for ``16 <= i < 40``, by
  ``pos_w`` beyond, the halves rotated as ``ops/layers.apply_rope`` does. The
  batch brings ``positions [3, b, s]``; text has all three equal and running
  on, an image span of a merged grid ``gh x gw`` that starts at position
  ``p`` has ``pos_t = p``, ``pos_h = p + row``, ``pos_w = p + col`` and the
  next token stands at ``p + max(gh, gw)``. Without positions the three
  streams are ``0 .. s - 1`` and the tables are the plain rope's bit for bit.
- **The index** (``sa_config``: 16 heads of 64 on ONE key a position,
  ``topk`` 2,048; DeepSeek-V3.2-Exp's ``Indexer`` as ``ops/dsa.py`` states
  it): ``q_i = u W_iq`` [16, 64] from the normed input (the model has no
  query latent), ``k_i = LayerNorm(u W_ik)`` [64] (weight and bias, eps
  1e-5), both rotated over all 64 dims with tables of that width in
  sections [8, 12, 12] (ASSUMED: the config gives no index rope width), ``w
  = (u W_iw) x 16 ** -0.5 x 64 ** -0.5`` float32; ``I[t, s] = sum_j w[t, j]
  ReLU(q_i[t, j] . k_i[s])``, ``S_t`` the ``min(t + 1, 2048)`` largest over
  ``s <= t`` in sequence order (the causal mask is over the sequence index,
  not over ``pos_*``), ties to the lower position. ``q_chunk_size`` /
  ``kv_chunk_size`` 512 are read as the tiles the published code computes
  its scores in and change no value (ASSUMED).
- ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h // 8] x
  128 ** -0.5) v[s, h // 8]``, then ``W_o``: ``ops/dsa.py``'s walk under
  grouped keys, no key or value repeated.
- **MLP**: ``x + routed(RMSNorm(x))``: softmax over 128 logits in float32,
  the 8 largest, their weights renormalised (``norm_topk_prob``), SwiGLU
  experts of 768, no shared expert.
- **Loss**: cross entropy over the text targets + ``index_loss_coef`` (1) x
  each layer's ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])`` (``p_t``
  the attention's probabilities over ``S_t`` summed over all 32 heads and
  L1-normalised, ``u`` and ``p_t`` under ``stop_gradient``, the choice not
  differentiated; ASSUMED: DeepSeek-V3.2's sparse stage, no dense warm-up) +
  ``router_aux_coef`` (0.001, Qwen3-MoE's; ASSUMED: the config keeps none) x
  the routers' load-balancing term.

``experts_held=(first, count)`` is this chip's share of each layer's experts
under expert parallelism (``ops/moe.py``). The model is the table
``LAYER_KINDS`` and ``models/stack.py`` walks it. Training only.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import llama, stack
from ray_tpu.ops.layers import mrope_frequencies
from ray_tpu.ops.moe import routed_part


@dataclass(frozen=True)
class KeyeVL2Config(llama.LlamaConfig):
    head_dim: Optional[int] = 128
    # frequency pairs each position stream (t, h, w) turns, of the head's
    # and of the index's
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    index_mrope_section: Tuple[int, ...] = (8, 12, 12)
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    index_norm_eps: float = 1e-5
    index_loss_coef: float = 1.0
    # how ``ops/dsa.sparse_attention`` walks its queries; no equation's
    index_block: int = 256
    index_tiers: int = 4
    num_experts: int = 128                  # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None
    held_headroom: Optional[int] = None
    top_k: int = 8
    routed_scale: float = 1.0
    moe_intermediate_size: int = 768
    router_aux_coef: float = 0.001
    rms_norm_eps: float = 1e-6
    tie_embeddings: bool = False

    @property
    def pattern(self) -> Tuple[str, ...]:
        """The kind of each layer, in order: one kind."""
        return ("sparse_moe",) * self.num_layers

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else self.num_experts

    @classmethod
    def keye_vl2_30b_a3b(cls, **kw) -> "KeyeVL2Config":
        """Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json (the language
        model): 48 layers, 128 experts of 768 in each. ``num_layers`` cuts
        the stack from its end."""
        sizes = dict(vocab_size=151_936, hidden_size=2048,
                     intermediate_size=6144, num_layers=48, num_heads=32,
                     num_kv_heads=4, head_dim=128, max_seq_len=262_144,
                     rope_theta=10_000_000.0)
        return cls(**{**sizes, **kw})

    @classmethod
    def tiny(cls, **kw) -> "KeyeVL2Config":
        cfg = cls(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_seq_len=64, rope_theta=10_000.0,
                  mrope_section=(2, 3, 3), index_mrope_section=(2, 1, 1),
                  index_heads=4, index_head_dim=8, index_topk=8,
                  index_block=16, index_tiers=3, num_experts=16, top_k=4,
                  moe_intermediate_size=32, dtype=jnp.float32, remat=False)
        return replace(cfg, **kw)


# the name ``benchmark/cells/train_hybrid.load_model`` and
# ``tools/step_program.py`` make of the module's
Keye_vl2Config = KeyeVL2Config


def text_positions(tokens) -> jnp.ndarray:
    """[3, b, s]: every stream ``0 .. s - 1`` (a batch of text)."""
    return jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                            (3,) + tokens.shape)


def _rope(cfg: KeyeVL2Config, tokens, positions=None):
    """((cos, sin) of the heads, (cos, sin) of the index), ``[b, s, width
    / 2]`` each, from the batch's ``positions [3, b, s]`` (text's where it
    brings none)."""
    if positions is None:
        positions = text_positions(tokens)
    return tuple(
        mrope_frequencies(width, positions, sections, cfg.rope_theta,
                          dtype=cfg.dtype)
        for width, sections in ((cfg.head_dim_, cfg.mrope_section),
                                (cfg.index_head_dim,
                                 cfg.index_mrope_section)))


LAYER_KINDS = {"sparse_moe": (
    llama.attention_part(qk_norm="head", rope=_rope, index=True),
    routed_part(score="softmax", renormalize=True, balance=True))}
STACK = stack.Stack(LAYER_KINDS, reports="router", blocked_head=True)

logical_axes = STACK.logical_axes
init_params = STACK.init_params
param_shardings = STACK.param_shardings
forward = STACK.forward
token_nll = STACK.token_nll
loss_terms = STACK.loss_terms
loss_fn = STACK.loss_fn
rows_held, rows_passed = stack.rows_held, stack.rows_passed
# no leaf is kept from the optimizer: the names the cells' steps ask for
trainable, with_trainable = stack.trainable, stack.with_trainable


def _hidden_reports(cfg, params, tokens, positions, mesh):
    return STACK.hidden(cfg, params, tokens, mesh=mesh,
                        keep_router_logits=True, keep_index_choice=True,
                        positions=positions)


def forward_reports(cfg: KeyeVL2Config, params, tokens, positions=None,
                    mesh=None):
    """tokens [b, s] -> (logits [b, s, vocab] float32, whole; everything
    the layers report with the routers' logits and the index's inputs and
    choices kept: ``said["router"]``, ``said["dsa"]``). For a check at
    sizes where the logits fit."""
    x, said = _hidden_reports(cfg, params, tokens, positions, mesh)
    return llama._final_head(cfg, params, x), said


def token_nll_reports(cfg: KeyeVL2Config, params, tokens, positions=None,
                      mesh=None):
    """tokens [b, s + 1] -> (the next-token loss of every position [b, s]
    float32 through the blocked head, as the timed step's loss goes;
    everything the layers report, as ``forward_reports``). For a check at
    sizes where the logits do not fit."""
    x, said = _hidden_reports(cfg, params, tokens[:, :-1], positions, mesh)
    return llama.blocked_token_nll(cfg, params, x, tokens[:, 1:]), said
