"""Operations of a routed mixture-of-experts decoder, from shapes.

``model`` holds the Hugging Face names ``lib/flops.py`` reads, with
``intermediate_size`` the width of ONE expert, plus ``num_experts`` and
``num_experts_per_tok``. A token multiplies the projections, the router,
``num_experts_per_tok`` experts of three matrices each, and the head;
recomputed operations are never counted.
"""

from __future__ import annotations

from typing import Dict

from benchmark.lib import flops, scope_flops


def expert_params(model: Dict) -> int:
    """One expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def routed_params_per_token(model: Dict) -> int:
    """The expert matrices one token multiplies, every layer."""
    return (model["num_hidden_layers"] * model["num_experts_per_tok"]
            * expert_params(model))


def active_matmul_params(model: Dict) -> int:
    """Parameters in a token's matrix multiplications: projections,
    router, its experts, the head."""
    router = (model["num_hidden_layers"] * model["hidden_size"]
              * model["num_experts"])
    return (scope_flops.attn_proj_params(model) + router
            + routed_params_per_token(model) + scope_flops.head_params(model))


def total_params(model: Dict) -> int:
    h, v, L = (model["hidden_size"], model["vocab_size"],
               model["num_hidden_layers"])
    n = (scope_flops.attn_proj_params(model) + L * h * model["num_experts"]
         + L * model["num_experts"] * expert_params(model)
         + scope_flops.head_params(model))
    if not model.get("tie_word_embeddings"):
        n += v * h
    qd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    return n + L * (2 * h + qd + kvd) + h       # norms, q and k norms


def train_flops_per_token(model: Dict, seq: int) -> float:
    """6 per active matmul parameter plus causal attention forward and
    backward, as ``flops.train_flops_per_token`` counts a dense model."""
    attn_fwd = flops.causal_attention_flops_fwd(
        1, seq, model["num_attention_heads"], model["head_dim"]) / seq
    return (6.0 * active_matmul_params(model)
            + 3.0 * model["num_hidden_layers"] * attn_fwd)


def experts_train_flops(model: Dict, tokens: float) -> float:
    """Forward and backward FLOPs of the grouped matmuls for ``tokens``
    tokens."""
    return 6.0 * routed_params_per_token(model) * tokens
