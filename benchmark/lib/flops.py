"""Operations and bytes that the algorithm needs, computed from shapes.

``model`` is the dict the cells pass around: the Hugging Face names
(hidden_size, intermediate_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, vocab_size, tie_word_embeddings).
Recomputed operations (rematerialisation) are never counted.
"""

from __future__ import annotations

from typing import Dict


def matmul_params(model: Dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the layers' projections and the output head (the embedding lookup is
    a gather, not a multiplication)."""
    h, f = model["hidden_size"], model["intermediate_size"]
    qd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    per_layer = h * qd + 2 * h * kvd + qd * h + 3 * h * f
    return model["num_hidden_layers"] * per_layer + h * model["vocab_size"]


def total_params(model: Dict) -> int:
    h, v = model["hidden_size"], model["vocab_size"]
    n = matmul_params(model) + v * h          # + the embedding table
    if model.get("tie_word_embeddings"):
        n -= v * h                            # head and table are one
    n += model["num_hidden_layers"] * 2 * h + h          # norms
    if model.get("attention_bias"):
        n += model["num_hidden_layers"] * (
            model["num_attention_heads"] + 2 * model["num_key_value_heads"]
        ) * model["head_dim"]
    return n


def causal_attention_flops_fwd(batch: int, seq: int, heads: int,
                               head_dim: int) -> float:
    """QK^T and PV of causal self-attention, forward: 2 matmuls of
    2*s*s*d each per head, half of it masked away."""
    return batch * heads * (4.0 * seq * seq * head_dim) / 2.0


def train_flops_per_token(model: Dict, seq: int) -> float:
    """6 per matmul parameter (forward 2, backward 4) plus causal
    attention, forward and backward (backward is twice the forward)."""
    attn_fwd_per_token = causal_attention_flops_fwd(
        1, seq, model["num_attention_heads"], model["head_dim"]) / seq
    return (6.0 * matmul_params(model)
            + 3.0 * model["num_hidden_layers"] * attn_fwd_per_token)


def flash_flops_per_step(model: Dict, batch: int, seq: int) -> float:
    """What the flash kernels of one train step must compute: forward
    (2 matmuls) and backward (5 matmuls: recomputed scores, dV, dP, dQ,
    dK), causal, every layer. 3.5x the forward."""
    fwd = causal_attention_flops_fwd(batch, seq,
                                     model["num_attention_heads"],
                                     model["head_dim"])
    return 3.5 * fwd * model["num_hidden_layers"]


def kv_bytes_per_token(model: Dict, dtype_bytes: int = 2) -> int:
    return (2 * model["num_hidden_layers"] * model["num_key_value_heads"]
            * model["head_dim"] * dtype_bytes)


def paged_attention_bytes(model: Dict, context_tokens: float, page: int,
                          slots: int, dtype_bytes: int = 2) -> float:
    """K and V bytes the pages of ONE decode step hold, over all layers:
    each slot reads every page that holds some of its context, whole."""
    pages = slots * -(-context_tokens // page)
    return (pages * page * 2 * model["num_key_value_heads"]
            * model["head_dim"] * dtype_bytes * model["num_hidden_layers"])
