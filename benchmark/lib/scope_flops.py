"""Matmul FLOPs of one train step by model scope (the names
``ray_tpu/models/llama.py`` gives its parts), from shapes: forward 2 per
parameter and token, backward 4, recomputation (rematerialisation) never
counted. Together with ``flops.flash_flops_per_step`` (3.5x the forward
there, 3x here) these are ``flops.train_flops_per_token``'s total, split.
"""

from __future__ import annotations

from typing import Dict


def mlp_params(model: Dict) -> int:
    """gate, up and down projections, every layer."""
    return (model["num_hidden_layers"] * 3 * model["hidden_size"]
            * model["intermediate_size"])


def attn_proj_params(model: Dict) -> int:
    """q, k, v and output projections, every layer."""
    h = model["hidden_size"]
    qd = model["num_attention_heads"] * model["head_dim"]
    kvd = model["num_key_value_heads"] * model["head_dim"]
    return model["num_hidden_layers"] * (h * qd + 2 * h * kvd + qd * h)


def head_params(model: Dict) -> int:
    """The output head (tied or not, it is one multiplication)."""
    return model["hidden_size"] * model["vocab_size"]


PARAMS = {"mlp": mlp_params, "attn_proj": attn_proj_params,
          "head_loss": head_params}


def train_flops(model: Dict, part: str, tokens: float) -> float:
    """Forward and backward FLOPs of ``part`` for ``tokens`` tokens."""
    return 6.0 * PARAMS[part](model) * tokens
