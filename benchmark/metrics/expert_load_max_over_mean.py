"""Rows of the busiest expert over the mean rows of an expert, mean over
the layers and the window's steps, from the per-layer expert counts the
train step returns: 1 is a perfectly balanced router, ``num_experts /
num_experts_per_tok`` one that sends every token to the same experts.
source: program_counter (ray_tpu/ops/moe.py's ``counts``)."""


def read(obs):
    return (obs.get("train") or {}).get("expert_load_max_over_mean")
