"""Model FLOP/s utilisation of a routed mixture-of-experts train step:
as ``mfu``, with the operations a token needs counted over the matrices
it multiplies (projections, router, its ``num_experts_per_tok`` experts,
head: ``lib/moe_flops.py``) plus causal attention, forward and backward.
The rate is taken over the window's steps outside the profiler's span.
source: host_clock (the rate) and shapes."""
from benchmark.lib import moe_flops, peaks


def read(obs):
    t = obs.get("train")
    if not t or not t["untraced_steps"] or "num_experts" not in obs["model"]:
        return None
    rate = t["untraced_steps"] * t["tokens_per_step"] / t["untraced_s"]
    per_tok = moe_flops.train_flops_per_token(obs["model"],
                                              obs["traffic"]["seq"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return 100.0 * per_tok * rate / (t["chips"] * peak)
