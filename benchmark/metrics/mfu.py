"""Model FLOP/s utilisation: (6 per matmul parameter + causal attention
forward and backward) per token, times this run's tokens per second,
over chips times the peak bf16 FLOP/s. Recomputation is not counted.
This metric is read in the traced run, where starting and stopping the
profiler stalls the steps around it; the rate is therefore taken over
the window's steps outside the profiler's span (cells/train.py), which
run as the untraced run's do.
source: host_clock (the rate) and shapes."""
from benchmark.lib import flops, peaks


def read(obs):
    t = obs.get("train")
    if not t or not t["untraced_steps"]:
        return None
    rate = t["untraced_steps"] * t["tokens_per_step"] / t["untraced_s"]
    per_tok = flops.train_flops_per_token(obs["model"], obs["traffic"]["seq"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return 100.0 * per_tok * rate / (t["chips"] * peak)
