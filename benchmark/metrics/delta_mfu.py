"""Model FLOP/s utilisation of a train step over gated delta-rule and
full-attention layers: as ``mfu``, the share of the whole step's peak,
with the operations a step needs counted by layer kind
(``lib/delta_flops.py``: a linear layer's two projections and its chunked
rule, attention's four and its causal scores, every layer's SwiGLU, the
held rows of the head). The rate is taken over the window's steps outside
the profiler's span.
source: host_clock (the rate) and shapes."""
from benchmark.lib import delta_flops, peaks


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"]
            or not delta_flops.is_delta_model(obs)):
        return None
    tf = obs["traffic"]
    per_step = delta_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
