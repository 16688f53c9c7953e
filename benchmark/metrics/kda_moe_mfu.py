"""Model FLOP/s utilisation of a train step over Kimi Delta Attention
layers, a gated latent-attention layer, dense SwiGLUs and a held share of a
routed mixture: as ``mfu``, the share of the whole step's peak, with the
operations a step needs counted from shapes (``lib/kda_moe_flops.py``: a
KDA layer's two projections and its chunked rule, the latent layer's
projections and its causal scores at 3 times their forward, a dense
layer's SwiGLU, a routed layer's router and shared expert, the held rows of
the head) and the held experts' from the rows they multiplied (the counter
``moe_rows_held``, mean over the window's steps). The rate is taken over
the window's steps outside the profiler's span.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import kda_moe_flops, peaks


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"]
            or not kda_moe_flops.is_kda_moe_model(obs)
            or t.get("moe_rows_held") is None):
        return None
    tf = obs["traffic"]
    per_step = kda_moe_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
