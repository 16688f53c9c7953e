"""Model FLOP/s utilisation of a train step with latent attention and a
held share of a routed mixture: as ``mfu``, the share of the whole step's
peak, with the operations a step needs counted from shapes
(``lib/latent_flops.py``: five projections a layer at the held heads,
causal attention at keys of 192 and values of 128 at 3 times its forward,
the dense SwiGLU or the router and the shared experts, the held rows of
the head) and the held experts' from the rows they multiplied (the counter
``moe_rows_held``, mean over the window's steps). The rate is taken over
the window's steps outside the profiler's span.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import latent_flops, peaks


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"]
            or not latent_flops.is_latent_model(obs)
            or t.get("moe_rows_held") is None):
        return None
    tf = obs["traffic"]
    per_step = latent_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
