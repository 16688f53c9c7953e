"""Model FLOP/s utilisation of a train step over convolution and
attention layers: as ``mfu``, with the operations a step needs counted by
layer kind (``lib/hybrid_flops.py``: a convolution's two projections or
attention's four and its causal scores, dense MLP or router, the head)
and the held experts' from the rows they multiplied (the counter
``moe_rows_held``, mean over the window's steps). The rate is taken over
the window's steps outside the profiler's span.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import hybrid_flops, peaks


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"] or not t.get("moe_rows_held")
            or "conv_L_cache" not in obs.get("model", ())):
        return None
    tf = obs["traffic"]
    per_step = hybrid_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
