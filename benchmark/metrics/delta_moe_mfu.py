"""Model FLOP/s utilisation of a train step over gated delta-rule layers at
grouped heads, a gated full-attention layer and a held share of a routed
mixture in every layer: as ``mfu``, the share of the whole step's peak, with
the operations a step needs counted from shapes (``lib/delta_moe_flops.py``:
a linear layer's two projections and its chunked rule at the value heads,
attention's projections with the gate's half of ``wq`` and its causal
scores at 3 times their forward, every layer's router and gated shared
expert, the held rows of the head) and the held experts' from the rows they
multiplied (the counter ``moe_rows_held``, mean over the window's steps).
The rate is taken over the window's steps outside the profiler's span.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import delta_moe_flops, peaks


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"]
            or not delta_moe_flops.is_delta_moe_model(obs)
            or t.get("moe_rows_held") is None):
        return None
    tf = obs["traffic"]
    per_step = delta_moe_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
