"""Model FLOP/s utilisation of a train step over layers of unequal
kinds: as ``mfu``, with the operations a step needs counted by layer kind
(``lib/mixed_flops.py``: projections of each layer's own head count,
causal or band attention, dense MLP or router and shared expert, the
head) and the held experts' from the rows they multiplied (the counter
``moe_rows_held``, mean over the window's steps). The rate is taken over
the window's steps outside the profiler's span.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import mixed_flops, peaks


def read(obs):
    t = obs.get("train")
    if not t or not t["untraced_steps"] or not t.get("moe_rows_held"):
        return None
    tf = obs["traffic"]
    per_step = mixed_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
