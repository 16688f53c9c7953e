"""Model FLOP/s utilisation of a train step over layers of one part (scans
at grouped heads, mixtures in a latent with a held share, attention) and a
multi-token prediction module: as ``mfu``, the share of the whole step's
peak, with the operations a step needs counted from shapes
(``lib/scan_moe_flops.py``: a scan layer's two projections and its chunked
scan, attention's four and its causal scores at 3 times their forward, every
mixture's router, latent projections and shared expert, the module's joining
matrix and layers, the held rows of the head twice) and the held experts'
from the rows they multiplied (the counter ``moe_rows_held``, mean over the
window's steps). The rate is taken over the window's steps outside the
profiler's span.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import peaks, scan_moe_flops


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"]
            or not scan_moe_flops.is_scan_moe_model(obs)
            or t.get("moe_rows_held") is None):
        return None
    tf = obs["traffic"]
    per_step = scan_moe_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
