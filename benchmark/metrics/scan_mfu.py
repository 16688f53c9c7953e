"""Model FLOP/s utilisation of a train step over selective-scan and
attention layers: as ``mfu``, with the operations a step needs counted by
layer kind (``lib/scan_flops.py``: a scan layer's two projections and its
chunked scan, attention's four and its causal scores, every layer's
SwiGLU, the head). The rate is taken over the window's steps outside the
profiler's span.
source: host_clock (the rate) and shapes."""
from benchmark.lib import peaks, scan_flops


def read(obs):
    t = obs.get("train")
    if not t or not t["untraced_steps"] or not scan_flops.is_scan_model(obs):
        return None
    tf = obs["traffic"]
    per_step = scan_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
