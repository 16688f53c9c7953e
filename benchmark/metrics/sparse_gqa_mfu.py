"""Model FLOP/s utilisation of a train step whose every layer attends, with
grouped-query heads, over the keys a learned index chooses: as ``mfu``, the
share of the whole step's peak, with the operations a step needs counted
from shapes (``lib/sparse_gqa_flops.py``: every layer's four attention
projections, the index's projections at 4 a parameter and token, its scores
over every causal pair forward and the chosen pairs backward, attention over
the chosen keys at 3 times its forward, the router, the held rows of the
head) and the held experts' from the rows they multiplied (the counter
``moe_rows_held``, mean over the window's steps). The rate is the window's:
the cell's traced steps follow it (``cells/train_sparse_gqa.py``), so a
traced run reads what an untraced run does.
source: host_clock (the rate), shapes and program_counter."""
from benchmark.lib import peaks, sparse_gqa_flops


def read(obs):
    t = obs.get("train")
    if (not t or not t["untraced_steps"]
            or not sparse_gqa_flops.is_sparse_gqa_model(obs)
            or t.get("moe_rows_held") is None):
        return None
    tf = obs["traffic"]
    per_step = sparse_gqa_flops.train_flops_per_step(
        obs["model"], tf["batch"], tf["seq"], t["moe_rows_held"])
    peak = peaks.peaks(obs["device"]["device_kind"])["bf16_flops"]
    return (100.0 * per_step * t["untraced_steps"]
            / (t["untraced_s"] * t["chips"] * peak))
