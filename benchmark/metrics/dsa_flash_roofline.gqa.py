"""``dsa_flash_roofline`` under grouped keys: attention over the keys the
index chose, against its roofline: the larger of the needed FLOPs (the
CHOSEN pairs alone, ``sum_t min(t + 1, topk)`` a sequence, times the 32
query heads, times the forward's two products and the backward's five at a
head of 128: ``8 d + 6 d`` a pair and head; every layer) at the peak bf16
FLOP/s and the least bytes (q, o, dO, dq at the query heads, k, v and their
gradients once a group) at the HBM bandwidth, divided by the device time per
step under the scope ``flash_sparse``, forward, recomputed forward and
backward. The same count whatever implements it: a walk that scores every
causal pair under a mask spends four times the needed operations at 16,384
positions and reads low here. Bound: compute.
source: device_trace (lib/sparse_gqa_flops.py's reduction)."""
from benchmark.lib import sparse_gqa_flops as sg


def read(obs):
    busy = sg.seconds(obs, ("flash_sparse",))
    if not busy:
        return None
    t, tf = obs["train"], obs["traffic"]
    return sg.percent_of_floor(
        obs, sg.sparse_flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        sg.flash_bytes_per_step(obs["model"], sg.chip_tokens(obs)), busy)
