"""Attention over the keys the index chose, against its roofline: the
larger of the needed FLOPs (the CHOSEN pairs alone, ``sum_t min(t + 1,
index_topk)`` a sequence, times the held heads, times the forward's two
products and the backward's five at keys of 192 and values of 128: ``8 d_k
+ 6 d_v`` a pair and head; both full layers) at the peak bf16 FLOP/s and
the least bytes (q, k, v, o, dO and the three gradients once each, the
shared rope dims once a position) at the HBM bandwidth, divided by the
device time per step under the scope ``flash_sparse``, forward, recomputed
forward and backward. The same count whatever implements it: a walk that
scores every causal pair under a mask spends four times the needed
operations at 16,384 positions and reads low here; a kernel that visits
the chosen keys alone is read by the same yardstick. Bound: compute.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import sparse_flops


def read(obs):
    busy = sparse_flops.seconds(obs, ("flash_sparse",))
    if not busy:
        return None
    t, tf = obs["train"], obs["traffic"]
    return sparse_flops.percent_of_floor(
        obs, sparse_flops.sparse_flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        sparse_flops.flash_bytes_per_step(
            obs["model"], sparse_flops.chip_tokens(obs)), busy)
