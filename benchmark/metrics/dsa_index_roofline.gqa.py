"""``dsa_index_roofline`` at an index of 16 heads of 64 in every layer: the
larger of the needed FLOPs (``2 x index heads x index width`` a causal pair
forward, since every causal pair is scored before any is dropped, and twice
that a CHOSEN pair backward, where the index term's gradient is not zero) at
the peak bf16 FLOP/s and the least bytes (the index's queries, keys and head
weights and their gradients once each) at the HBM bandwidth, divided by the
device time per step under the scope ``dsa_scores``, forward, recomputed
forward and backward. The same count whatever computes the scores. Bound:
compute.
source: device_trace (lib/sparse_gqa_flops.py's reduction)."""
from benchmark.lib import sparse_gqa_flops as sg


def read(obs):
    busy = sg.seconds(obs, ("dsa_scores",))
    if not busy:
        return None
    t, tf = obs["train"], obs["traffic"]
    return sg.percent_of_floor(
        obs, sg.index_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        sg.index_bytes_per_step(obs["model"], sg.chip_tokens(obs)), busy)
