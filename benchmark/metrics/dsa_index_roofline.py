"""The learned index's scores against their roofline: the larger of the
needed FLOPs (``2 x index heads x index width`` a causal pair forward,
since every causal pair is scored before any is dropped, and twice that a
CHOSEN pair backward, where the index term's gradient is not zero; both
full layers) at the peak bf16 FLOP/s and the least bytes (the index's
queries, keys and head weights and their gradients once each) at the HBM
bandwidth, divided by the device time per step under the scope
``dsa_scores``, forward, recomputed forward and backward. The same count
whatever computes the scores. Bound: compute.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import sparse_flops


def read(obs):
    busy = sparse_flops.seconds(obs, ("dsa_scores",))
    if not busy:
        return None
    t, tf = obs["train"], obs["traffic"]
    return sparse_flops.percent_of_floor(
        obs, sparse_flops.index_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        sparse_flops.index_bytes_per_step(
            obs["model"], sparse_flops.chip_tokens(obs)), busy)
