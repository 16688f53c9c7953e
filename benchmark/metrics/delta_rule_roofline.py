"""The chunked gated delta rule against its roofline: the larger of its
FLOPs (``lib/delta_flops.rule_flops_per_step``: forward and backward of
the chunked algorithm at a chunk of 64, every linear layer) over the peak
bf16 FLOP/s and its least bytes (``rule_bytes_per_step``) over the HBM
bandwidth, for one chip's tokens of a step, divided by the device time per
step under the scope ``gdn_rule`` (the L2 norms of q and k, the decay and
beta, the walked chunks with their triangular inverses; the recomputed
forward is in the time). The rule is XLA's fusions and matmuls, not a
kernel. Bound: whichever is larger; at 30 heads of keys 96 and values 192,
the bytes.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    busy = delta_flops.seconds(obs, ("gdn_rule",))
    if not busy:
        return None
    tf, t = obs["traffic"], obs["train"]
    return delta_flops.percent_of_floor(
        obs, delta_flops.rule_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        delta_flops.rule_bytes_per_step(obs["model"],
                                        delta_flops.chip_tokens(obs)), busy)
