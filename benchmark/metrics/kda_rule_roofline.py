"""The delta rule whose decay is a vector over the key's channels against
its roofline: the larger of the rule's FLOPs
(``lib/kda_moe_flops.rule_flops_per_step``: the chunked algorithm at a
chunk of 64, 32 heads of 128 / 128, forward and backward, every KDA layer;
the decays add no product) over the peak bf16 FLOP/s and its least bytes
(``rule_bytes_per_step``) over the HBM bandwidth, for one chip's tokens of
a step, divided by the device time per step under the scope ``kda_rule``
(the swaps, the L2 norms, the running sums, the sub-blocks' decayed copies
and the walk; the recomputed forward is in the time). Bound: the
operations at these widths.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("kda_rule",))
    if not busy:
        return None
    tf, t = obs["traffic"], obs["train"]
    return lib.percent_of_floor(
        obs, lib.rule_flops_per_step(obs["model"], tf["batch"] / t["chips"],
                                     tf["seq"]),
        lib.rule_bytes_per_step(obs["model"], lib.chip_tokens(obs)), busy)
