"""``delta_rule_roofline`` where fewer key heads serve the value heads
(Qwen3-Next: 16 under 32): the larger of the rule's FLOPs
(``lib/delta_moe_flops.rule_flops_per_step``: the chunked algorithm at a
chunk of 64 at the value heads, forward and backward, every linear layer)
over the peak bf16 FLOP/s and its least bytes (``rule_bytes_per_step``: q
and k once at the key heads, whatever the program copies) over the HBM
bandwidth, for one chip's tokens of a step, divided by the device time per
step under the scope ``gdn_rule`` (the L2 norms, the decay and beta, the
copies of q and k to the value heads, the kernels' relayouts and the two
Mosaic calls; the recomputed forward is in the time). Bound: whichever is
larger; at keys and values of 128, the operations.
source: device_trace (lib/delta_moe_flops.py's reduction)."""
from benchmark.lib import delta_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("gdn_rule",))
    if not busy:
        return None
    tf, t = obs["traffic"], obs["train"]
    return lib.percent_of_floor(
        obs, lib.rule_flops_per_step(obs["model"], tf["batch"] / t["chips"],
                                     tf["seq"]),
        lib.rule_bytes_per_step(obs["model"], lib.chip_tokens(obs)), busy)
