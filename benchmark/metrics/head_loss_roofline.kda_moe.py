"""``head_loss_roofline`` for Ling-3.0-flash's untied head and loss over
blocks of tokens and the held rows of the vocabulary: 6 x hidden x the held
rows per token (forward and backward of the head) for one chip's tokens of
a step over the peak bf16 FLOP/s, divided by the device time per step under
the scope ``head_loss`` (the last norm and, block by block, the head, the
softmax cross-entropy and both gradients of a block:
``ops/layers.blocked_head_loss``). Bound: compute.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return lib.percent_of_peak(obs, lib.head_params(obs["model"]), busy)
