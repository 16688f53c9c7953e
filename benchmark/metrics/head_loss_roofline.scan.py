"""``head_loss_roofline`` for a head and loss that walk blocks of tokens:
6 x hidden x the vocabulary's rows per token (forward and backward of the
tied head) for one chip's tokens of a step over the peak bf16 FLOP/s,
divided by the device time per step under the scope ``head_loss`` (final
norm, and block by block the head, the softmax cross-entropy and, in the
backward, the head once more: that recomputed forward is not needed work
and is not counted, so a third of the time cannot be claimed). Bound:
compute.
source: device_trace (lib/scan_flops.py's reduction)."""
from benchmark.lib import scan_flops


def read(obs):
    busy = scan_flops.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return scan_flops.percent_of_peak(
        obs, scan_flops.head_params(obs["model"]), busy)
