"""``head_loss_roofline`` for an untied head and loss that walk blocks of
tokens over the held rows of the vocabulary: 6 x hidden x the held rows
per token (forward and backward of the head) for one chip's tokens of a
step over the peak bf16 FLOP/s, divided by the device time per step under
the scope ``head_loss`` (final norm, and block by block the head, the
softmax cross-entropy and, in the backward, the head once more: that
recomputed forward is not needed work and is not counted, so a third of
the time cannot be claimed). Bound: compute.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    busy = delta_flops.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return delta_flops.percent_of_peak(
        obs, delta_flops.head_params(obs["model"]), busy)
