"""``head_loss_roofline`` for a stack of convolution and attention layers
with a slice of a tied vocabulary: 6 x hidden x the held rows of the
embedding per token (forward and backward of the head) for one chip's
tokens of a step over the peak bf16 FLOP/s, divided by the device time
per step under the scope ``head_loss`` (final norm, head, softmax
cross-entropy). Bound: compute.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    busy = hybrid_flops.seconds(obs, ("head_loss",), need=("head_loss",))
    if not busy:
        return None
    return hybrid_flops.percent_of_peak(
        obs, hybrid_flops.head_params(obs["model"]), busy)
