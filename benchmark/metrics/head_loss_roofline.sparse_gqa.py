"""``head_loss_roofline`` for this stack: 6 x hidden x the held rows of
the vocabulary per token (forward and backward of the untied head) for one
chip's tokens of a step over the peak bf16 FLOP/s, divided by the device
time per step under the scope ``head_loss`` (final norm, head, softmax
cross-entropy in blocks of tokens under the text mask, three products a
block). Bound: compute.
source: device_trace (lib/sparse_gqa_flops.py's reduction)."""
from benchmark.lib import sparse_gqa_flops as sg


def read(obs):
    busy = sg.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return sg.percent_of_peak(obs, sg.head_params(obs["model"]), busy)
