"""``head_loss_roofline`` for this stack: 6 x hidden x the held rows of
the vocabulary per token (forward and backward of the untied head) for one
chip's tokens of a step over the peak bf16 FLOP/s, divided by the device
time per step under the scope ``head_loss`` (final norm, head, softmax
cross-entropy in blocks of tokens, three products a block). Bound: compute.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import sparse_flops


def read(obs):
    busy = sparse_flops.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return sparse_flops.percent_of_peak(
        obs, sparse_flops.head_params(obs["model"]), busy)
