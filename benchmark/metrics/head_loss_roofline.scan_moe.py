"""``head_loss_roofline`` for an untied head walked twice a step, by the
model and by its prediction module: 2 x 6 x hidden x the held rows of the
vocabulary per token for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scope ``head_loss``
in the stack and under ``mtp`` (a last norm each and, block by block, the
head, the softmax cross-entropy and both gradients of a block). Bound:
compute.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return lib.percent_of_peak(obs, 2 * lib.head_params(obs["model"]), busy)
