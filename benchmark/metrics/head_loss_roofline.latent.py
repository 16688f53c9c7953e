"""``head_loss_roofline`` for a stack of latent-attention layers: 6 x
hidden x the held rows of the vocabulary per token (forward and backward
of the untied head) for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scope ``head_loss``
(final norm, head, softmax cross-entropy). Bound: compute.
source: device_trace (lib/latent_flops.py's reduction)."""
from benchmark.lib import latent_flops


def read(obs):
    busy = latent_flops.seconds(obs, ("head_loss",))
    if not busy:
        return None
    return latent_flops.percent_of_peak(
        obs, latent_flops.head_params(obs["model"]), busy)
