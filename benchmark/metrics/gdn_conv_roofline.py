"""The linear layers' causal taps and silu against the memory roofline:
the least bytes they move in a step
(``lib/delta_flops.conv_bytes_per_step``: forward 2 widths of q k v a
token, backward 3; one chip's tokens, every linear layer) over the HBM
bandwidth, divided by the device time per step under the scope
``gdn_conv``: the kernel pair ``ops/conv.taps_silu`` that Granite's
``ssm_conv`` runs too, here over 11,520 channels with a zero bias. Bound:
memory bandwidth.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    busy = delta_flops.seconds(obs, ("gdn_conv",))
    if not busy:
        return None
    return delta_flops.percent_of_floor(
        obs, 0.0, delta_flops.conv_bytes_per_step(
            obs["model"], delta_flops.chip_tokens(obs)), busy)
