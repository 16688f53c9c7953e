"""The KDA layers' causal taps and silu against the memory roofline: the
least bytes they move in a step (``lib/kda_moe_flops.conv_bytes_per_step``:
forward 2 widths of q k v a token, backward 3, over 12,288 channels) over
the HBM bandwidth, divided by the device time per step under the scope
``kda_conv``: the kernel pair ``ops/conv.taps_silu`` that the delta-rule
and scan layers run too. Bound: memory bandwidth.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("kda_conv",))
    if not busy:
        return None
    return lib.percent_of_floor(
        obs, 0.0, lib.conv_bytes_per_step(obs["model"],
                                          lib.chip_tokens(obs)), busy)
