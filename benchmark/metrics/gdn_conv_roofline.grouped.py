"""``gdn_conv_roofline`` at grouped heads: the least bytes the linear
layers' causal taps and silu move in a step
(``lib/delta_moe_flops.conv_bytes_per_step``: forward 2 widths of q k v a
token, backward 3, over 8,192 channels: q and k at the key heads) over the
HBM bandwidth, divided by the device time per step under the scope
``gdn_conv``: the kernel pair ``ops/conv.taps_silu`` that Olmo-Hybrid's and
Granite's layers run too. Bound: memory bandwidth.
source: device_trace (lib/delta_moe_flops.py's reduction)."""
from benchmark.lib import delta_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("gdn_conv",))
    if not busy:
        return None
    return lib.percent_of_floor(
        obs, 0.0, lib.conv_bytes_per_step(obs["model"],
                                          lib.chip_tokens(obs)), busy)
