"""``ssm_conv_roofline`` for the taps over x and 8 groups of B and C
(10,240 channels): the least bytes the taps, their bias and the silu move in
a step (forward 2 widths a token, backward 3; every scan layer) over the HBM
bandwidth, divided by the device time per step under the scope
``ssm_conv``. Bound: memory bandwidth.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("ssm_conv",))
    if not busy:
        return None
    return lib.percent_of_floor(
        obs, 0.0, lib.conv_bytes_per_step(obs["model"],
                                          lib.chip_tokens(obs)), busy)
