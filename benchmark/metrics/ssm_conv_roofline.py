"""The scan layers' causal taps, bias and silu against the memory
roofline: the least bytes they move in a step
(``lib/scan_flops.conv_bytes_per_step``: forward 2 widths of x B C a
token, backward 3; one chip's tokens, every scan layer) over the HBM
bandwidth, divided by the device time per step under the scope
``ssm_conv``. The pass is XLA's fusions, not a kernel. Bound: memory
bandwidth.
source: device_trace (lib/scan_flops.py's reduction)."""
from benchmark.lib import scan_flops


def read(obs):
    busy = scan_flops.seconds(obs, ("ssm_conv",))
    if not busy:
        return None
    return scan_flops.percent_of_floor(
        obs, 0.0, scan_flops.conv_bytes_per_step(
            obs["model"], scan_flops.chip_tokens(obs)), busy)
