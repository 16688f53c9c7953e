"""``mlp_roofline`` for a stack of selective-scan and attention layers:
forward and backward FLOPs of every layer's SwiGLU for one chip's tokens
of a step over the peak bf16 FLOP/s, divided by the device time per step
under the scope ``mlp`` (the norm before it, the SwiGLU, the residual's
multiplier, and their recomputed forward). Bound: compute.
source: device_trace (lib/scan_flops.py's reduction)."""
from benchmark.lib import scan_flops


def read(obs):
    busy = scan_flops.seconds(obs, ("mlp",))
    if not busy:
        return None
    return scan_flops.percent_of_peak(
        obs, scan_flops.mlp_params(obs["model"]), busy)
