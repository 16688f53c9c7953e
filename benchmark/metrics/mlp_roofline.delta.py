"""``mlp_roofline`` for a stack of delta-rule and full-attention layers:
forward and backward FLOPs of every layer's SwiGLU for one chip's tokens
of a step over the peak bf16 FLOP/s, divided by the device time per step
under the scope ``mlp`` (the SwiGLU, the norm of its output, and their
recomputed forward). Bound: compute.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    busy = delta_flops.seconds(obs, ("mlp",))
    if not busy:
        return None
    return delta_flops.percent_of_peak(
        obs, delta_flops.mlp_params(obs["model"]), busy)
