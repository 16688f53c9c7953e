"""``mlp_roofline`` for this stack: forward and backward FLOPs of the
dense layer's SwiGLU and every routed layer's shared expert for one chip's
tokens of a step over the peak bf16 FLOP/s, divided by the device time per
step under the scopes ``mlp`` and ``moe_shared`` with the routed mixture's
own scopes taken out (the SwiGLUs, the norm before them, and their
recomputed forward). Bound: compute.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import sparse_flops


def read(obs):
    busy = sparse_flops.seconds(obs, ("mlp", "moe_shared"), need=("mlp",))
    if not busy:
        return None
    return sparse_flops.percent_of_peak(
        obs, sparse_flops.mlp_params(obs["model"]), busy)
