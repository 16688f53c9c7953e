"""``mlp_roofline`` for a stack of convolution and attention layers:
forward and backward FLOPs of the dense layers' SwiGLU for one chip's
tokens of a step over the peak bf16 FLOP/s, divided by the device time
per step under the scope ``mlp`` itself: the routed experts' four scopes
lie inside it and are not in it, so what is there is the dense MLP, the
norm before it and before each router, and their recomputed forward.
Bound: compute.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    busy = hybrid_flops.seconds(obs, ("mlp",), need=("mlp",))
    if not busy:
        return None
    return hybrid_flops.percent_of_peak(
        obs, hybrid_flops.mlp_params(obs["model"]), busy)
