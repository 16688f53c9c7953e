"""``mlp_roofline`` for a stack of latent-attention layers: forward and
backward FLOPs of the dense layer's SwiGLU and every routed layer's shared
experts for one chip's tokens of a step over the peak bf16 FLOP/s, divided
by the device time per step under the scope ``mlp`` with the routed
mixture's own scopes taken out (the SwiGLUs, the norm before them, and
their recomputed forward). Bound: compute.
source: device_trace (lib/latent_flops.py's reduction)."""
from benchmark.lib import latent_flops


def read(obs):
    busy = latent_flops.seconds(obs, ("mlp",))
    if not busy:
        return None
    return latent_flops.percent_of_peak(
        obs, latent_flops.mlp_params(obs["model"]), busy)
