"""Latent attention's projections against the compute roofline: forward
and backward FLOPs of every layer's five projections (both down, both up
at the held heads, the output) for one chip's tokens of a step over the
peak bf16 FLOP/s, divided by the device time per step under the scopes
``mla_q``, ``mla_kv``, ``mla_rope`` and ``mla_out`` (the layer's norm, the
two latents' norms and rope are in the time, and so is the recomputed
forward). Bound: compute.
source: device_trace (lib/latent_flops.py's reduction)."""
from benchmark.lib import latent_flops


def read(obs):
    busy = latent_flops.seconds(obs, latent_flops.MLA_SCOPES)
    if not busy:
        return None
    m = obs["model"]
    return latent_flops.percent_of_peak(
        obs, latent_flops.layers(m) * latent_flops.mla_proj_params(m), busy)
