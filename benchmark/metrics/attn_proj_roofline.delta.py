"""``attn_proj_roofline`` for a stack of delta-rule and full-attention
layers: forward and backward FLOPs of the full layers' q, k, v and output
projections for one chip's tokens of a step over the peak bf16 FLOP/s,
divided by the device time per step under the scopes ``attn_qkv`` (the
norms of q and k; no rope in this stack) and ``attn_out`` (the norm of the
block's output); the recomputed forward is in the time. The flash kernels
are not in it. Bound: compute.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    busy = delta_flops.seconds(obs, ("attn_qkv", "attn_out"))
    if not busy:
        return None
    m = obs["model"]
    return delta_flops.percent_of_peak(
        obs, delta_flops.count(m, "full") * delta_flops.attn_proj_params(m),
        busy)
