"""``attn_proj_roofline`` for a stack of convolution and attention
layers: forward and backward FLOPs of the attention layers' q, k, v and
output projections for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scopes ``attn_qkv``
(norm, the per-head q/k norms, both ropes) and ``attn_out``. The flash
kernels are not in it. Bound: compute.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    busy = hybrid_flops.seconds(obs, ("attn_qkv", "attn_out"),
                                need=("attn_qkv", "attn_out"))
    if not busy:
        return None
    m = obs["model"]
    return hybrid_flops.percent_of_peak(
        obs, hybrid_flops.count(m, attn=True)
        * hybrid_flops.attn_proj_params(m), busy)
