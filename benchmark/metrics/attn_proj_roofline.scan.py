"""``attn_proj_roofline`` for a stack of selective-scan and attention
layers: forward and backward FLOPs of the attention layers' q, k, v and
output projections for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scopes ``attn_qkv``
(the norm before them; no rope in this stack) and ``attn_out`` (the
residual's multiplier); the recomputed forward is in the time. The flash
kernels are not in it. Bound: compute.
source: device_trace (lib/scan_flops.py's reduction)."""
from benchmark.lib import scan_flops


def read(obs):
    busy = scan_flops.seconds(obs, ("attn_qkv", "attn_out"))
    if not busy:
        return None
    m = obs["model"]
    return scan_flops.percent_of_peak(
        obs, scan_flops.count(m, "attention")
        * scan_flops.attn_proj_params(m), busy)
