"""The gated short convolution operators against the compute roofline:
forward and backward FLOPs of every convolution layer's in- and
out-projection for one chip's tokens of a step over the peak bf16 FLOP/s,
divided by the device time per step under the scope ``short_conv``
(``conv_in``, ``conv_mix``, ``conv_out``: both projections and the
elementwise pass between them, whose few FLOPs are not counted; the
recomputed forward is in the time). Bound: compute.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    busy = hybrid_flops.seconds(obs, hybrid_flops.CONV_SCOPES,
                                need=("conv_in", "conv_out"))
    if not busy:
        return None
    m = obs["model"]
    return hybrid_flops.percent_of_peak(
        obs, hybrid_flops.count(m, attn=False)
        * hybrid_flops.conv_proj_params(m), busy)
