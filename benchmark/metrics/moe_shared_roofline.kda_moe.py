"""``moe_shared_roofline`` for an ungated shared expert of 768 at hidden
2,560 in six routed layers: forward and backward FLOPs of its SwiGLU for
one chip's tokens of a step over the peak bf16 FLOP/s, divided by the
device time per step under the scope ``moe_shared``; the recomputed
forward is in the time. Bound: compute.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("moe_shared",))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "moe") * lib.shared_params(m), busy)
