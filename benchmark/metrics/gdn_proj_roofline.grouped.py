"""``gdn_proj_roofline`` at grouped heads: forward and backward FLOPs of
every linear layer's in-projection (to the gate z at the value heads, q and
k at the key heads, v, a and b) and out-projection for one chip's tokens of
a step over the peak bf16 FLOP/s, divided by the device time per step under
the scopes ``gdn_in`` and ``gdn_out`` (and the block's norm before them,
``gdn_pre_norm``); the recomputed forward is in the time. Bound: compute.
source: device_trace (lib/delta_moe_flops.py's reduction)."""
from benchmark.lib import delta_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("gdn_in", "gdn_out"), also=("gdn_pre_norm",))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "linear") * lib.gdn_proj_params(m), busy)
