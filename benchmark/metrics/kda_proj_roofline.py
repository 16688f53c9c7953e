"""A KDA layer's projections against the compute roofline: forward and
backward FLOPs of every KDA layer's in-projection (to q, k, v, the decay's
f, beta's b and the output gate) and out-projection for one chip's tokens
of a step over the peak bf16 FLOP/s, divided by the device time per step
under the scopes ``kda_in`` and ``kda_out`` (and the block's norm before
them, ``kda_pre_norm``); the recomputed forward is in the time. Bound:
compute.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("kda_in", "kda_out"), also=("kda_pre_norm",))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "kda") * lib.kda_proj_params(m), busy)
