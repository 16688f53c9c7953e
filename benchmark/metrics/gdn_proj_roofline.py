"""The linear layers' two projections against the compute roofline:
forward and backward FLOPs of every linear layer's in-projection (to the
gate, q k v, a and b) and out-projection for one chip's tokens of a step
over the peak bf16 FLOP/s, divided by the device time per step under the
scopes ``gdn_in`` and ``gdn_out``; the recomputed forward is in the time.
Bound: compute.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    busy = delta_flops.seconds(obs, ("gdn_in", "gdn_out"))
    if not busy:
        return None
    m = obs["model"]
    return delta_flops.percent_of_peak(
        obs, delta_flops.count(m, "linear") * delta_flops.gdn_proj_params(m),
        busy)
