"""The two projections of a mixture in a latent against the compute
roofline: forward and backward FLOPs of ``l_down`` (4,096 to 1,024 before
the dispatch) and ``l_up`` (back, after the combine) of every mixture, the
module's with them, for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scope ``moe_latent``;
the recomputed forward is in the time. Bound: compute.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("moe_latent",))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "moe") * lib.latent_params(m), busy)
