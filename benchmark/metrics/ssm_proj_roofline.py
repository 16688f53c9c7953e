"""The scan layers' two projections against the compute roofline: forward
and backward FLOPs of every scan layer's in-projection (to z, x B C and
dt) and out-projection for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scopes ``ssm_in``
and ``ssm_out``; the recomputed forward is in the time. Bound: compute.
source: device_trace (lib/scan_flops.py's reduction)."""
from benchmark.lib import scan_flops


def read(obs):
    busy = scan_flops.seconds(obs, ("ssm_in", "ssm_out"))
    if not busy:
        return None
    m = obs["model"]
    return scan_flops.percent_of_peak(
        obs, scan_flops.count(m, "mamba") * scan_flops.ssm_proj_params(m),
        busy)
