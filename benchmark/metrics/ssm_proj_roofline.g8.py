"""``ssm_proj_roofline`` for a scan layer of 128 heads in 8 groups: forward
and backward FLOPs of the in-projection (to z, x B C at 10,240 channels and
dt) and the out-projection of every scan layer for one chip's tokens of a
step over the peak bf16 FLOP/s, divided by the device time per step under
the scopes ``ssm_in`` and ``ssm_out``; the recomputed forward is in the
time. Bound: compute.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("ssm_in", "ssm_out"))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "mamba") * lib.ssm_proj_params(m), busy)
