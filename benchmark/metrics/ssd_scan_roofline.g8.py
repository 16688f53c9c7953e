"""``ssd_scan_roofline`` for scans at 128 heads in 8 groups of B and C and a
chunk of 128: the larger of the chunked algorithm's FLOPs (a chunk's ``C
B^T`` once a group: ``lib/scan_moe_flops.scan_flops_per_step``) over the peak
bf16 FLOP/s and its least bytes over the HBM bandwidth, for one chip's
tokens of a step, divided by the device time per step under the scope
``ssm_scan`` (softplus, the kernels' two calls and the running sums around
them; the recomputed forward is in the time). Bound: whichever is larger.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("ssm_scan",))
    if not busy:
        return None
    tf, t = obs["traffic"], obs["train"]
    return lib.percent_of_floor(
        obs, lib.scan_flops_per_step(obs["model"], tf["batch"] / t["chips"],
                                     tf["seq"]),
        lib.scan_bytes_per_step(obs["model"], lib.chip_tokens(obs)), busy)
