"""The chunked selective scan against its roofline: the larger of its
FLOPs (``lib/scan_flops.scan_flops_per_step``: forward and backward of the
chunked algorithm at the published chunk, every scan layer) over the peak
bf16 FLOP/s and its least bytes (``scan_bytes_per_step``) over the HBM
bandwidth, for one chip's tokens of a step, divided by the device time per
step under the scope ``ssm_scan`` (softplus, the walked chunks, the skip;
the recomputed forward is in the time). The scan is XLA's fusions and
matmuls, not a kernel. Bound: whichever is larger; at 64 heads of 64,
state 128, the bytes by a little.
source: device_trace (lib/scan_flops.py's reduction)."""
from benchmark.lib import scan_flops


def read(obs):
    busy = scan_flops.seconds(obs, ("ssm_scan",))
    if not busy:
        return None
    tf, t = obs["traffic"], obs["train"]
    return scan_flops.percent_of_floor(
        obs, scan_flops.scan_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        scan_flops.scan_bytes_per_step(obs["model"],
                                       scan_flops.chip_tokens(obs)), busy)
