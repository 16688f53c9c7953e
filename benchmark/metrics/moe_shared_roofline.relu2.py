"""``moe_shared_roofline`` for a squared-ReLU shared expert of two matrices
(5,376 wide at hidden 4,096) on the hidden state of every mixture, the
module's with them: forward and backward FLOPs for one chip's tokens of a
step over the peak bf16 FLOP/s, divided by the device time per step under
the scope ``moe_shared``; the recomputed forward is in the time. Bound:
compute.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("moe_shared",))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "moe") * lib.shared_params(m), busy)
