"""``moe_route_share`` for a router of 22 of 512 with a selection bias in a
stack of one-part layers: the share of the device's busy time under the
scope ``moe_route`` (router matmul, sigmoid, top-22 on scores plus bias, the
sort, the counts) and, after the optimizer, the bias's update
(``moe_bias_update``), in the stack and under ``mtp``. Latency- and
memory-bound work beside the matmuls.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("moe_route",), also=("moe_bias_update",))
    r = lib.for_obs(obs) if busy else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * busy / r["busy_s"]
