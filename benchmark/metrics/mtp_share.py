"""Share of the device's busy time under the scope ``mtp``: the prediction
module's two norms and joining matrix (``mtp_join``), its attention layer
and its mixture, its pass of the blocked head (``mtp_head``), forward,
recomputed forward and backward.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.module_seconds(obs)
    r = lib.for_obs(obs) if busy else None
    if not r or not r["busy_s"]:
        return None
    return 100.0 * busy / r["busy_s"]
