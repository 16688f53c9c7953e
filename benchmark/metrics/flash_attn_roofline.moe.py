"""``flash_attn_roofline`` for a step that holds other Mosaic calls
beside the flash kernels (a routed layer's grouped matmuls): the same
FLOPs over the peak, divided by the device time per step of the calls
named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` alone.
Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import flops, moe_scopes


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"]:
        return None
    tf = obs["traffic"]
    return moe_scopes.percent_of_peak(
        obs, flops.flash_flops_per_step(obs["model"],
                                        tf["batch"] / t["chips"], tf["seq"]),
        moe_scopes.kernel_seconds(obs, ("flash_",)))
