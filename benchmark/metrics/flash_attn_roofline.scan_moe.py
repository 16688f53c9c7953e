"""``flash_attn_roofline`` for attention without rotation in a stack of
one-part layers and in its prediction module: the causal FLOPs (forward and
backward, 3.5 times the forward) of both layers at 32 query heads on 2 of
128 over the peak, divided by the device time per step of the calls named
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``; the recomputed
forward's call is in the time. Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import mixed_flops
from benchmark.lib import scan_moe_flops as lib

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or not lib.is_scan_moe_model(obs):
        return None
    tf = obs["traffic"]
    return mixed_flops.percent_of_peak_in_kernels(
        obs, lib.flash_flops_per_step(obs["model"], tf["batch"] / t["chips"],
                                      tf["seq"]), KERNELS)
