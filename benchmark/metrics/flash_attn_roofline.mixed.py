"""``flash_attn_roofline`` for a stack of unequal layers: the causal
FLOPs (forward and backward) of the full-attention layers, at their own
head count, over the peak, divided by the device time per step of the
calls named ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` alone
(the window layers' calls are ``flash_win_*``). Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import mixed_flops

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or "held" not in obs["model"]:
        return None
    tf = obs["traffic"]
    return mixed_flops.percent_of_peak_in_kernels(
        obs, mixed_flops.flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"], sliding=False),
        KERNELS)
