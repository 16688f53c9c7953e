"""``flash_attn_roofline`` at a head of 256 (Qwen3-Next's full layer: 16
query heads over 2 key/value heads at 32,768 positions): the causal FLOPs,
forward and backward, of the full layers over the peak, divided by the
device time per step of the calls named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv``; the recomputed forward's call is in the time. Bound:
compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import delta_moe_flops as lib
from benchmark.lib import mixed_flops

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or not lib.is_delta_moe_model(obs):
        return None
    tf = obs["traffic"]
    return mixed_flops.percent_of_peak_in_kernels(
        obs, lib.flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]), KERNELS)
