"""``flash_attn_roofline`` for a stack of delta-rule and full-attention
layers: the causal FLOPs (forward and backward) of the full layers at
their head size (128 in Olmo-Hybrid, 30 heads at 32,768 positions) over
the peak, divided by the device time per step of the calls named
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``; the recomputed
forward's call is in the time. Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import delta_flops, mixed_flops

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    t = obs.get("train")
    if (not t or not t["traced_steps"]
            or not delta_flops.is_delta_model(obs)):
        return None
    tf = obs["traffic"]
    return mixed_flops.percent_of_peak_in_kernels(
        obs, delta_flops.flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]), KERNELS)
