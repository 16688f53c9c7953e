"""The window flash kernels against the compute roofline: the band's
needed FLOPs (forward and backward, ``sliding_window`` keys a query) of
the sliding layers for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step of the calls named
``flash_win_fwd``, ``flash_win_bwd_dq`` and ``flash_win_bwd_dkv``. The
recomputed forward's calls are in the time, their FLOPs are not counted;
a kernel that visits whole blocks computes the band's ragged edges too.
Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import mixed_flops

KERNELS = ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or "held" not in obs["model"]:
        return None
    tf = obs["traffic"]
    return mixed_flops.percent_of_peak_in_kernels(
        obs, mixed_flops.flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"], sliding=True),
        KERNELS)
