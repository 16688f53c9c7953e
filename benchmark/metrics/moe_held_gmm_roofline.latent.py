"""``moe_held_gmm_roofline`` for two-matrix experts in a latent: 6 x the
rows the held experts multiplied (the counter ``moe_rows_held``, mean over
the traced steps, every mixture and the module's) x one expert's parameters
(2 x 1,024 x 2,688) over the peak bf16 FLOP/s, divided by the device time
per step of the megablox calls (``gmm``, ``tgmm``, under whatever
transformation jax named them): the kernels' own time and nothing of a
pass around them. At 352 rows an expert a group's last row tile of 256 is
five eighths empty. Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``) and program_counter."""
from benchmark.lib import mixed_flops
from benchmark.lib import scan_moe_flops as lib

KERNELS = ("gmm", "tgmm")


def read(obs):
    t = obs.get("train")
    if (not t or not t["traced_steps"] or not lib.is_scan_moe_model(obs)
            or not t.get("moe_rows_held_traced")):
        return None
    return mixed_flops.percent_of_peak_in_kernels(
        obs, lib.experts_train_flops(
            obs["model"], t["moe_rows_held_traced"] / t["chips"]), KERNELS)
