"""The grouped-matmul kernels of a layer that holds a share of its
experts, against the compute roofline: 6 x the rows the held experts
multiplied (the program's counter ``moe_rows_held``, mean over the traced
steps, all routed layers) x one expert's parameters over the peak bf16
FLOP/s, divided by the device time per step of the megablox calls
(``gmm``, ``tgmm``, under whatever transformation jax named them:
``jvp_jit_gmm__``): the kernels' own time in the traced steps and nothing
else, not the gathers, masks and scatter-adds of a pass around them
(those are ``moe_dispatch_share``). The calls touch the row tiles the
held rows fill and no other, so rows counted and rows timed are the same
rows; the recomputed forward's calls are in the time, and every group's
last row tile is part empty: at 640 rows an expert that is a fifth of
its tiles. Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``) and program_counter."""
from benchmark.lib import mixed_flops

KERNELS = ("gmm", "tgmm")


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or not t.get("moe_rows_held_traced"):
        return None
    return mixed_flops.percent_of_peak_in_kernels(
        obs, mixed_flops.experts_train_flops(
            obs["model"], t["moe_rows_held_traced"] / t["chips"]), KERNELS)
