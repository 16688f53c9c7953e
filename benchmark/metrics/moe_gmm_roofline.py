"""The grouped-matmul kernels alone against the compute roofline: the
experts' forward and backward FLOPs for one chip's tokens of a step
(``moe_experts_roofline``'s) over the peak bf16 FLOP/s, divided by the
device time per step of the step's Mosaic grouped-matmul calls: jax's
megablox kernels (``gmm``, ``tgmm``), those XLA:TPU makes of
``jax.lax.ragged_dot`` (``ragged-dot*``), or a kernel of the repo's own
(``moe_gmm*``). The recomputed forward's calls are in the
time, their FLOPs are not counted. Bound: compute.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import moe_flops, moe_scopes

KERNELS = ("gmm", "tgmm", "moe_gmm", "ragged-dot")


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or "num_experts" not in obs["model"]:
        return None
    return moe_scopes.percent_of_peak(
        obs, moe_flops.experts_train_flops(obs["model"],
                                           t["tokens_per_step"] / t["chips"]),
        moe_scopes.kernel_seconds(obs, KERNELS))
