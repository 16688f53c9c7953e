"""The experts' grouped matmuls against the compute roofline: 6 x layers
x experts per token x 3 x hidden x expert width FLOPs per token (gate, up,
down; forward and backward) for one chip's tokens of a step, over the
peak bf16 FLOP/s, divided by the device time under the scope
``moe_experts`` per step (which also holds the activation and the
recomputed forward). Bound: compute (a mean of 1,024 rows against an
expert's 12.6 MB of weights is ~500 FLOP a byte, above the chip's 240).
source: device_trace (lib/moe_scopes.py)."""
from benchmark.lib import moe_flops, moe_scopes


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or "num_experts" not in obs["model"]:
        return None
    return moe_scopes.percent_of_peak(
        obs, moe_flops.experts_train_flops(obs["model"],
                                           t["tokens_per_step"] / t["chips"]),
        moe_scopes.seconds(obs, ("moe_experts",)))
