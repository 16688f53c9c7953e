"""``mlp_roofline`` for a stack of unequal layers: forward and backward
FLOPs of the SwiGLUs every token multiplies (the dense layer's MLP of
``intermediate_size``, each routed layer's shared expert) for one chip's
tokens of a step, over the peak bf16 FLOP/s, divided by the device time
per step under the scope ``mlp`` without the routed experts' four scopes
(``lib/moe_scopes.py``): what is left there is the dense MLP, the shared
expert (``moe_shared``), the norm before them and their recomputed
forward. Bound: compute.
source: device_trace (lib/moe_scopes.py)."""
from benchmark.lib import mixed_flops, moe_scopes


def read(obs):
    if "held" not in obs.get("model", ()):
        return None
    return mixed_flops.percent_of_peak_in_scopes(
        obs, mixed_flops.mlp_params(obs["model"]),
        moe_scopes.seconds(obs, ("mlp",)))
