"""``attn_proj_roofline`` for a stack of unequal layers: forward and
backward FLOPs of each held layer's q, k, v and output projections at its
own head count and of its per-head gate, for one chip's tokens of a step,
over the peak bf16 FLOP/s, divided by the device time per step under the
scopes ``attn_qkv`` (norm, both ropes, the gate's matmul and sigmoid:
``attn_gate`` lies inside) and ``attn_out`` (the gate's product with the
heads inside). The flash kernels are not in it. Bound: compute.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import mixed_flops, scopes


def read(obs):
    if "held" not in obs.get("model", ()):
        return None
    return mixed_flops.percent_of_peak_in_scopes(
        obs, mixed_flops.attn_proj_params_all(obs["model"]),
        scopes.model_scope_seconds(obs, ("attn_qkv", "attn_out")))
