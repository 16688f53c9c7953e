"""``attn_proj_roofline`` for a full layer whose ``wq`` holds an
elementwise gate beside each head's query: forward and backward FLOPs of
``wq`` at twice the heads' width, ``wk``, ``wv`` and ``wo`` for one chip's
tokens of a step over the peak bf16 FLOP/s, divided by the device time per
step under the scopes ``attn_qkv`` (the block's norm, the per-head norms of
q and k, the partial rope), ``attn_out`` and ``attn_gate`` (the sigmoid and
its product, which lie inside them); the recomputed forward is in the time.
The flash kernels are not in it. Bound: compute.
source: device_trace (lib/delta_moe_flops.py's reduction)."""
from benchmark.lib import delta_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("attn_qkv", "attn_out", "attn_gate"))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "full") * lib.attn_proj_params(m), busy)
