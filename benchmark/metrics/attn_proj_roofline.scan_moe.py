"""``attn_proj_roofline`` for the attention layer of a stack of one-part
layers and the one of its prediction module: forward and backward FLOPs of
``wq``, ``wk``, ``wv`` and ``wo`` (32 query heads on 2 of 128 at hidden
4,096) of both layers for one chip's tokens of a step over the peak bf16
FLOP/s, divided by the device time per step under the scopes ``attn_qkv``
and ``attn_out``, in the stack and under ``mtp``. Bound: compute.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, ("attn_qkv", "attn_out"))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "attention") * lib.attn_proj_params(m), busy)
