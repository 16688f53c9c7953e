"""The q, k, v and output projections against the compute roofline:
their forward and backward FLOPs for one chip's tokens of a step, over
the peak bf16 FLOP/s, divided by the device time under the scopes
``attn_qkv`` (with norm and rope) and ``attn_out`` per step. The flash
kernels are not in it (``flash_attn_roofline``). Bound: compute.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import scope_roofline


def read(obs):
    return scope_roofline.percent(obs, "attn_proj", ("attn_qkv", "attn_out"))
