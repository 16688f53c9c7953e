"""``attn_proj_roofline`` for grouped-query layers with an index: forward
and backward FLOPs of every layer's four projections (6 a parameter and
token) and of its index's three (4: their input takes no gradient) for one
chip's tokens of a step over the peak bf16 FLOP/s, divided by the device
time per step under the scopes ``attn_qkv``, ``attn_out`` and ``dsa_proj``
(the norms, the heads' norms, the rope from the batch's tables, the index
key's LayerNorm and the recomputed forward are in the time). Bound: compute.
source: device_trace (lib/sparse_gqa_flops.py's reduction)."""
from benchmark.lib import moe_scopes, sparse_gqa_flops as sg


def read(obs):
    busy = sg.seconds(obs, sg.PROJ_SCOPES)
    if not busy:
        return None
    return moe_scopes.percent_of_peak(
        obs, sg.proj_flops_per_step(obs["model"], sg.chip_tokens(obs)), busy)
