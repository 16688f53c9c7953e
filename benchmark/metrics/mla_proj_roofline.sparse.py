"""``mla_proj_roofline`` for a stack of two kinds of latent layer with a
head gate and an index: forward and backward FLOPs of every layer's five
projections and gate (6 a parameter and token) and of the full layers'
index projections (4: their inputs take no gradient) for one chip's tokens
of a step over the peak bf16 FLOP/s, divided by the device time per step
under the scopes ``mla_q``, ``mla_kv``, ``mla_rope``, ``mla_out``,
``attn_gate`` and ``dsa_proj`` (norms, rope, the index key's LayerNorm and
the recomputed forward are in the time). Bound: compute.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import moe_scopes, sparse_flops


def read(obs):
    busy = sparse_flops.seconds(obs, sparse_flops.PROJ_SCOPES)
    if not busy:
        return None
    return moe_scopes.percent_of_peak(
        obs, sparse_flops.proj_flops_per_step(
            obs["model"], sparse_flops.chip_tokens(obs)), busy)
