"""``mla_proj_roofline`` for a latent layer with no query latent and a
head-wise gate: forward and backward FLOPs of its projections (``W_q``
straight from the layer's input, ``W_kva``, ``W_kvb``, ``W_g``, ``W_o``)
for one chip's tokens of a step over the peak bf16 FLOP/s, divided by the
device time per step under the scopes ``mla_q``, ``mla_kv``, ``mla_rope``
and ``mla_out`` and the gate's ``attn_gate`` (the layer's norm, the
latent's norm and rope are in the time, and so is the recomputed forward).
Bound: compute.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    busy = lib.seconds(obs, lib.MLA_SCOPES, also=("attn_gate",))
    if not busy:
        return None
    m = obs["model"]
    return lib.percent_of_peak(
        obs, lib.count(m, "mla") * lib.mla_proj_params(m), busy)
