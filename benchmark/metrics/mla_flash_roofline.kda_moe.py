"""``mla_flash_roofline`` for the one latent layer of a KDA stack (keys of
192, values of 128, 32 heads, no query latent): the larger of the needed
FLOPs (the forward's two products and the backward's five over the causal
pairs) at the peak bf16 FLOP/s and the least bytes (q, k, v, o, dO and the
three gradients once each, the shared rope dims once a position) at the
HBM bandwidth, divided by the device time per step of the calls named
``flash_kv_fwd``, ``flash_kv_bwd_dq`` and ``flash_kv_bwd_dkv``; the
recomputed forward's calls are in the time, their FLOPs are not. Bound:
compute at these sizes.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import kda_moe_flops as lib
from benchmark.lib import scopes


def read(obs):
    t = obs.get("train")
    if not t or not t["traced_steps"] or not lib.is_kda_moe_model(obs):
        return None
    names = lib.FLASH_KERNELS
    got = [v for k, v in (scopes.for_obs(obs) or {}).get(
        "kernel_s", {}).items()
        if k in names or k.strip("_").endswith(tuple("_" + n for n in names))]
    if not got:
        return None
    tf = obs["traffic"]
    return lib.percent_of_floor(
        obs, lib.flash_flops_per_step(obs["model"], tf["batch"] / t["chips"],
                                      tf["seq"]),
        lib.flash_bytes_per_step(obs["model"], lib.chip_tokens(obs)),
        sum(got))
