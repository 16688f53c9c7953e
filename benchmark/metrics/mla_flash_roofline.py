"""The flash kernels of latent attention (keys of 192, values of 128)
against their roofline: the larger of the needed FLOPs (the forward's two
products and the backward's five over the causal pairs, every layer, at
the held heads: 3.6 times the forward) at the peak bf16 FLOP/s and the
least bytes (q, k, v, o, dO and the three gradients once each, the shared
rope dims once a position) at the HBM bandwidth, divided by the device
time per step of the calls named ``flash_kv_fwd``, ``flash_kv_bwd_dq`` and
``flash_kv_bwd_dkv``. The same count whatever implements the kernels: no
padding and no broadcast is counted; the recomputed forward's calls are in
the time, their FLOPs are not. Bound: compute at these sizes.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import latent_flops, scopes


def read(obs):
    t = obs.get("train")
    if (not t or not t["traced_steps"]
            or not latent_flops.is_latent_model(obs)):
        return None
    names = latent_flops.FLASH_KERNELS
    got = [v for k, v in (scopes.for_obs(obs) or {}).get(
        "kernel_s", {}).items()
        if k in names or k.strip("_").endswith(tuple("_" + n for n in names))]
    if not got:
        return None
    tf = obs["traffic"]
    return latent_flops.percent_of_floor(
        obs, latent_flops.flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        latent_flops.flash_bytes_per_step(
            obs["model"], latent_flops.chip_tokens(obs)), sum(got))
