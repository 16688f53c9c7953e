"""The flash kernels of the latent window layers (keys of 192 + 64 shared,
values of 128, a band of ``sliding_window_size`` keys) against their
roofline: the larger of the needed FLOPs (the band's pairs, ``sum_t min(t +
1, window)``, times the held heads, ``8 d_k + 6 d_v`` a pair and head, the
three window layers) at the peak bf16 FLOP/s and the least bytes (q, k, v,
o, dO and the three gradients once each, the shared rope dims once a
position) at the HBM bandwidth, divided by the device time per step of the
calls named ``flash_kv_fwd``, ``flash_kv_bwd_dq`` and ``flash_kv_bwd_dkv``
(this program's full layers run none). The tiles a band's edge cuts are in
the time and not in the count. Bound: bytes at a band this narrow.
source: device_trace (lib/scopes.py's ``kernel_s``)."""
from benchmark.lib import scopes, sparse_flops


def read(obs):
    t = obs.get("train")
    if (not t or not t["traced_steps"]
            or not sparse_flops.is_sparse_model(obs)):
        return None
    names = sparse_flops.WINDOW_KERNELS
    got = [v for k, v in (scopes.for_obs(obs) or {}).get(
        "kernel_s", {}).items()
        if k in names or k.strip("_").endswith(tuple("_" + n for n in names))]
    if not got:
        return None
    tf = obs["traffic"]
    return sparse_flops.percent_of_floor(
        obs, sparse_flops.window_flash_flops_per_step(
            obs["model"], tf["batch"] / t["chips"], tf["seq"]),
        sparse_flops.flash_bytes_per_step(
            obs["model"], sparse_flops.chip_tokens(obs), "swa_"), sum(got))
