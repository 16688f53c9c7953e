"""``unscoped_device_share`` for a program with latent attention: the
share of the device's busy time in operations that carry none of the
model's scopes, latent attention's among them (``lib/scopes.py`` does not
know ``mla_q`` and would count the projections as unscoped): the optimizer
update, gradient casts and accumulation, the embedding's scatter-add,
whatever the cell's own step function adds.
source: device_trace (lib/latent_flops.py's reduction)."""
from benchmark.lib import latent_flops


def read(obs):
    r = latent_flops.for_obs(obs)
    # nothing for a program without latent attention's scopes
    if not r or not r["busy_s"] or "mla_q" not in r["scope_self_s"]:
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
