"""``unscoped_device_share`` for a program with convolution layers: the
share of the device's busy time in operations that carry none of the
model's scopes, the convolution's among them (``lib/scopes.py`` does not
know ``short_conv`` and would count a quarter of this step as unscoped):
the optimizer update, gradient casts and accumulation, the embedding's
scatter-add, whatever the cell's own step function adds.
source: device_trace (lib/hybrid_flops.py's reduction)."""
from benchmark.lib import hybrid_flops


def read(obs):
    r = hybrid_flops.for_obs(obs)
    # nothing for a program without the convolution's scopes
    if not r or not r["busy_s"] or "conv_mix" not in r["scope_self_s"]:
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
