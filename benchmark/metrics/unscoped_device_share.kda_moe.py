"""``unscoped_device_share`` for a program with KDA layers, a latent layer
and a routed mixture: the share of the device's busy time in operations
that carry none of the model's scopes, the rule's, the latent layer's and
the mixture's among them: the optimizer update, the bias's move, gradient
casts and accumulation, the embedding's scatter-add, whatever the cell's
own step function adds.
source: device_trace (lib/kda_moe_flops.py's reduction)."""
from benchmark.lib import kda_moe_flops as lib


def read(obs):
    if not lib.is_kda_moe_model(obs):
        return None
    r = lib.for_obs(obs)
    # nothing for a program without the rule's and the shared expert's scopes
    if (not r or not r["busy_s"] or "kda_rule" not in r["scope_self_s"]
            or "moe_shared" not in r["scope_self_s"]):
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
