"""``unscoped_device_share`` for a program with delta-rule layers and a
routed mixture in one kind: the share of the device's busy time in
operations that carry none of the model's scopes, the rule's, the
mixture's and the gates' among them: the optimizer update, gradient casts
and accumulation, the embedding's scatter-add, whatever the cell's own
step function adds.
source: device_trace (lib/delta_moe_flops.py's reduction)."""
from benchmark.lib import delta_moe_flops as lib


def read(obs):
    if not lib.is_delta_moe_model(obs):
        return None
    r = lib.for_obs(obs)
    # nothing for a program without the rule's and the shared gate's scopes
    if (not r or not r["busy_s"] or "gdn_rule" not in r["scope_self_s"]
            or "moe_shared" not in r["scope_self_s"]):
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
