"""``unscoped_device_share`` for a program with delta-rule layers: the
share of the device's busy time in operations that carry none of the
model's scopes, the rule's among them (``lib/scopes.py`` does not know
``gdn_rule`` and would count most of this step as unscoped): the optimizer
update, gradient casts and accumulation, the embedding's scatter-add,
whatever the cell's own step function adds.
source: device_trace (lib/delta_flops.py's reduction)."""
from benchmark.lib import delta_flops


def read(obs):
    r = delta_flops.for_obs(obs)
    # nothing for a program without the rule's scopes
    if not r or not r["busy_s"] or "gdn_rule" not in r["scope_self_s"]:
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
