"""``unscoped_device_share`` for a program with an index over grouped keys
and a rope from the batch's positions: the share of the device's busy time
in operations that carry none of the model's scopes, the index's and
``mrope`` among them: the optimizer update, gradient casts and accumulation,
the embedding's scatter-add, the walk's own bookkeeping, whatever the cell's
own step function adds.
source: device_trace (lib/sparse_gqa_flops.py's reduction)."""
from benchmark.lib import sparse_gqa_flops as sg


def read(obs):
    if not sg.is_sparse_gqa_model(obs):
        return None
    r = sg.for_obs(obs)
    # nothing for a program without the index's scopes
    if not r or not r["busy_s"] or "dsa_scores" not in r["scope_self_s"]:
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
