"""``unscoped_device_share`` for a program with an index: the share of the
device's busy time in operations that carry none of the model's scopes,
the index's and the gate's among them: the optimizer update, gradient
casts and accumulation, the embedding's scatter-add, the walk's own
bookkeeping, whatever the cell's own step function adds.
source: device_trace (lib/sparse_flops.py's reduction)."""
from benchmark.lib import sparse_flops


def read(obs):
    if not sparse_flops.is_sparse_model(obs):
        return None
    r = sparse_flops.for_obs(obs)
    # nothing for a program without the index's scopes
    if not r or not r["busy_s"] or "dsa_scores" not in r["scope_self_s"]:
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
