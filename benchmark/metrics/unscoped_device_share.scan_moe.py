"""``unscoped_device_share`` for a program of one-part layers with a
prediction module: the share of the device's busy time in operations that
carry none of the model's scopes, the scan's, the mixture's, the latent's
and the module's among them: the optimizer update, gradient casts and
accumulation, the embedding's scatter-add, whatever the cell's own step
function adds.
source: device_trace (lib/scan_moe_flops.py's reduction)."""
from benchmark.lib import scan_moe_flops as lib


def read(obs):
    if not lib.is_scan_moe_model(obs):
        return None
    r = lib.for_obs(obs)
    # nothing for a program without the scan's and the latent's scopes
    if (not r or not r["busy_s"] or "ssm_scan" not in r["scope_self_s"]
            or "moe_latent" not in r["scope_self_s"]):
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
