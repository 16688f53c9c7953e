"""Share of the device's busy time spent in operations that carry no
model scope: the optimizer update, gradient casts and accumulation, the
embedding's scatter-add, whatever the cell's own step function adds.
Nothing where the program names no scope at all.
source: device_trace (lib/scopes.py)."""
from benchmark.lib import scopes


def read(obs):
    r = scopes.for_obs(obs)
    if not r or not r["busy_s"] or set(r["scope_self_s"]) <= {"unscoped"}:
        return None
    return 100.0 * r["scope_self_s"].get("unscoped", 0.0) / r["busy_s"]
