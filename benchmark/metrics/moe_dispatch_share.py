"""Share of the device's busy time under the scopes ``moe_route`` (router
matmul, softmax, top-k, sort), ``moe_dispatch`` (gather into expert
order) and ``moe_combine`` (gate weighting, gather back, sum): what a
dropless routed layer costs beside its matmuls, memory- and
latency-bound work.
source: device_trace (lib/moe_scopes.py)."""
from benchmark.lib import moe_scopes


def read(obs):
    r = moe_scopes.for_obs(obs)
    around = moe_scopes.seconds(obs, ("moe_route", "moe_dispatch",
                                      "moe_combine"))
    if around is None or not r["busy_s"]:
        return None
    return 100.0 * around / r["busy_s"]
