"""Median time from send to first delivery, client side, over requests
whose first delivery falls inside the window (per-layer: within a round
it sits on one step of a ladder of waits and now and then on the next).
source: host_clock (client stamps)."""
from benchmark.lib.window import percentile


def read(obs):
    c = obs.get("client")
    if not c or not c["ttft_s"]:
        return None
    return 1e3 * percentile(c["ttft_s"], 50)
