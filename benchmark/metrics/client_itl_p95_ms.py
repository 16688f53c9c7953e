"""95th percentile of the gap between successive deliveries of one
stream; one sample per delivery after a request's first, over all
requests, the sample belonging to the window by the later stamp.
source: host_clock (client stamps)."""
from benchmark.lib.window import percentile


def read(obs):
    c = obs.get("client")
    if not c or not c["itl_s"]:
        return None
    return 1e3 * percentile(c["itl_s"], 95)
