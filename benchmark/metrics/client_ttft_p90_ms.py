"""90th percentile of client-side time to first token; with 3 of 4 asks
hitting the prefix cache this is the middle of the miss path.
source: host_clock (client stamps)."""
from benchmark.lib.window import percentile


def read(obs):
    c = obs.get("client")
    if not c or not c["ttft_s"]:
        return None
    return 1e3 * percentile(c["ttft_s"], 90)
