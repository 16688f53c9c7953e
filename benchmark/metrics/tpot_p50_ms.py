"""Median over requests finishing inside the window of
(t_last - t_first) / (tokens after the first delivery).
source: host_clock (client stamps)."""
from benchmark.lib.window import percentile


def read(obs):
    c = obs.get("client")
    if not c or not c["tpot_s"]:
        return None
    return 1e3 * percentile(c["tpot_s"], 50)
