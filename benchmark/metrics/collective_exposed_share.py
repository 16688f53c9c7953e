"""Share of the traced window in which a collective ran on a chip and
no compute operation did (mean over chips).
source: device_trace."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
