"""Wall time of a traced step minus the time a device operation ran in
it (mean over chips): what the host and the hand-over add to a step.
source: device_trace."""


def read(obs):
    tr, t = obs.get("trace"), obs.get("train")
    if not tr or not t or not t["traced_steps"]:
        return None
    return 1e3 * (tr["window_s"] - tr["busy_s"]) / t["traced_steps"]
