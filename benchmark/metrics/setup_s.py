"""Process start to window open: runtime start, worker spawn, jax
start-up, weights, every compile (or cache load), priming, lead-in.
source: host_clock."""


def read(obs):
    return obs.get("setup_s")
