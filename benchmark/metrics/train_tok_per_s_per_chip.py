"""Tokens of the global batch per second per chip over the window of
whole steps, every step ending in block_until_ready.
source: host_clock (in the worker that owns the chips)."""


def read(obs):
    t = obs.get("train")
    if not t:
        return None
    return t["steps"] * t["tokens_per_step"] / t["window_s"] / t["chips"]
