"""Output tokens delivered inside the window (by arrival stamp, whether
or not their request has finished) over the window's length.
source: host_clock (client stamps)."""


def read(obs):
    c = obs.get("client")
    return c["out_tokens"] / c["window_s"] if c else None
