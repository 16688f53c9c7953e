"""Device time of the jit_paged_decode_chunk program runs in the trace
over the decode steps they made (one page-gather Mosaic call per layer
per step, so steps = Mosaic calls / layers).
source: device_trace."""
PROGRAM = "jit_paged_decode_chunk"


def read(obs):
    tr = obs.get("trace")
    if not tr or PROGRAM not in tr.get("modules", {}):
        return None
    calls = tr.get("mosaic", {}).get(PROGRAM, {}).get("count", 0)
    steps = calls / obs["model"]["num_hidden_layers"]
    if not steps:
        return None
    return 1e3 * tr["modules"][PROGRAM]["device_s"] / steps
