"""Device time of the jit_prefill_chunk program runs in the trace over
the thousands of prompt tokens the engine computed (not served from the
prefix cache) while the trace ran.
source: device_trace and program_counter (prefill_tokens_computed)."""
PROGRAM = "jit_prefill_chunk"


def read(obs):
    tr, c = obs.get("trace"), obs.get("trace_counters")
    if not tr or not c or PROGRAM not in tr.get("modules", {}):
        return None
    toks = c.get("prefill_tokens_computed", 0)
    if not toks:
        return None
    return 1e3 * tr["modules"][PROGRAM]["device_s"] / (toks / 1e3)
