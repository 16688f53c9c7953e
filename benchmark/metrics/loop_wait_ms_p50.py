"""The usual wait for a step, from inside the chips' owner: the median
length of the waits at the place its loop usually waits at
(``rtpu.train.loop``'s ``wait_ms_p50``, of the last 64 waits there; the
place is the span's ``place``, the loop's ``block_until_ready`` line).
Within a tick (10 ms) of the trace's busy time a step.
source: program_span (trace_spans.json, lib/loop_spans.py)."""
from benchmark.lib import loop_spans


def read(obs):
    return loop_spans.loop_value(obs, "wait_ms_p50")
