"""How much of the window the chips' owner did not run: ``late_ms`` of
the ``rtpu.proc.pause`` events (a 10 ms tick that came 100 ms or more
late) that start in the window, over the window. 0 in a clean run.
source: program_span (trace_spans.json, lib/loop_spans.py)."""
from benchmark.lib import loop_spans


def read(obs):
    return loop_spans.window_share(
        obs, loop_spans.PAUSE, lambda a: a["late_ms"])
