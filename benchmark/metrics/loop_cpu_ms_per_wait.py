"""The loop thread's CPU time for a step: from the end of one wait at the
main place to the end of the next that began where it ended, the mean
over the session's such pairs (``rtpu.train.loop``'s ``cpu_ms_mean``).
What lay between a wait elsewhere and a wait here (the warm-up, the
profiler's stop, the check) is in no pair. It holds the step's host work,
as ``host_ms_per_step`` does, and what the thread's clock is charged
while it waits in ``block_until_ready``: on the chips' machines about
half a per cent of the wait, so the cells with steps of 1.6-2.2 s read
9-12 ms where those with steps under 0.4 s read 1-3 (PERF.md section 6,
PR 48). A mean and no median: those machines advance a thread's CPU
clock in steps of 10 ms, so a single reading is 0 or 10.
source: program_span (trace_spans.json, lib/loop_spans.py)."""
from benchmark.lib import loop_spans


def read(obs):
    return loop_spans.loop_value(obs, "cpu_ms_mean")
