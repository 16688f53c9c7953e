"""Opening the chips: the first ``jax.devices()`` in the worker that
owns them (span ``rtpu.backend.devices``), the longest over the gang's
workers. Nothing where no worker opened a chip (a CPU rehearsal).
source: program_span (trace_spans.json, lib/program_spans.py)."""
from benchmark.lib import program_spans


def read(obs):
    found = program_spans.gang_start(obs)
    if not found:
        return None
    return program_spans.longest_inside(
        found[0], "rtpu.backend.devices", found[1]) or None
