"""Bringing up the train gang: ``JaxTrainer.fit`` entered until the
train function has been entered on every worker (span
``rtpu.train.start``: placement group, worker processes, the backend's
start-up, importing jax), minus opening the chips (the longest
``rtpu.backend.devices`` inside it), which ``setup_chip_open_s`` holds.
source: program_span (trace_spans.json, lib/program_spans.py)."""
from benchmark.lib import program_spans


def read(obs):
    found = program_spans.gang_start(obs)
    if not found:
        return None
    events, start = found
    return (start[1] - start[0]) - program_spans.longest_inside(
        events, "rtpu.backend.devices", start)
