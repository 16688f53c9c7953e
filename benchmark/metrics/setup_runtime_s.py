"""Starting the runtime: ``ray_tpu.init`` entered to returned (object
store, listener, the first workers), the span ``rtpu.init`` of the
driver process.
source: program_span (trace_spans.json, lib/program_spans.py)."""
from benchmark.lib import program_spans


def read(obs):
    return program_spans.first_seconds(obs, "rtpu.init")
