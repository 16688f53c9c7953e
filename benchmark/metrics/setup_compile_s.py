"""The backend's compiles before the window: the union of the
``rtpu.jax.compile`` intervals over the same stretch as
``setup_trace_lower_s``. XLA's compile on a cold machine, the
persistent cache's fetch and load on a warm one.
source: program_span (trace_spans.json, lib/compile_spans.py)."""
from benchmark.lib import compile_spans


def read(obs):
    return compile_spans.seconds(obs, compile_spans.COMPILE)
