"""Tracing and lowering before the window: the union of the
``rtpu.jax.trace`` and ``rtpu.jax.lower`` intervals in the worker that
owns the chips, from the train function's entry to the window's
opening. The models' and kernels' Python and the lowering of each
Pallas call to Mosaic, which no cache saves.
source: program_span (trace_spans.json, lib/compile_spans.py)."""
from benchmark.lib import compile_spans


def read(obs):
    return compile_spans.seconds(obs, compile_spans.TRACE, compile_spans.LOWER)
