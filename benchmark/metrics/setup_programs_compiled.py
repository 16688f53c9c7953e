"""Programs the backend compiled before the window because the
persistent cache had them not (``rtpu.jax.compile`` events whose
``cache`` is not ``"hit"``): 0 on a warm run, so a slow ``setup_s``
beside a count above 0 was a cold cache.
source: program_span (trace_spans.json, lib/compile_spans.py)."""
from benchmark.lib import compile_spans


def read(obs):
    return compile_spans.programs_compiled(obs)
