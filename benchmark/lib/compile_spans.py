"""What jax did between the gang's start and the window's opening, read
from the program's own record of it: the ``rtpu.jax.trace``,
``rtpu.jax.lower`` and ``rtpu.jax.compile`` events that
``ray_tpu.util.tracing.watch_jax`` leaves in ``trace_spans.json``
(``lib/program_spans.py`` says where that file is).

The stretch read starts where the first ``rtpu.train.start`` ends (the
train function has been entered) and ends where the window opens. The
cell's loop knows that moment and the file does not: it is taken as the
start of ``rtpu.init`` plus ``obs["setup_s"]``, which ``run.py`` counts
from its own first line, a few tenths of a second before ``init``. The
last compile before the window ends warm-up steps earlier and the
check's compile comes a window later, so the seam has seconds of slack
on both sides.

Times are unions of intervals, not sums: an inner jit's trace lies
inside the outer's. A program without these events (the parent of
PR 34) gives nothing, and the line leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import program_spans

TRACE, LOWER, COMPILE = "rtpu.jax.trace", "rtpu.jax.lower", "rtpu.jax.compile"


def union_seconds(spans: List[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


def setup_events(obs: Dict[str, Any]
                 ) -> Optional[Tuple[List[Dict[str, Any]], float, float]]:
    """The jax events of the worker that owns the chips and the stretch
    ``(from, to)`` in wall-clock seconds; nothing where the run left no
    such events or no ``setup_s``."""
    found = program_spans.gang_start(obs)
    if not found or obs.get("setup_s") is None:
        return None
    events, start = found
    inits = program_spans.intervals(events, "rtpu.init")
    jax_events = [e for e in events if e["name"] in (TRACE, LOWER, COMPILE)]
    if not inits or not jax_events:
        return None
    # the chips' owner ran the longest ``rtpu.backend.devices``; a CPU
    # rehearsal opens no chip, and every worker's events are read
    opened = [e for e in events if e["name"] == "rtpu.backend.devices"
              and start[0] <= e["ts"] / 1e6 <= start[1]]
    if opened:
        owner = max(opened, key=lambda e: e["dur"])["pid"]
        jax_events = [e for e in jax_events if e["pid"] == owner]
    return jax_events, start[1], inits[0][0] + obs["setup_s"]


def _inside(events: List[Dict[str, Any]], names: Tuple[str, ...],
            t_from: float, t_to: float) -> List[Tuple[float, float]]:
    """The events' intervals cut to the stretch."""
    out = []
    for e in events:
        s, t = e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6
        if e["name"] in names and t > t_from and s < t_to:
            out.append((max(s, t_from), min(t, t_to)))
    return out


def seconds(obs: Dict[str, Any], *names: str) -> Optional[float]:
    found = setup_events(obs)
    if not found:
        return None
    return union_seconds(_inside(found[0], names, *found[1:]))


def programs_compiled(obs: Dict[str, Any]) -> Optional[int]:
    """Backend compiles that started in the stretch and that the
    persistent cache did not serve."""
    found = setup_events(obs)
    if not found:
        return None
    events, t_from, t_to = found
    return sum(1 for e in events if e["name"] == COMPILE
               and t_from <= e["ts"] / 1e6 < t_to
               and e["args"].get("cache") != "hit")
