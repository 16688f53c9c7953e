"""The program's own spans of a training run, read from the file
``JaxTrainer.fit`` leaves in the run's storage directory
(``<storage_path>/<name>/trace_spans.json``: the chrome-trace list of
``ray_tpu.util.tracing``, the driver's and every worker's, on the wall
clock, ``ts`` and ``dur`` in microseconds).

The cell passes ``storage_path=.bench_tmp/train-<cell>`` and the cell's
name as the run's name, and empties the directory before each run, so
the file found is this run's. A program without spans leaves no file,
and every reader here then returns nothing.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import spec


def spans_file(obs: Dict[str, Any]) -> str:
    name = obs["cell"]["name"]
    return os.path.join(spec.ROOT, ".bench_tmp", "train-" + name, name,
                        "trace_spans.json")


def load(obs: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    if "cell" not in obs:
        return None
    try:
        with open(spans_file(obs)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def intervals(events: List[Dict[str, Any]], name: str
              ) -> List[Tuple[float, float]]:
    """(start, end) in wall-clock seconds of every span called ``name``,
    in order of start."""
    return sorted((e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
                  for e in events if e["name"] == name)


def gang_start(obs: Dict[str, Any]
               ) -> Optional[Tuple[List[Dict[str, Any]], Tuple[float, float]]]:
    """The run's events and its first ``rtpu.train.start`` (a later one
    is a gang rebuilt after a failure, not the set-up)."""
    events = load(obs)
    starts = intervals(events, "rtpu.train.start") if events else []
    return (events, starts[0]) if starts else None


def first_seconds(obs: Dict[str, Any], name: str) -> Optional[float]:
    """Duration of the run's first span called ``name``."""
    events = load(obs)
    got = intervals(events, name) if events else []
    return got[0][1] - got[0][0] if got else None


def longest_inside(events: List[Dict[str, Any]], name: str,
                   outer: Tuple[float, float]) -> float:
    """The longest span called ``name`` that lies inside ``outer`` (the
    workers of a gang run it side by side); 0 where there is none."""
    return max((e - s for s, e in intervals(events, name)
                if s >= outer[0] and e <= outer[1]), default=0.0)
