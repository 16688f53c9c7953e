"""What the train loop's pulse saw, read from the program's own record of
it: the ``rtpu.train.loop`` span and the ``rtpu.train.stall`` and
``rtpu.proc.pause`` events that ``ray_tpu/train/pulse.py`` leaves in
``trace_spans.json`` (``lib/program_spans.py`` says where that file is),
in a traced and in an untraced run alike.

The events read are those of the worker that owns the chips (the process
with the longest ``rtpu.backend.devices``, as ``lib/compile_spans.py``
takes it). The two shares count the events that *start* in the timed
window, which opens at the start of ``rtpu.init`` plus ``obs["setup_s"]``
(``lib/compile_spans.py`` says why that is the seam) and lasts
``obs["train"]["window_s"]``. A program without the pulse (the parent of
PR 48) leaves no ``rtpu.train.loop``: every reader then gives nothing and
the line leaves the metric out. A run with the span and without a stall
or a pause in its window reads 0.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from benchmark.lib import program_spans

LOOP, STALL, PAUSE = "rtpu.train.loop", "rtpu.train.stall", "rtpu.proc.pause"


def owner_events(obs: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The pulse's events of the chips' owner, its ``rtpu.train.loop``
    first; nothing where the run left none."""
    found = program_spans.gang_start(obs)
    if not found:
        return None
    events, start = found
    loops = [e for e in events if e["name"] == LOOP]
    if not loops:
        return None
    opened = [e for e in events if e["name"] == "rtpu.backend.devices"
              and start[0] <= e["ts"] / 1e6 <= start[1]]
    # a gang rebuilt after a failure ran a shorter loop; a CPU rehearsal
    # opens no chip, and the longest loop is read
    pids = {max(opened, key=lambda e: e["dur"])["pid"]} if opened \
        else {e["pid"] for e in loops}
    mine = [e for e in loops if e["pid"] in pids]
    if not mine:
        return None
    loop = max(mine, key=lambda e: e["dur"])
    return [loop] + [e for e in events if e["pid"] == loop["pid"]
                     and e["name"] in (STALL, PAUSE)]


def loop_value(obs: Dict[str, Any], key: str) -> Optional[float]:
    """An attribute of the owner's ``rtpu.train.loop``."""
    mine = owner_events(obs)
    return mine[0]["args"].get(key) if mine else None


def window(obs: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """The timed window in wall-clock seconds."""
    events = program_spans.load(obs)
    inits = program_spans.intervals(events, "rtpu.init") if events else []
    length = (obs.get("train") or {}).get("window_s")
    if not inits or obs.get("setup_s") is None or length is None:
        return None
    opened = inits[0][0] + obs["setup_s"]
    return opened, opened + length


def window_share(obs: Dict[str, Any], name: str,
                 ms_of: Callable[[Dict[str, Any]], float],
                 at_main_place: bool = False) -> Optional[float]:
    """``ms_of`` summed over the owner's events called ``name`` that start
    in the window, in per cent of the window; ``at_main_place`` leaves out
    the events of another place than the loop span's (the seam lies a few
    tenths of a second inside the window, so the window read ends that
    much late, where the check's compile is a long wait of its own
    place)."""
    mine, span = owner_events(obs), window(obs)
    if not mine or not span:
        return None
    place = mine[0]["args"].get("place")
    inside = sum(ms_of(e["args"]) for e in mine[1:] if e["name"] == name
                 and span[0] <= e["ts"] / 1e6 < span[1]
                 and (not at_main_place or e["args"].get("place") == place))
    return 100.0 * inside / (1e3 * (span[1] - span[0]))
