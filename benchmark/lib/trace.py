"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU
trace holds (looked at by hand, PR 23, jax 0.9.0, v5e):

- one plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules``
  (one event per program run, named ``jit_<fn>(<hash>)``) and ``XLA Ops``
  (one event per HLO operation run, named by its whole HLO text; an
  operation inside a ``while`` body lies inside the ``while``'s event, so
  events nest);
- the plane ``/host:CPU`` with one line per host thread; a
  ``jax.profiler.TraceAnnotation`` is an event there under its own name.

All planes share one clock. Device busy time is the union of the ``XLA
Ops`` intervals; the traced window is the span from the first device
operation's start to the last one's end. A Mosaic (Pallas) kernel is an
operation whose text says ``custom_call_target="tpu_custom_call"``; a
collective is one whose HLO kind is in ``COLLECTIVE_KINDS``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast")
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
HOST_SPAN_PREFIX = "bench."
# a gap shorter than this between two device operations is the device's
# own turn-around, not the host keeping it waiting
MIN_GAP_NS = 20_000

_KIND_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_short_name(hlo_text: str) -> str:
    """``%while.42 = (...) while(...)`` -> ``while.42(while)``."""
    head, _, rest = hlo_text.partition(" = ")
    m = _KIND_RE.search(" " + rest) if rest else None
    kind = m.group(1) if m else "?"
    return f"{head.lstrip('%')}({kind})"


def op_kind(hlo_text: str) -> str:
    _, _, rest = hlo_text.partition(" = ")
    m = _KIND_RE.search(" " + rest) if rest else None
    return m.group(1) if m else "?"


def module_name(event_name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def union(intervals: List[_Interval]) -> List[_Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: List[_Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[_Interval], b: List[_Interval]) -> List[_Interval]:
    """The parts of union ``a`` not covered by union ``b``."""
    out: List[_Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _self_times(events: List[Tuple[float, float, str]]
                ) -> List[Tuple[float, float, str, float, bool]]:
    """(start, end, name, self_ns, is_leaf) for nested events."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    child = [0.0] * len(events)
    has_child = [False] * len(events)
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            child[stack[-1]] += e - s
            has_child[stack[-1]] = True
        stack.append(i)
    return [(events[i][0], events[i][1], events[i][2],
             events[i][1] - events[i][0] - child[i], not has_child[i])
            for i in range(len(events))]


def reduce_trace(xplane_path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    chips: List[Dict[str, Any]] = []
    host_spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods: List[Tuple[float, float, str]] = []
            ops: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             module_name(ev.name)) for ev in line.events]
                elif line.name == "XLA Ops":
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
            chips.append({"name": plane.name, "modules": mods, "ops": ops})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    chips = [c for c in chips if c["ops"]]
    if not chips:
        return {"chips": 0, "window_s": 0.0, "busy_s": 0.0, "op_self_s": {}}
    t0 = min(min(o[0] for o in c["ops"]) for c in chips)
    t1 = max(max(o[1] for o in c["ops"]) for c in chips)
    n = len(chips)

    modules: Dict[str, Dict[str, float]] = {}
    op_self: Dict[str, float] = {}
    mosaic: Dict[str, Dict[str, float]] = {}
    busy_ns = coll_ns = exposed_ns = 0.0
    gaps: Dict[str, float] = {}
    host_spans.sort()
    for c in chips:
        mods = sorted(c["modules"])
        for s, e, name in mods:
            d = modules.setdefault(name, {"count": 0, "device_s": 0.0})
            d["count"] += 1 / n
            d["device_s"] += (e - s) / 1e9 / n
        starts = [m[0] for m in mods]

        def owner(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < mods[i][1]:
                return mods[i][2]
            return "_no_module_"

        busy = union([(s, e) for s, e, _ in c["ops"]])
        busy_ns += total(busy) / n
        coll: List[_Interval] = []
        compute: List[_Interval] = []
        for s, e, text, self_ns, leaf in _self_times(c["ops"]):
            prog = owner(s)
            key = f"{prog}:{op_short_name(text)}"
            op_self[key] = op_self.get(key, 0.0) + self_ns / 1e9 / n
            kind = op_kind(text)
            base = kind[:-6] if kind.endswith("-start") else (
                kind[:-5] if kind.endswith("-done") else kind)
            if base in COLLECTIVE_KINDS:
                coll.append((s, e))
            elif leaf:
                compute.append((s, e))
            if MOSAIC_MARK in text:
                d = mosaic.setdefault(prog, {"count": 0, "device_s": 0.0})
                d["count"] += 1 / n
                d["device_s"] += (e - s) / 1e9 / n
        coll_u = union(coll)
        coll_ns += total(coll_u) / n
        exposed_ns += total(subtract(coll_u, union(compute))) / n
        # idle gaps of this chip, named by the host span over their middle
        edges = [(t0, t0)] + busy + [(t1, t1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a < MIN_GAP_NS:
                continue
            mid = (a + b) / 2
            name = "_no_span_"
            for s, e, span in host_spans:
                if s <= mid < e:
                    name = span
                if s > mid:
                    break
            gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9 / n
    return {
        "chips": n,
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "modules": modules,
        "mosaic": mosaic,
        "op_self_s": op_self,
        "collective_s": coll_ns / 1e9,
        "collective_exposed_s": exposed_ns / 1e9,
        "idle_gaps_s": gaps,
    }


def breakdown(reduced: Dict[str, Any], top: int = 10) -> Dict[str, list]:
    ops = sorted(reduced.get("op_self_s", {}).items(),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced.get("idle_gaps_s", {}).items(),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def idle_share_percent(obs: Dict[str, Any]) -> Optional[float]:
    """The reader behind the device_idle_share.* metrics."""
    tr = obs.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def main() -> None:
    import json
    import sys

    reduced = reduce_trace(find_xplane(sys.argv[1]))
    reduced["breakdown"] = breakdown(reduced)
    del reduced["op_self_s"]
    print(json.dumps(reduced))


if __name__ == "__main__":
    main()
