"""Device time of a routed-experts train step by the program's own
names: ``lib/scopes.py``'s reduction with the four scopes that
``ray_tpu/ops/moe.py`` nests under ``mlp`` added to the names it knows
(``moe_route``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``).

``lib/scopes.py`` knows a fixed tuple of names and sends a routed layer's
operations to ``mlp``, which is what the readers of the dense cells want;
here an operation goes to the innermost scope of the longer tuple, so
``mlp`` keeps only the norm before the router. Same plane reader, same
self-time rule, so the scopes again add up to the device's busy time.
Cached beside the trace as ``moe_scopes.json``; a program without these
scopes gives a reduction without them, and the readers return nothing.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import peaks, scopes, trace

MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(scopes.MODEL_SCOPES + MOE_SCOPES)
    + r")(?![A-Za-z0-9_.])")


def scope_of(path: str) -> str:
    found = _SCOPE_RE.findall(path.split(";", 1)[0])
    return found[-1] if found else "unscoped"


def reduce_scopes(xplane_path: str) -> Dict[str, Any]:
    planes = scopes.read_planes(xplane_path)
    planes.pop("/host:CPU", None)
    chips = []
    for name in sorted(planes):
        ops = [ev for ln in planes[name]["lines"] if ln["name"] == "XLA Ops"
               for ev in ln["events"]]
        if ops:
            chips.append((planes[name], ops))
    by_scope: Dict[str, float] = {}
    busy_ns = 0.0
    for p, ops in chips:
        events = [(s, e, str(mid)) for mid, s, e in ops]
        for _s, _e, mid, self_ns, _leaf in trace._self_times(events):
            sc = scope_of(p["paths"].get(int(mid), ""))
            by_scope[sc] = by_scope.get(sc, 0.0) + self_ns / 1e9 / len(chips)
        busy_ns += trace.total(trace.union(
            [(s, e) for _, s, e in ops])) / len(chips)
    return {"chips": len(chips), "busy_s": busy_ns / 1e9,
            "scope_self_s": by_scope}


def for_obs(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if not obs.get("trace") or "cell" not in obs:
        return None
    d = scopes.trace_dir_of(obs)
    cached = os.path.join(d, "moe_scopes.json")
    if os.path.exists(cached):
        with open(cached) as f:
            return json.load(f)
    try:
        reduced = reduce_scopes(trace.find_xplane(d))
    except FileNotFoundError:
        return None
    with open(cached, "w") as f:
        json.dump(reduced, f)
    return reduced


def seconds(obs: Dict[str, Any], names: Tuple[str, ...]) -> Optional[float]:
    """Device seconds of the traced window (mean over chips) under all of
    ``names``; nothing where the program lacks one of them."""
    r = for_obs(obs)
    if not r or any(n not in r["scope_self_s"] for n in names):
        return None
    return sum(r["scope_self_s"][n] for n in names)


def kernel_seconds(obs: Dict[str, Any], prefixes: Tuple[str, ...]
                   ) -> Optional[float]:
    """Device seconds of the traced window in the Mosaic calls whose HLO
    name starts with one of ``prefixes`` (``lib/scopes.py``'s
    ``kernel_s``); nothing where there is no such call."""
    r = scopes.for_obs(obs)
    got = [v for k, v in (r or {}).get("kernel_s", {}).items()
           if k.startswith(prefixes)]
    return sum(got) if got else None


def percent_of_peak(obs: Dict[str, Any], flops_per_step: float,
                    busy_s: Optional[float]) -> Optional[float]:
    """``flops_per_step`` of one chip at the chip's peak bf16 FLOP/s, as a
    share of ``busy_s`` device seconds of the traced window per step."""
    if not busy_s:
        return None
    floor_s = flops_per_step / peaks.peaks(
        obs["device"]["device_kind"])["bf16_flops"]
    return 100.0 * floor_s / (busy_s / obs["train"]["traced_steps"])
