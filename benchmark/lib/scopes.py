"""Device time by the program's own names, read from a profiler trace.

``lib/trace.py`` reads a trace through ``jax.profiler.ProfileData``,
which shows an operation's HLO text and its time but not where in the
model it came from. That path (``jit(step)/transpose(jvp(mlp))/while/
body/closed_call/checkpoint/mlp/dot_general:``, written by the
``jax.named_scope`` calls in ``ray_tpu/models``) is the ``tf_op`` stat of
the operation's *event metadata* in the ``.xplane.pb``. This module reads
the protobuf itself: a reader of the seven messages used (XSpace, XPlane,
XLine, XEvent, XEventMetadata, XStatMetadata, XStat; tsl/profiler/
protobuf/xplane.proto), in plain Python, so it needs neither jax nor
tensorflow and runs in the cell's driver process.

- every ``XLA Ops`` event goes to the innermost model scope in its path,
  forward, backward and rematerialised copies together; what carries no
  model scope is ``unscoped`` (the optimizer update, casts, the cell's own
  step function). Time is self time by ``trace.py``'s nesting rule, so the
  scopes add up to the device's busy time.
- a host span (``jax.profiler.TraceAnnotation``; the program's are named
  ``rtpu.*``) lies on ``/host:CPU`` on the same clock; an idle gap of the
  device is named by the program span over its middle.

The result is cached beside the trace (``scopes.json``), once per run.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmark.lib import spec, trace

MODEL_SCOPES = ("embed", "attn_qkv", "flash", "attn_out", "mlp", "head_loss",
                "attn", "sample", "pool_copy")
PROGRAM_SPAN_PREFIX = "rtpu."
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])(" + "|".join(MODEL_SCOPES) + r")(?![A-Za-z0-9_.])")


# ---- the protobuf wire format, as far as xplane.proto uses it

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, start: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value): an int for varint and fixed
    fields, a (start, end) range of ``buf`` for a length-delimited one."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield key >> 3, wt, v


def _text(buf: bytes, rng: Tuple[int, int]) -> str:
    return buf[rng[0]:rng[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, rng: Tuple[int, int]) -> Tuple[int, Tuple[int, int]]:
    key, val = 0, (rng[1], rng[1])
    for f, _, v in _fields(buf, *rng):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_name(buf: bytes, rng: Tuple[int, int]) -> str:
    for f, _, v in _fields(buf, *rng):
        if f == 2:
            return _text(buf, v)
    return ""


def read_plane(buf: bytes, rng: Tuple[int, int]) -> Dict[str, Any]:
    """One XPlane: its lines' events as (metadata id, start ns, end ns)
    on the trace's clock, the events' names, and the ``tf_op`` path of
    those that have one."""
    stat_names: Dict[int, str] = {}
    meta_ranges: List[Tuple[int, Tuple[int, int]]] = []
    line_ranges: List[Tuple[int, int]] = []
    for f, _, v in _fields(buf, *rng):
        if f == 3:
            line_ranges.append(v)
        elif f == 4:
            meta_ranges.append(_map_entry(buf, v))
        elif f == 5:
            sid, sr = _map_entry(buf, v)
            for g, _, w in _fields(buf, *sr):
                if g == 2:
                    stat_names[sid] = _text(buf, w)
    tf_op_ids = {i for i, n in stat_names.items() if n == "tf_op"}
    names: Dict[int, str] = {}
    paths: Dict[int, str] = {}
    for mid, mr in meta_ranges:
        for f, _, v in _fields(buf, *mr):
            if f == 2:
                names[mid] = _text(buf, v)
            elif f == 5:                       # XStat of the metadata
                sid, value = 0, None
                for g, wt, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 5:
                        value = _text(buf, w)
                    elif g == 7:               # a reference to a name
                        value = stat_names.get(w, "")
                if sid in tf_op_ids and value:
                    paths[mid] = value
    lines = []
    for lr in line_ranges:
        name, t0_ns, events = "", 0, []
        for f, _, v in _fields(buf, *lr):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                mid = off = dur = 0
                for g, _, w in _fields(buf, *v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = w
                    elif g == 3:
                        dur = w
                events.append((mid, off, dur))
        lines.append({"name": name, "events": [
            (m, t0_ns + o / 1e3, t0_ns + (o + d) / 1e3)
            for m, o, d in events]})
    return {"lines": lines, "names": names, "paths": paths}


def read_planes(xplane_path: str) -> Dict[str, Dict[str, Any]]:
    """The device planes and ``/host:CPU`` of a trace, by name."""
    with open(xplane_path, "rb") as f:
        buf = f.read()
    out = {}
    for f_, _, rng in _fields(buf):
        if f_ != 1:
            continue
        name = _plane_name(buf, rng)
        if name.startswith("/device:TPU:") or name == "/host:CPU":
            out[name] = read_plane(buf, rng)
    return out


# ---- the reduction

def scope_of(path: str) -> str:
    """The innermost model scope in an operation's path, else
    ``unscoped``. A fused operation lists its parts' paths with ``;``
    between them: the first names it."""
    found = _SCOPE_RE.findall(path.split(";", 1)[0])
    return found[-1] if found else "unscoped"


def reduce_scopes(xplane_path: str) -> Dict[str, Any]:
    planes = read_planes(xplane_path)
    host = planes.pop("/host:CPU", None)
    spans: List[Tuple[float, float, str]] = []
    if host:
        for line in host["lines"]:
            for mid, s, e in line["events"]:
                name = host["names"].get(mid, "")
                if name.startswith(PROGRAM_SPAN_PREFIX):
                    spans.append((s, e, name))
    spans.sort()
    chips = []
    for name in sorted(planes):
        p = planes[name]
        ops = [ev for ln in p["lines"] if ln["name"] == "XLA Ops"
               for ev in ln["events"]]
        if ops:
            chips.append((p, ops))
    if not chips:
        return {"chips": 0, "busy_s": 0.0, "scope_self_s": {},
                "kernel_s": {}, "program_spans": sorted({s[2] for s in spans}),
                "idle_s": 0.0, "idle_unnamed_s": 0.0}
    n = len(chips)
    t0 = min(min(o[1] for o in ops) for _, ops in chips)
    t1 = max(max(o[2] for o in ops) for _, ops in chips)
    by_scope: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    busy_ns = idle_ns = unnamed_ns = 0.0
    for p, ops in chips:
        events = [(s, e, str(mid)) for mid, s, e in ops]
        for s, e, mid, self_ns, _leaf in trace._self_times(events):
            mid = int(mid)
            sc = scope_of(p["paths"].get(mid, ""))
            by_scope[sc] = by_scope.get(sc, 0.0) + self_ns / 1e9 / n
            text = p["names"].get(mid, "")
            if trace.MOSAIC_MARK in text:
                k = trace.op_short_name(text).split("(", 1)[0]
                k = re.sub(r"\.\d+$", "", k)
                kernels[k] = kernels.get(k, 0.0) + (e - s) / 1e9 / n
        busy = trace.union([(s, e) for _, s, e in ops])
        busy_ns += trace.total(busy) / n
        edges = [(t0, t0)] + busy + [(t1, t1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b - a < trace.MIN_GAP_NS:
                continue
            idle_ns += (b - a) / n
            mid_t = (a + b) / 2
            if not any(s <= mid_t < e for s, e, _ in spans):
                unnamed_ns += (b - a) / n
    return {"chips": n, "busy_s": busy_ns / 1e9, "scope_self_s": by_scope,
            "kernel_s": kernels,
            "program_spans": sorted({s[2] for s in spans}),
            "idle_s": idle_ns / 1e9, "idle_unnamed_s": unnamed_ns / 1e9}


def trace_dir_of(obs: Dict[str, Any]) -> str:
    """Where the cell runners put a cell's profiler trace."""
    return os.path.join(spec.ROOT, ".bench_tmp",
                        "trace-" + obs["cell"]["name"])


def for_obs(obs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace, or nothing where the run was
    not traced. Cached beside the trace; the cell runner removes the
    directory before every run."""
    if not obs.get("trace") or "cell" not in obs:
        return None
    d = trace_dir_of(obs)
    cached = os.path.join(d, "scopes.json")
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


def model_scope_seconds(obs: Dict[str, Any], scopes: Tuple[str, ...]
                        ) -> Optional[float]:
    """Device seconds of the traced window (mean over chips) under the
    given scopes; nothing where the program carries no such scope."""
    r = for_obs(obs)
    if not r:
        return None
    got = [r["scope_self_s"][s] for s in scopes if s in r["scope_self_s"]]
    return sum(got) if len(got) == len(scopes) else None
