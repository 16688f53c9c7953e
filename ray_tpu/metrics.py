"""Metrics: counters/gauges/histograms + Prometheus text exposition.

Reference: python/ray/util/metrics.py (Counter/Gauge/Histogram backed by
opencensus + the dashboard's /metrics endpoint). Here a process-local
registry renders the Prometheus text format, served by a stdlib HTTP
endpoint (start_metrics_server) — scrapeable by any Prometheus.

The program's own counters are not copied into this registry: the object
that owns them (the runtime, a serving engine, a train session)
registers its ``stats()`` as a *source*, read at scrape time, and the
span accumulators of ``ray_tpu.util.tracing`` are one more source.
"""

from __future__ import annotations

import bisect
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Sequence, Tuple


def _span_totals() -> Dict[str, float]:
    from ray_tpu.util import tracing

    out: Dict[str, float] = {}
    for name, acc in tracing.totals().items():
        key = name.replace(".", "_").replace("-", "_")
        if isinstance(acc, int):        # jax_cache_hits, jax_cache_misses
            out[key] = acc
            continue
        out[key + "_count"] = acc["count"]
        out[key + "_seconds_total"] = acc["total_ns"] / 1e9
    return out


class _Registry:
    def __init__(self):
        self._metrics: List["Metric"] = []
        self._sources: Dict[str, Callable[[], Dict]] = {
            "rtpu_span": _span_totals}
        self._lock = threading.Lock()

    def register(self, m: "Metric"):
        with self._lock:
            self._metrics.append(m)

    def register_source(self, prefix: str, read: Callable[[], Dict]):
        """``read()`` returns a ``stats()``-like dict; its numeric items
        are served as gauges ``<prefix>_<key>``, read at scrape time. A
        second source under one prefix replaces the first."""
        with self._lock:
            self._sources[prefix] = read

    def render(self) -> str:
        with self._lock:
            metrics, sources = list(self._metrics), dict(self._sources)
        lines = []
        for prefix, read in sources.items():
            try:
                items = read().items()
            except Exception:  # noqa: BLE001 — its owner is shutting down
                continue
            for k, v in items:
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    lines.append(f"# TYPE {prefix}_{k} gauge\n"
                                 f"{prefix}_{k} {v}\n")
        return "".join(m.render() for m in metrics) + "".join(lines)


REGISTRY = _Registry()


def _fmt_tags(tags: Dict[str, str]) -> str:
    if not tags:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(tags.items()))
    return "{" + inner + "}"


class Metric:
    kind = "untyped"

    def __init__(self, name: str, description: str = "",
                 tag_keys: Sequence[str] = ()):
        self.name = name
        self.description = description
        self._tag_keys = tuple(tag_keys)
        self._values: Dict[Tuple[str, ...], float] = {}
        self._lock = threading.Lock()
        REGISTRY.register(self)

    def _key(self, tags: Optional[Dict[str, str]]) -> Tuple[str, ...]:
        tags = tags or {}
        return tuple(str(tags.get(k, "")) for k in self._tag_keys)

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.description}",
                 f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            items = list(self._values.items())
        if not items and not self._tag_keys:
            items = [((), 0.0)]
        for key, v in items:
            tags = dict(zip(self._tag_keys, key))
            lines.append(f"{self.name}{_fmt_tags(tags)} {v}")
        return "\n".join(lines) + "\n"


class Counter(Metric):
    kind = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None):
        k = self._key(tags)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + value


class Gauge(Metric):
    kind = "gauge"

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        with self._lock:
            self._values[self._key(tags)] = float(value)


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Sequence[float] = (0.01, 0.1, 1, 10),
                 tag_keys: Sequence[str] = ()):
        super().__init__(name, description, tag_keys)
        self._bounds = sorted(boundaries)
        self._buckets: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}
        self._counts: Dict[Tuple[str, ...], int] = {}

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None):
        k = self._key(tags)
        with self._lock:
            b = self._buckets.setdefault(k, [0] * (len(self._bounds) + 1))
            b[bisect.bisect_left(self._bounds, value)] += 1
            self._sums[k] = self._sums.get(k, 0.0) + value
            self._counts[k] = self._counts.get(k, 0) + 1

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.description}",
                 f"# TYPE {self.name} histogram"]
        with self._lock:
            for k, buckets in self._buckets.items():
                tags = dict(zip(self._tag_keys, k))
                cum = 0
                for bound, n in zip(self._bounds, buckets):
                    cum += n
                    t = {**tags, "le": str(bound)}
                    lines.append(f"{self.name}_bucket{_fmt_tags(t)} {cum}")
                t = {**tags, "le": "+Inf"}
                lines.append(
                    f"{self.name}_bucket{_fmt_tags(t)} {self._counts[k]}")
                lines.append(f"{self.name}_sum{_fmt_tags(tags)} "
                             f"{self._sums[k]}")
                lines.append(f"{self.name}_count{_fmt_tags(tags)} "
                             f"{self._counts[k]}")
        return "\n".join(lines) + "\n"


class _MetricsHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_GET(self):
        body = REGISTRY.render().encode()
        # core runtime gauges refresh lazily on scrape
        self.send_response(200)
        self.send_header("Content-Type", "text/plain; version=0.0.4")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


_server = None


def start_metrics_server(host: str = "127.0.0.1", port: int = 0):
    """Expose REGISTRY at http://host:port/ (Prometheus text format)."""
    global _server
    if _server is None:
        _server = ThreadingHTTPServer((host, port), _MetricsHandler)
        threading.Thread(target=_server.serve_forever, daemon=True,
                         name="metrics-http").start()
    return _server.server_address


def stop_metrics_server():
    global _server
    if _server is not None:
        _server.shutdown()
        _server.server_close()  # release the listening socket now
        _server = None
