"""Counts compilations in this process through jax's monitoring events:
one fires for each program that the backend compiles and one for each
that is fetched from the persistent cache; neither fires for a hit of
the in-memory jit cache, which is all a warmed-up window may see."""

from __future__ import annotations

_STATE = {"installed": False, "count": 0}
_EVENTS = ("/jax/core/compile/backend_compile_duration",
           "/jax/compilation_cache/cache_retrieval_time_sec")


def install() -> None:
    if _STATE["installed"]:
        return
    from jax import monitoring

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event in _EVENTS:
            _STATE["count"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    _STATE["installed"] = True


def count() -> int:
    install()
    return _STATE["count"]
