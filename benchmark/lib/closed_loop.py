"""The closed loop: ``users`` caller threads, each sending its next
request the moment its last one ended, every delivery stamped on arrival.
The load goes on until a delivery has arrived at or after the window's
close; requests still running then are abandoned (the router cancels
them).

A schedule is what a generator returns: ``users``; either ``per_user``
(a list of requests for each user) or ``shared`` (one list that the users
pull from in order); ``prime`` (requests sent and awaited before the load
starts, in order); ``lead_in_s``. A request is
``{"prompt", "max_new_tokens", "tag"}``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from benchmark.lib import window as W


class ScheduleExhausted(RuntimeError):
    """The schedule ran out of requests before the window closed."""


def _stream_one(handle, req: Dict[str, Any], rec: Dict[str, Any],
                stop: threading.Event) -> None:
    rec["sent"] = time.monotonic()
    stream = handle.stream(req["prompt"],
                           max_new_tokens=req["max_new_tokens"])
    try:
        for chunk in stream:
            rec["stamps"].append(time.monotonic())
            rec["counts"].append(len(chunk))
            rec["tokens"].extend(chunk)
            if stop.is_set():
                return
        rec["done"] = True
    finally:
        stream.close()


def _prime(handle, schedule, errors: List[str]) -> None:
    """What a long-running deployment already holds: sent and awaited
    before the load starts, four at a time, in order."""
    prime = schedule["prime"]
    if not prime:
        return
    lock = threading.Lock()
    pi = [0]

    def prime_loop() -> None:
        while True:
            with lock:
                i = pi[0]
                pi[0] += 1
            if i >= len(prime):
                return
            out = handle.remote(
                prime[i]["prompt"],
                max_new_tokens=prime[i]["max_new_tokens"]).result(timeout=600)
            if not isinstance(out, dict):
                errors.append(f"prime {i}: {out!r}")

    ts = [threading.Thread(target=prime_loop, daemon=True)
          for _ in range(min(schedule["users"], 4))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errors:
        raise RuntimeError(f"priming failed: {errors[:3]}")


class _Feed:
    """Hands out the schedule's requests (a user's own list, or one shared
    list taken in order) and keeps every request's record."""

    def __init__(self, schedule: Dict[str, Any]):
        self._schedule = schedule
        self.lock = threading.Lock()
        self._cursor = 0
        self.records: List[Dict[str, Any]] = []

    def next(self, user: int, k: int) -> Optional[Dict[str, Any]]:
        """User ``user``'s ``k``-th request with its fresh record, or
        None when the schedule has run out."""
        if self._schedule["per_user"] is not None:
            lst, i = self._schedule["per_user"][user], k
        else:
            lst = self._schedule["shared"]
            with self.lock:
                i = self._cursor
                self._cursor += 1
        if i >= len(lst):
            return None
        req = lst[i]
        rec = {"user": user, "tag": req["tag"], "sent": None, "stamps": [],
               "counts": [], "tokens": [], "done": False, "error": None,
               "req": req}
        with self.lock:
            self.records.append(rec)
        return rec


def drive(handle, schedule: Dict[str, Any], seconds: float,
               on_open: Callable[[float], None],
               on_tick: Callable[[float], None]) -> Dict[str, Any]:
    """Runs the load; returns the records and the window. ``on_open(t)``
    runs at the nominal opening; ``on_tick(t)`` about every 50 ms while
    the window is open (the cell starts and stops its trace from it)."""
    users = schedule["users"]
    stop = threading.Event()
    errors: List[str] = []
    feed = _Feed(schedule)

    def user_loop(user: int) -> None:
        k = 0
        while not stop.is_set():
            rec = feed.next(user, k)
            if rec is None:
                errors.append(f"user {user}: schedule exhausted")
                return
            k += 1
            try:
                _stream_one(handle, rec["req"], rec, stop)
            except Exception as e:  # noqa: BLE001 — a refused or errored
                rec["error"] = repr(e)         # request counts as failed

    _prime(handle, schedule, errors)
    threads = [threading.Thread(target=user_loop, args=(u,), daemon=True,
                                name=f"bench-user-{u}")
               for u in range(users)]
    t_load = time.monotonic()
    for t in threads:
        t.start()

    def first_stamps() -> List[float]:
        with feed.lock:
            firsts = {}
            for r in feed.records:
                if r["stamps"] and r["user"] not in firsts:
                    firsts[r["user"]] = r["stamps"][0]
        return list(firsts.values())

    try:
        while True:                       # every user's first token
            fs = first_stamps()
            if len(fs) == users:
                break
            if errors or time.monotonic() - t_load > 600:
                raise RuntimeError(
                    f"load did not start: {len(fs)}/{users} users have a "
                    f"first token after {time.monotonic() - t_load:.0f}s; "
                    f"{errors[:3]}")
            time.sleep(0.02)
        nominal_open = max(fs) + schedule["lead_in_s"]
        time.sleep(max(0.0, nominal_open - time.monotonic()))
        on_open(time.monotonic())
        nominal_close = nominal_open + seconds
        win = None
        while True:
            now = time.monotonic()
            on_tick(now)
            if errors:
                raise ScheduleExhausted("; ".join(errors[:3]))
            if now >= nominal_close:
                with feed.lock:
                    stamps = sorted(t for r in feed.records
                                    for t in r["stamps"])
                try:
                    win = W.aligned_window(stamps, nominal_open, seconds)
                    break
                except ValueError:
                    pass
                if now > nominal_close + 120:
                    raise RuntimeError("no delivery for 120 s after the "
                                       "window's nominal close")
            time.sleep(0.05)
    finally:
        stop.set()
    for t in threads:
        t.join(timeout=120)
    alive = [t.name for t in threads if t.is_alive()]
    if alive:
        raise RuntimeError(f"user threads did not stop: {alive}")
    return {"records": feed.records, "window": win,
            "nominal_open": nominal_open}
