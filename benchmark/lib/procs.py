"""Ends a run's processes before the run's own process goes on.

The runtime's ``kill`` (which ends a train worker or a replica) and its
``shutdown`` send a signal and do not wait: the worker that holds the
chips is popped from the runtime's table when it dies and nobody reaps
it, so ``ray_tpu.shutdown()`` can return, and this process exit, while
that worker is still giving back its chips. The next run on the machine
then finds a chip busy. ``wait_for_children`` waits, after the runtime's
own shutdown, until every child of this process has ended, and so until
the chips are free again.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Dict, List


def _children() -> List[int]:
    me = os.getpid()
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        if ppid == me:
            out.append(int(entry))
    return out


def wait_for_children(grace_s: float = 60.0) -> Dict[str, float]:
    """Reaps every child of this process; one still alive after
    ``grace_s`` is killed and then waited for. Returns how many were
    reaped, how many had to be killed, and the seconds it took."""
    t0 = time.monotonic()
    reaped = killed = 0
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            break
        if pid:
            reaped += 1
            continue
        if not killed and time.monotonic() - t0 > grace_s:
            for p in _children():
                try:
                    os.kill(p, signal.SIGKILL)
                    killed += 1
                except OSError:
                    pass
        if time.monotonic() - t0 > 2 * grace_s:
            break
        time.sleep(0.02)
    return {"reaped": reaped, "killed": killed,
            "seconds": time.monotonic() - t0}


def host_memory_used_share() -> float:
    """1 - MemAvailable / MemTotal, as the runtime's memory monitor
    reads it (it kills a worker at 0.95)."""
    total = avail = 0
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemTotal:"):
                total = int(line.split()[1])
            elif line.startswith("MemAvailable:"):
                avail = int(line.split()[1])
    return 1.0 - avail / total if total else float("nan")
