"""Env rules shared by BOTH worker spawn paths (cold Popen and zygote
fork). One definition so a new TPU/PJRT env rule can never apply to one
path and silently miss the other."""

from __future__ import annotations

from typing import Dict, List


def sanitize_cpu_worker_env(env) -> None:
    """Pin a plain pool worker to the CPU backend.

    A chip belongs to one process at a time, and it goes to the TPU actor
    the runtime granted it to. Whatever the ambient JAX_PLATFORMS says
    (unset, ``tpu``, ``tpu,cpu``), a pool worker that imports jax (data,
    rllib, a CPU engine replica) must not open the chip ahead of that
    actor. Mutates ``env`` in place (works for both a dict and
    os.environ)."""
    env["JAX_PLATFORMS"] = "cpu"


# TPU_CHIPS_PER_PROCESS_BOUNDS for a process holding part of a host,
# by chip count: the shapes seen to initialise under libtpu 0.0.34 on a
# v5e 2x2 host (PR 21's four-chip probe: visible chips alone fail on
# libtpu's multi-process lockfile; with bounds, four 1-chip and two
# 2-chip processes ran side by side).
_SUBSLICE_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def tpu_worker_env(chips: List[int], chips_on_host: int) -> Dict[str, str]:
    """Env that confines a TPU actor's process to its granted chips.

    libtpu opens every chip of the host unless told otherwise, so a
    second process would find them taken. ``TPU_VISIBLE_CHIPS`` names the
    chips; a process holding fewer than the whole host must also be told
    the shape of its own sub-slice, or libtpu still claims the host. A
    count with no known shape gets the visible chips alone — libtpu then
    refuses at start-up, loudly, if that is not enough."""
    chips_str = ",".join(str(c) for c in chips)
    env = {"TPU_VISIBLE_CHIPS": chips_str, "RTPU_TPU_CHIPS": chips_str}
    bounds = _SUBSLICE_BOUNDS.get(len(chips))
    if len(chips) < chips_on_host and bounds is not None:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env
