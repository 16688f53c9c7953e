"""Backend hooks: per-framework gang wiring.

Reference: python/ray/train/backend.py (Backend/BackendConfig) and
train/torch/config.py:154 (_TorchBackend wires torch.distributed). Here the
first-class backend is JAX: set up jax.distributed for multi-host TPU pods,
or a virtual CPU platform for tests, plus a host-level (DCN) collective
group for cross-gang reductions outside jitted programs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import tracing


@dataclass
class BackendConfig:
    def backend_cls(self):
        return Backend


class Backend:
    """No-op base backend."""

    def on_start(self, worker_group: WorkerGroup, backend_config: "BackendConfig"):
        pass

    def on_training_start(self, worker_group: WorkerGroup,
                          backend_config: "BackendConfig"):
        pass

    def abort_collectives(self, worker_group: WorkerGroup, reason: str):
        """Elastic resize, step 1: unblock survivors stuck in in-flight
        collectives (they fail over to CollectiveAbortedError within a
        poll interval instead of stalling out the op timeout). Called
        with the gang still at its OLD generation."""

    def on_resize(self, worker_group: WorkerGroup,
                  backend_config: "BackendConfig"):
        """Elastic resize, step 2: re-wire the (already re-ranked) gang
        at its new world size and generation — re-join collective
        groups, refresh platform/distributed state on every worker
        (including workers added by a grow)."""

    def on_shutdown(self, worker_group: WorkerGroup):
        pass


@dataclass
class JaxConfig(BackendConfig):
    """JAX gang wiring.

    platform: 'tpu' (real chips), 'cpu' (virtual devices for tests), or None
        to inherit the ambient platform.
    cpu_devices_per_worker: when platform='cpu', how many virtual XLA host
        devices each worker exposes (xla_force_host_platform_device_count).
    distributed: initialize jax.distributed across the gang (multi-host TPU
        pods / multi-process CPU). Worker 0 is the coordinator.
    host_collectives: create a host-level collective group named 'train'
        over the gang (the DCN/GLOO-equivalent path).
    """

    platform: Optional[str] = None
    cpu_devices_per_worker: int = 1
    distributed: bool = False
    coordinator_port: int = 0  # 0 = pick a free port on rank 0's host
    host_collectives: bool = True

    def backend_cls(self):
        return _JaxBackend


def _setup_jax_platform(platform: Optional[str], n_cpu_devices: int):
    from ray_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    if platform == "cpu":
        import re

        # REPLACE any inherited device-count flag (the pytest conftest
        # exports one for the whole session; each gang worker must get its
        # own local count, not the driver's)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_cpu_devices}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    elif platform == "tpu":
        # asked for chips: open them now, in the worker the runtime gave
        # them to, and fail here — not on some other backend mid-loop —
        # if libtpu cannot
        os.environ["JAX_PLATFORMS"] = "tpu"
        import jax

        jax.config.update("jax_platforms", "tpu")
        with tracing.span("rtpu.backend.devices", keep=True):
            jax.devices()
    # every program the train function compiles from here on is a span
    tracing.watch_jax()


def _pick_coordinator(port: int) -> str:
    """Runs on rank 0: its host + a concrete port (a free one when the
    config leaves port=0, so repeated gangs never collide)."""
    import socket

    from ray_tpu.core.cluster.rpc import pick_port

    host = socket.gethostname()
    return f"{host}:{port or pick_port()}"


def _init_jax_distributed(coordinator: str, num_processes: int, process_id: int):
    import jax

    try:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    except RuntimeError as e:
        # a reused worker process from an earlier gang: reset and rejoin
        if "already" not in str(e).lower():
            raise
        jax.distributed.shutdown()
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)


def _join_host_collective_group(world_size: int, rank: int, group_name: str,
                                generation: int = 0):
    from ray_tpu.parallel import collective

    collective.init_collective_group(world_size, rank, backend="host",
                                     group_name=group_name,
                                     generation=generation)


TRAIN_GROUP = "train"


class _JaxBackend(Backend):
    def on_start(self, worker_group: WorkerGroup, cfg: JaxConfig):
        from ray_tpu.train.session import _install_preemption_handler

        worker_group.execute(_setup_jax_platform, cfg.platform,
                             cfg.cpu_devices_per_worker)
        # TPU maintenance events arrive as SIGTERM: give every gang
        # worker a grace window to checkpoint (session.preempted())
        worker_group.execute(_install_preemption_handler)
        if cfg.distributed and len(worker_group) > 1:
            coordinator = worker_group.execute_single(
                0, _pick_coordinator, cfg.coordinator_port)
            import ray_tpu

            refs = [
                w.execute.remote(_init_jax_distributed, coordinator,
                                 len(worker_group), rank)
                for rank, w in enumerate(worker_group.workers)
            ]
            ray_tpu.get(refs)

    def _join_collectives(self, worker_group: WorkerGroup, cfg: JaxConfig):
        if cfg.host_collectives and len(worker_group) > 1:
            import ray_tpu

            refs = [
                w.execute.remote(_join_host_collective_group,
                                 len(worker_group), rank, TRAIN_GROUP,
                                 worker_group.generation)
                for rank, w in enumerate(worker_group.workers)
            ]
            ray_tpu.get(refs)

    def on_training_start(self, worker_group: WorkerGroup, cfg: JaxConfig):
        self._join_collectives(worker_group, cfg)

    def abort_collectives(self, worker_group: WorkerGroup, reason: str):
        from ray_tpu.parallel import collective

        collective.abort_group(TRAIN_GROUP, reason,
                               generation=worker_group.generation)

    def on_resize(self, worker_group: WorkerGroup, cfg: JaxConfig):
        from ray_tpu.parallel import collective
        from ray_tpu.train.session import _install_preemption_handler

        # the previous incarnation's (aborted) coordinator has been fully
        # drained by now; reclaim its name slot
        if worker_group.generation > 0:
            collective.destroy_coordinator(
                TRAIN_GROUP, generation=worker_group.generation - 1)
        # idempotent for survivors, required for grown-in workers
        worker_group.execute(_setup_jax_platform, cfg.platform,
                             cfg.cpu_devices_per_worker)
        worker_group.execute(_install_preemption_handler)
        if cfg.distributed and len(worker_group) > 1:
            coordinator = worker_group.execute_single(
                0, _pick_coordinator, cfg.coordinator_port)
            import ray_tpu

            refs = [
                w.execute.remote(_init_jax_distributed, coordinator,
                                 len(worker_group), rank)
                for rank, w in enumerate(worker_group.workers)
            ]
            ray_tpu.get(refs)
        self._join_collectives(worker_group, cfg)

    def on_shutdown(self, worker_group: WorkerGroup):
        from ray_tpu.parallel import collective

        # reclaim the current incarnation's coordinator so a later gang
        # (cold restart in the same runtime) starts from fresh,
        # un-aborted state
        collective.destroy_coordinator(
            TRAIN_GROUP, generation=worker_group.generation)
