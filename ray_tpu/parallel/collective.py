"""Host-level collective groups across actors (the out-of-band API).

Mirrors the reference's ``ray.util.collective`` surface
(collective.py:120 init_collective_group, :258 allreduce, :373 broadcast,
:423 allgather, :531/:594 send/recv) with TPU-native backends:

- ``backend="host"``: cross-process collectives through a named coordinator
  actor + the shared-memory object store — the GLOO/DCN-fallback path. The
  coordinator plays the role of the reference's ``Rendezvous`` actor
  (collective_group/nccl_collective_group.py:29), but since there is no NCCL
  to bootstrap it carries the data itself.
- ``backend="xla"``: an in-process group over local devices; collectives are
  jitted XLA programs over ICI via shard_map (see device_collectives for the
  in-program forms — the hot path for model math should use those directly).

Gang-step data-plane collectives in trainers do NOT go through this module;
they live inside the jitted train step (parallel/device_collectives.py).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.exceptions import RayTpuError

_COORD_PREFIX = "rtpu_collective::"
_groups: Dict[str, "CollectiveGroup"] = {}

REDUCE_OPS = ("sum", "prod", "min", "max")

# Sentinel the coordinator hands back from every rendezvous method once
# the group is aborted; members convert it into CollectiveAbortedError.
# A marker return (instead of raising inside the actor) keeps the abort
# indistinguishable from a normal reply on the wire — no reliance on
# exception pickling — and lets blocked pollers observe it on their very
# next 2 ms poll instead of waiting out the 120 s _sync_op timeout.
_ABORT = "__rtpu_collective_abort__"


class CollectiveAbortedError(RayTpuError):
    """An in-flight collective was aborted — typically because a gang
    peer died and the driver is resizing the group. The message names
    the reason (including the dead rank when known). Callers inside a
    train loop should let it propagate: the session/executor treat it
    as a resize signal, not an application error."""


class _Coordinator:
    """Named actor holding rendezvous + reduction state for one group.

    Methods are polled by members; per-operation state is keyed by a
    monotonically increasing per-member round counter so reuse is safe.
    Once ``abort`` is called every rendezvous method returns the abort
    marker forever — the group is dead and must be re-created (under a
    new generation) to be used again.
    """

    def __init__(self, world_size: int):
        self.world_size = world_size
        self.rounds: Dict[str, dict] = {}
        self.mailbox: Dict[Tuple[int, int, int], Any] = {}
        self.aborted: Optional[str] = None

    def abort(self, reason: str):
        self.aborted = reason or "collective group aborted"
        self.rounds.clear()
        self.mailbox.clear()
        return True

    def contribute(self, key: str, rank: int, data, op: str):
        if self.aborted is not None:
            return (_ABORT, self.aborted)
        st = self.rounds.setdefault(key, {"parts": {}, "result": None, "op": op})
        st["parts"][rank] = data
        if len(st["parts"]) == self.world_size and st["result"] is None:
            parts = [st["parts"][r] for r in range(self.world_size)]
            st["result"] = self._combine(parts, op)
        return st["result"] is not None

    def fetch(self, key: str, rank: int):
        if self.aborted is not None:
            return (_ABORT, self.aborted)
        st = self.rounds.get(key)
        if st is None or st["result"] is None:
            return False, None
        st.setdefault("fetched", set()).add(rank)
        result = st["result"]
        if len(st["fetched"]) == self.world_size:
            del self.rounds[key]  # all members have it; free the round
        return True, result

    @staticmethod
    def _combine(parts: List[Any], op: str):
        if op == "gather":
            return parts
        if op == "barrier":
            return True
        arrs = [np.asarray(p) for p in parts]
        if op == "sum":
            out = arrs[0].copy()
            for a in arrs[1:]:
                out += a
            return out
        if op == "prod":
            out = arrs[0].copy()
            for a in arrs[1:]:
                out *= a
            return out
        if op == "min":
            return np.minimum.reduce(arrs)
        if op == "max":
            return np.maximum.reduce(arrs)
        if op.startswith("bcast:"):
            src = int(op.split(":", 1)[1])
            return parts[src]
        raise ValueError(f"unknown reduce op {op!r}")

    def post(self, src: int, dst: int, tag: int, data):
        if self.aborted is not None:
            return (_ABORT, self.aborted)
        self.mailbox[(src, dst, tag)] = data
        return None

    def take(self, src: int, dst: int, tag: int):
        if self.aborted is not None:
            return (_ABORT, self.aborted)
        if (src, dst, tag) in self.mailbox:
            return True, self.mailbox.pop((src, dst, tag))
        return False, None


class CollectiveGroup:
    """A member's view of one collective group."""

    def __init__(self, group_name: str, world_size: int, rank: int,
                 backend: str = "host", generation: int = 0):
        if backend not in ("host", "xla"):
            raise ValueError(f"backend must be 'host' or 'xla', got {backend!r}")
        self.name = group_name
        self.world_size = world_size
        self.rank = rank
        self.backend = backend
        self.generation = generation
        self._round = 0
        self._coord = None
        self._mesh = None
        if backend == "host":
            self._coord = _get_or_create_coordinator(
                group_name, world_size, generation)
        else:
            from ray_tpu.parallel.mesh import MeshSpec, build_mesh

            self._mesh = build_mesh(MeshSpec({"dp": world_size}))

    # ---- host backend primitives -------------------------------------------

    def abort(self, reason: str = "aborted"):
        """Poison the group: every member blocked in (or later entering)
        a collective gets CollectiveAbortedError on its next poll."""
        if self._coord is not None:
            import ray_tpu

            ray_tpu.get(self._coord.abort.remote(reason))

    def _check_abort(self, reply):
        """Raise if the coordinator replied with the abort marker."""
        if (isinstance(reply, tuple) and len(reply) == 2
                and reply[0] == _ABORT):
            raise CollectiveAbortedError(
                f"collective group {self.name!r} aborted "
                f"(rank {self.rank}/{self.world_size}): {reply[1]}")
        return reply

    def _sync_op(self, data, op: str, timeout: float = 120.0):
        import ray_tpu

        self._round += 1
        key = f"{op.split(':')[0]}:{self._round}"
        self._check_abort(ray_tpu.get(
            self._coord.contribute.remote(key, self.rank, data, op),
            timeout=timeout,
        ))
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            done, result = self._check_abort(ray_tpu.get(
                self._coord.fetch.remote(key, self.rank), timeout=timeout
            ))
            if done:
                return result
            time.sleep(0.002)
        raise TimeoutError(
            f"collective {op} timed out in group {self.name!r} "
            f"(rank {self.rank}/{self.world_size})"
        )

    # ---- ring allreduce ------------------------------------------------------

    # Above this size the host backend switches from the star (everything
    # through the coordinator) to a RING: chunks hop peer-to-peer as
    # ObjectRefs, the shm store is the data plane, and the coordinator
    # mailbox only rendezvouses refs — O(N) total movement per member and
    # O(refs) coordinator memory instead of O(world x N) payloads.
    RING_THRESHOLD_BYTES = 1 << 20

    def _ring_allreduce(self, arr: np.ndarray, op: str, timeout: float):
        import ray_tpu

        W, r = self.world_size, self.rank
        self._round += 1
        base = self._round * 10_000
        flat = arr.ravel()
        bounds = np.linspace(0, flat.size, W + 1).astype(int)
        own = [flat[bounds[i]: bounds[i + 1]].copy() for i in range(W)]

        def send_chunk(chunk, tag):
            ref = ray_tpu.put(np.ascontiguousarray(chunk))
            # nested (listed) refs pass through UNRESOLVED, so the
            # coordinator mailbox holds the ref, never the payload
            self._check_abort(ray_tpu.get(
                self._coord.post.remote(r, (r + 1) % W, tag, [ref])))

        def recv_chunk(tag):
            boxed = self.recv((r - 1) % W, tag=tag, timeout=timeout)
            return np.asarray(ray_tpu.get(boxed[0]))

        # phase 1: reduce-scatter around the ring
        for s in range(W - 1):
            send_chunk(own[(r - s) % W], base + s)
            idx = (r - s - 1) % W
            own[idx] = _reduce2(own[idx], recv_chunk(base + s), op)
        # phase 2: all-gather the reduced chunks
        for s in range(W - 1):
            send_chunk(own[(r + 1 - s) % W], base + 5000 + s)
            idx = (r - s) % W
            own[idx] = recv_chunk(base + 5000 + s)
        return np.concatenate(own).reshape(arr.shape)

    # ---- API ----------------------------------------------------------------

    def allreduce(self, tensor, op: str = "sum", timeout: float = 120.0):
        if self.backend == "xla":
            return _xla_allreduce(self._mesh, tensor, op)
        arr = np.asarray(tensor)
        if (self.world_size > 1 and op in REDUCE_OPS
                and arr.nbytes >= self.RING_THRESHOLD_BYTES):
            return self._ring_allreduce(arr, op, timeout)
        return self._sync_op(arr, op, timeout)

    def allgather(self, tensor, timeout: float = 120.0) -> List[Any]:
        return self._sync_op(np.asarray(tensor), "gather", timeout)

    def reducescatter(self, tensor, op: str = "sum", timeout: float = 120.0):
        full = self._sync_op(np.asarray(tensor), op, timeout)
        chunks = np.array_split(full, self.world_size, axis=0)
        return chunks[self.rank]

    def broadcast(self, tensor, src_rank: int = 0, timeout: float = 120.0):
        return self._sync_op(np.asarray(tensor), f"bcast:{src_rank}", timeout)

    def barrier(self, timeout: float = 120.0):
        self._sync_op(None, "barrier", timeout)

    def send(self, tensor, dst_rank: int, tag: int = 0):
        import ray_tpu

        self._check_abort(ray_tpu.get(
            self._coord.post.remote(self.rank, dst_rank, tag, np.asarray(tensor))
        ))

    def recv(self, src_rank: int, tag: int = 0, timeout: float = 120.0):
        import ray_tpu

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            ok, data = self._check_abort(ray_tpu.get(
                self._coord.take.remote(src_rank, self.rank, tag)
            ))
            if ok:
                return data
            time.sleep(0.002)
        raise TimeoutError(f"recv from rank {src_rank} timed out")


def _reduce2(a: np.ndarray, b: np.ndarray, op: str) -> np.ndarray:
    if op == "sum":
        return a + b
    if op == "prod":
        return a * b
    if op == "min":
        return np.minimum(a, b)
    return np.maximum(a, b)


def _xla_allreduce(mesh, tensor, op: str):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fns = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}
    if op not in fns:
        raise ValueError(f"xla backend supports {list(fns)}, got {op!r}")
    f = shard_map(
        lambda x: fns[op](x, "dp"), mesh=mesh,
        in_specs=P("dp"), out_specs=P(),
    )
    return jax.jit(f)(jnp.asarray(tensor))


def _coord_name(group_name: str, generation: int = 0) -> str:
    """Named-actor name for a group's coordinator. Generations let an
    elastic gang re-form the same logical group at a new world size
    without colliding with (or resurrecting the abort flag of) the
    previous incarnation's coordinator."""
    name = _COORD_PREFIX + group_name
    return name if generation == 0 else f"{name}@{generation}"


def _get_or_create_coordinator(group_name: str, world_size: int,
                               generation: int = 0):
    import ray_tpu

    name = _coord_name(group_name, generation)
    try:
        return ray_tpu.get_actor(name)
    except ValueError:
        pass
    try:
        coord_cls = ray_tpu.remote(_Coordinator)
        return coord_cls.options(name=name).remote(world_size)
    except ValueError:
        # lost the creation race; the winner's actor is registered
        return ray_tpu.get_actor(name)


def abort_group(group_name: str = "default", reason: str = "aborted",
                generation: int = 0) -> bool:
    """Driver-side: poison a group's coordinator so every member blocked
    in a collective fails over to CollectiveAbortedError within one poll
    interval (~ms), instead of stalling out the 120 s op timeout. Safe
    to call from a process that never joined the group. Returns False
    when no coordinator exists (nothing to abort)."""
    import ray_tpu

    try:
        coord = ray_tpu.get_actor(_coord_name(group_name, generation))
    except ValueError:
        return False
    ray_tpu.get(coord.abort.remote(reason))
    return True


def destroy_coordinator(group_name: str = "default",
                        generation: int = 0) -> bool:
    """Driver-side: kill a group's coordinator actor (after members have
    drained). A later init at the same name starts from fresh state."""
    import ray_tpu

    name = _coord_name(group_name, generation)
    try:
        coord = ray_tpu.get_actor(name)
    except ValueError:
        return False
    ray_tpu.kill(coord)
    # Wait until the name is actually deregistered: kill() is async, and
    # a fresh gang re-forming at the same name (cold restart after a
    # shrink below min_workers) must get-or-create a NEW coordinator, not
    # rendezvous with this dying one.
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        try:
            ray_tpu.get_actor(name)
        except ValueError:
            return True
        time.sleep(0.02)
    return True


def init_collective_group(world_size: int, rank: int,
                          backend: str = "host",
                          group_name: str = "default",
                          generation: int = 0) -> CollectiveGroup:
    """Join a collective group (call once per member)."""
    if not 0 <= rank < world_size:
        raise ValueError(f"rank {rank} out of range for world_size {world_size}")
    group = CollectiveGroup(group_name, world_size, rank, backend, generation)
    _groups[group_name] = group
    return group


def get_group(group_name: str = "default") -> CollectiveGroup:
    if group_name not in _groups:
        raise ValueError(
            f"collective group {group_name!r} not initialized in this process"
        )
    return _groups[group_name]


def destroy_collective_group(group_name: str = "default"):
    _groups.pop(group_name, None)


def allreduce(tensor, group_name: str = "default", op: str = "sum"):
    return get_group(group_name).allreduce(tensor, op)


def allgather(tensor, group_name: str = "default"):
    return get_group(group_name).allgather(tensor)


def reducescatter(tensor, group_name: str = "default", op: str = "sum"):
    return get_group(group_name).reducescatter(tensor, op)


def broadcast(tensor, src_rank: int = 0, group_name: str = "default"):
    return get_group(group_name).broadcast(tensor, src_rank)


def barrier(group_name: str = "default"):
    get_group(group_name).barrier()


def send(tensor, dst_rank: int, group_name: str = "default", tag: int = 0):
    get_group(group_name).send(tensor, dst_rank, tag)


def recv(src_rank: int, group_name: str = "default", tag: int = 0):
    return get_group(group_name).recv(src_rank, tag)
