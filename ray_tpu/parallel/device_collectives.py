"""In-program collectives: XLA ops over ICI, the TPU data plane.

These are thin named wrappers around ``jax.lax`` collectives for use inside
``shard_map``/``pjit`` programs over a ray_tpu mesh. They replace the
reference's eager NCCL calls (util/collective/collective.py:258 allreduce,
:423 allgather, :472 reducescatter, :531/:594 send/recv): on TPU the
collective IS part of the compiled program and XLA schedules it onto ICI
links (scaling-book recipe), rather than a runtime service call.

Ring primitives (`ring_permute`, `ring_slice_exchange`) are the substrate
ring attention and pipeline microbatching build on.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

AxisName = Union[str, Sequence[str]]


def psum(x, axis: AxisName):
    import jax

    return jax.lax.psum(x, axis)


def pmean(x, axis: AxisName):
    import jax

    return jax.lax.pmean(x, axis)


def pmax(x, axis: AxisName):
    import jax

    return jax.lax.pmax(x, axis)


def pmin(x, axis: AxisName):
    import jax

    return jax.lax.pmin(x, axis)


def all_gather(x, axis: AxisName, *, gather_axis: int = 0, tiled: bool = True):
    """Gather shards along ``gather_axis`` across the mesh axis."""
    import jax

    return jax.lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0):
    """Sum-reduce then scatter along ``scatter_axis`` (ZeRO gradient path)."""
    import jax

    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                                tiled=True)


def all_to_all(x, axis: AxisName, *, split_axis: int, concat_axis: int):
    """All-to-all (the Ulysses/DeepSpeed sequence-parallel primitive)."""
    import jax

    return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def axis_index(axis: AxisName):
    import jax

    return jax.lax.axis_index(axis)


def axis_size(axis: str):
    import jax

    return jax.lax.axis_size(axis)


def ring_permute(x, axis: str, shift: int = 1):
    """Send this shard to the neighbor ``shift`` steps around the ring and
    receive from the opposite neighbor — one hop of a ring collective
    (ppermute over ICI; the building block of ring attention)."""
    import jax

    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def ring_slice_exchange(kv, axis: str):
    """One ring-attention step: pass the current KV block to the next rank.

    Returns the block received from the previous rank. Used in a
    ``lax.fori_loop`` of ``axis_size`` steps so every rank sees every block
    while only ever holding 1/n of the sequence.
    """
    return ring_permute(kv, axis, shift=1)


def pbroadcast(x, axis: str, src: int = 0):
    """Broadcast src rank's value across the axis."""
    import jax
    import jax.numpy as jnp

    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)
