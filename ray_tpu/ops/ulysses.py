"""Ulysses sequence parallelism: all-to-all head/sequence re-sharding.

The second long-context substrate next to ring attention (SURVEY §5 —
the reference ships neither; it only provides the NCCL send/recv these
are hand-built from). Where ring attention keeps the sequence sharded
and rotates KV blocks around the ICI ring, Ulysses re-shards with two
all-to-alls: ranks swap their sequence shard for a head shard, compute
exact full-sequence attention for their head subset with the best local
kernel (Pallas flash on TPU), and swap back. Comm volume is O(s·h·d/n)
per all-to-all — independent of the ring's n-step pipeline — which
makes it the better fit when heads are plentiful and the per-step
latency of the ring would dominate (short-ish chunks, small n).

q/k/v locals are [batch, chunk, heads, head_dim] with chunk = seq/n.
all_to_all(split=heads, concat=seq) yields [batch, seq, heads/n,
head_dim]; tiled concatenation orders blocks by rank index, so the
gathered sequence is in global order and a plain causal mask is exact.

GQA: the head blocks handed to rank i are q[i·h/n:(i+1)·h/n] and
kv[i·kv/n:(i+1)·kv/n]; when kv % n == 0 these correspond exactly (the
local attention applies the remaining repeat factor). When kv heads
don't divide n, KV is first repeated by the minimal factor
r = n / gcd(kv, n) (r divides h/kv whenever n divides h, so the local
repeat stays integral) — correctness is preserved at the cost of a
larger KV all-to-all, matching DeepSpeed-Ulysses' replication strategy.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp


def _a2a_seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    # [b, chunk, h, d] -> [b, seq, h/n, d]
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def _a2a_heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    # [b, seq, h/n, d] -> [b, chunk, h, d]
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                            axis_name: str, causal: bool = True,
                            sm_scale: Optional[float] = None,
                            attn_fn: Optional[Callable] = None) -> jax.Array:
    """Ulysses body — call inside shard_map over ``axis_name``.

    q: [batch, chunk, heads, head_dim]; k/v may have fewer (GQA) heads.
    Returns [batch, chunk, heads, head_dim].
    """
    from ray_tpu.ops.layers import repeat_kv

    from ray_tpu.parallel.device_collectives import axis_size

    n = axis_size(axis_name)
    h, kvh = q.shape[2], k.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses attention requires num_heads ({h}) divisible by the "
            f"'{axis_name}' axis size ({n}); use ring attention otherwise")
    if kvh % n:
        r = n // math.gcd(kvh, n)
        k = repeat_kv(k, r)
        v = repeat_kv(v, r)

    qh = _a2a_seq_to_heads(q, axis_name)
    kh = _a2a_seq_to_heads(k, axis_name)
    vh = _a2a_seq_to_heads(v, axis_name)

    if attn_fn is None:
        if jax.default_backend() == "tpu":
            from ray_tpu.ops.attention import flash_attention as attn_fn
        else:
            from ray_tpu.ops.attention import attention_reference as attn_fn
    out = attn_fn(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return _a2a_heads_to_seq(out, axis_name)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh,
                      axis_name: str = "sp", causal: bool = True,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None) -> jax.Array:
    """Global-array entry: q/k/v [batch, seq, heads, head_dim] with seq
    sharded over ``axis_name``; returns the same layout."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    f = shard_map(
        partial(ulysses_attention_local, axis_name=axis_name, causal=causal,
                sm_scale=sm_scale, attn_fn=attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return f(q, k, v)
