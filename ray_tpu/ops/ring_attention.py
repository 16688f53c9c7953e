"""Ring attention: exact attention over sequence shards on the "sp" axis.

Long-context substrate (SURVEY §5: the reference ships none — only the
NCCL send/recv primitives a ring could be hand-built from; here it is a
first-class op). Each rank holds 1/n of the sequence; KV blocks rotate
around the ICI ring (ppermute) for n steps while each rank accumulates
online-softmax statistics, so no rank ever materializes more than
[chunk, chunk] scores and the full sequence is never gathered.

Causality uses absolute positions: rank r owns positions
[r*chunk, (r+1)*chunk); a KV block originating at rank j is fully attended
when j < r, causally masked when j == r, fully masked when j > r.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, causal: bool = True,
                         sm_scale: Optional[float] = None) -> jax.Array:
    """Ring attention body — call inside shard_map over ``axis_name``.

    q, k, v: local shards [batch, chunk, heads, head_dim] (KV heads may be
    fewer; GQA is applied blockwise). Returns [batch, chunk, heads, head_dim].
    """
    from ray_tpu.ops.layers import repeat_kv

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    from ray_tpu.parallel.device_collectives import axis_size

    n = axis_size(axis_name)
    my_rank = jax.lax.axis_index(axis_name)
    b, chunk, h, d = q.shape
    n_rep = h // k.shape[2]

    qf = q.astype(jnp.float32) * sm_scale
    q_pos = my_rank * chunk + jnp.arange(chunk)

    def step(i, carry):
        acc, m, l, k_cur, v_cur = carry
        # The block currently held arrived from `i` hops upstream.
        src_rank = (my_rank - i) % n
        k_rep = repeat_kv(k_cur, n_rep).astype(jnp.float32)
        v_rep = repeat_kv(v_cur, n_rep).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_rep,
                       preferred_element_type=jnp.float32)
        if causal:
            k_pos = src_rank * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_rep, preferred_element_type=jnp.float32
        )
        # rotate kv to the next rank (one ICI hop)
        perm = [(r, (r + 1) % n) for r in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc_new, m_new, l_new, k_nxt, v_nxt

    # mark the fresh accumulators as varying over the ring axis so the
    # fori_loop carry types match (outputs depend on axis_index)
    def varying(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    acc0 = varying(jnp.zeros((b, h, chunk, d), jnp.float32))
    m0 = varying(jnp.full((b, h, chunk, 1), _NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((b, h, chunk, 1), jnp.float32))
    acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh,
                   axis_name: str = "sp", causal: bool = True,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Global-array entry: q/k/v [batch, seq, heads, head_dim] with seq
    sharded over ``axis_name``; returns the same layout."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)
    f = shard_map(
        partial(ring_attention_local, axis_name=axis_name, causal=causal,
                sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return f(q, k, v)
