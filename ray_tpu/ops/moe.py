"""Routed mixture of SwiGLU experts: sorted, dropless, every shape static.

The layer the many-small-expert models share (OLMoE, Mixtral here;
Moonlight, DeepSeek-V2-Lite, Trinity-Mini on the roadmap). A token picks
``top_k`` of ``E`` experts; the ``n * top_k`` (token, choice) pairs are
sorted by expert, the token rows gathered into that order, and each
expert multiplies its own contiguous group of rows: three grouped
matmuls with SwiGLU between. The rows come back to their tokens weighted
by the gate and summed. There is no capacity and no
dropped row: how many rows an expert gets is data (``group_sizes``), the
number of rows in all is the static ``n * top_k``, so no routing, however
uneven, compiles anything.

The two permutations are written as gathers in both directions (a
``custom_vjp`` each): the transpose of "gather the rows into expert
order" is "gather them back and sum over the choices", not a
scatter-add of 65,536 rows.

The grouped matmul is jax's megablox Mosaic kernels on a TPU (``gmm``,
``tgmm`` in a trace) and ``jax.lax.ragged_dot`` elsewhere. On the chip
XLA lowers ``ragged_dot`` to a Mosaic kernel of its own, which read 84-89
TFLOP/s at OLMoE's shapes against megablox's 128-133 (gmm) and 111-114
(tgmm) at the tiles below, and which drops the scope names from its calls
(v5e, PR 26, PERF.md).

Named scopes (metadata only, nested under the caller's ``mlp``; a
backward operation carries the scope of the call it transposes):
``moe_route`` (router matmul, softmax, top-k, sort), ``moe_dispatch``
(gather into expert order), ``moe_experts`` (grouped matmuls and the
activation), ``moe_combine`` (gate weighting, gather back, sum).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, token_of, inv, top_k):
    """x [n, h] -> rows in expert order [n * top_k, h]."""
    return x[token_of]


def _dispatch_fwd(x, token_of, inv, top_k):
    return x[token_of], inv


def _dispatch_bwd(top_k, inv, d_rows):
    n = inv.shape[0] // top_k
    dx = d_rows[inv].reshape(n, top_k, -1).astype(jnp.float32).sum(1)
    return dx.astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, top_w, inv, order):
    """rows [n * K, h] in expert order, top_w [n, K] float32 in token
    order -> [n, h]: sum_k top_w[t, k] * rows[inv[t * K + k]], weighted
    and summed in float32."""
    n, k = top_w.shape
    back = rows[inv].reshape(n, k, -1).astype(jnp.float32)
    return (back * top_w[:, :, None]).sum(1).astype(rows.dtype)


def _combine_fwd(rows, top_w, inv, order):
    return _combine(rows, top_w, inv, order), (rows, top_w, inv, order)


def _combine_bwd(res, d_out):
    rows, top_w, inv, order = res
    n, k = top_w.shape
    g = d_out.astype(jnp.float32)
    back = rows[inv].reshape(n, k, -1).astype(jnp.float32)
    d_w = (back * g[:, None, :]).sum(-1)
    w_rows = top_w.reshape(-1)[order]
    d_rows = (g[order // k] * w_rows[:, None]).astype(rows.dtype)
    return d_rows, d_w, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)

# megablox tiles (rows, contraction, columns), from a sweep on v5e at
# 65,536 rows x 2048 x 1024 and x 1024 x 2048, 64 groups, balanced and
# skewed alike: 256 rows a tile (a group's last tile is part empty: the
# smaller, the less is wasted), the whole contraction, and as many
# columns as keep a weight tile at 2M elements (VMEM). tgmm wants 1024s.
_ROW_TILE = 256


def _gmm_tiles(k: int, n: int) -> Tuple[int, int, int]:
    tk = min(k, 2048)
    return _ROW_TILE, tk, min(n, (2 << 20) // tk)


def _megablox():
    from importlib import import_module

    return import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _gmm_tpu(rows, w, sizes):
    """rows [m, k] in groups of ``sizes`` rows, w [E, k, n] -> [m, n]."""
    return _megablox().gmm(rows, w, sizes, rows.dtype,
                           _gmm_tiles(*w.shape[1:]))


def _gmm_tpu_fwd(rows, w, sizes):
    return _gmm_tpu(rows, w, sizes), (rows, w, sizes)


def _gmm_tpu_bwd(res, d_out):
    rows, w, sizes = res
    k, n = w.shape[1:]
    mb = _megablox()
    d_rows = mb.gmm(d_out, w, sizes, rows.dtype, _gmm_tiles(n, k),
                    transpose_rhs=True)
    d_w = mb.tgmm(rows.swapaxes(0, 1), d_out, sizes, w.dtype,
                  (_ROW_TILE, min(k, 1024), min(n, 1024)))
    return d_rows, d_w, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(rows: jax.Array, w: jax.Array, sizes: jax.Array
                   ) -> jax.Array:
    """rows [m, k], the first ``sizes[0]`` of them expert 0's and so on,
    w [E, k, n] -> [m, n] in the rows' dtype, accumulated in float32."""
    if jax.default_backend() == "tpu" and rows.shape[0] % _ROW_TILE == 0:
        return _gmm_tpu(rows, w, sizes)
    return jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=rows.dtype)


def route(x: jax.Array, router_w: jax.Array, top_k: int,
          renormalize: bool = False
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(router_logits [n, E] float32, top_w [n, K] float32, top_e [n, K]
    int32): logits accumulate in float32, softmax in float32 over all E,
    then the K largest; ``renormalize`` divides the K weights by their
    sum (Mixtral does, OLMoE does not)."""
    logits = jnp.dot(x, router_w.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    return logits, top_w, top_e


def routed_experts(x: jax.Array, router_w: jax.Array, e_gate: jax.Array,
                   e_up: jax.Array, e_down: jax.Array, top_k: int,
                   renormalize: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [n, h], router_w [h, E], e_gate / e_up [E, h, f], e_down
    [E, f, h] -> (out [n, h], router_logits [n, E] float32, counts [E]
    int32: rows each expert multiplied, n * top_k in all)."""
    n, _ = x.shape
    num_experts = router_w.shape[-1]
    dt = x.dtype
    with jax.named_scope("moe_route"):
        logits, top_w, top_e = route(x, router_w, top_k, renormalize)
        flat_e = top_e.reshape(n * top_k)
        # stable: an expert's rows stay in token order
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * top_k, dtype=jnp.int32))
        counts = (flat_e[:, None] == jnp.arange(num_experts)[None, :]
                  ).sum(0, dtype=jnp.int32)
    with jax.named_scope("moe_dispatch"):
        rows = _dispatch(x, order // top_k, inv, top_k)
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, e_gate.astype(dt), counts)
        up = grouped_matmul(rows, e_up.astype(dt), counts)
        rows = grouped_matmul(jax.nn.silu(gate) * up, e_down.astype(dt),
                              counts)
    with jax.named_scope("moe_combine"):
        out = _combine(rows, top_w, inv, order)
    return out, logits, counts
