"""Routed mixture of SwiGLU experts: sorted, dropless, every shape static.

The layer the many-small-expert models share (OLMoE, Mixtral here;
Moonlight, DeepSeek-V2-Lite, Trinity-Mini on the roadmap). A token picks
``top_k`` of ``E`` experts; the ``n * top_k`` (token, choice) pairs are
sorted by expert, the token rows gathered into that order, and each
expert multiplies its own contiguous group of rows: three grouped
matmuls with SwiGLU between. The rows come back to their tokens and
are summed. There is no capacity and no dropped row: how many rows an
expert gets is data (``group_sizes``), the number of rows in all is the
static ``n * top_k``, so no routing, however uneven, compiles anything.

The gate weight multiplies the SwiGLU activation, not the down
projection's output (linear: ``sum_k w * (a @ W)`` = ``sum_k (w * a) @
W``): in float32, inside the activation's elementwise pass, rounded once;
``d top_w`` is a row sum in that pass's backward. No float32 ``[n * top_k,
h]`` array exists and the backward never reads the down projection's
output, so a layer's ``jax.checkpoint`` does not recompute it (8 grouped
matmuls a layer, not 9; PERF.md 6, PR 29).

The two permutations are gathers in both directions, not a scatter-add
of 65,536 rows: ``_dispatch`` (rows into expert order) and ``_combine``
(back, summed over the choices) are each other's transpose.

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
activation, the gate weighting in it), ``moe_combine`` (gather back, sum).
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


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, token_of, inv, top_k):
    """rows [n * top_k, h] in expert order -> [n, h]: a token's
    ``top_k`` rows gathered back and summed in float32."""
    back = rows[inv].reshape(-1, top_k, rows.shape[-1])
    return back.astype(jnp.float32).sum(1).astype(rows.dtype)


def _gather_rules(permute, transpose):
    """A permutation's ``custom_vjp`` rules: the indices are the residuals."""
    return (lambda x, token_of, inv, top_k: (
                permute(x, token_of, inv, top_k), (token_of, inv)),
            lambda top_k, res, g: (transpose(g, *res, top_k), None, None))


_dispatch.defvjp(*_gather_rules(_dispatch, _combine))
_combine.defvjp(*_gather_rules(_combine, _dispatch))


@jax.custom_vjp
def _place(values, to):
    """values [m] -> out [m], out[to[i]] = values[i], ``to`` a permutation:
    a sort by ``to``, 0.06 ms for 65,536 scalars on a v5e where the gather
    by its inverse is 0.56 (PR 29). The transpose stays the gather ``g[to]``:
    a sort there splits the activation's backward fusion in two."""
    return jax.lax.sort((to, values), num_keys=1)[1]


_place.defvjp(lambda values, to: (_place(values, to), to),
              lambda to, g: (g[to], None))

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
    int32: rows each expert multiplied, n * top_k in all). A row's gate
    weight goes into its activation, before the down projection (above)."""
    num_experts = router_w.shape[-1]
    dt = x.dtype
    with jax.named_scope("moe_route"):
        logits, top_w, top_e = route(x, router_w, top_k, renormalize)
        flat_e = top_e.reshape(-1)
        # stable: an expert's rows stay in token order
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order)
        token_of = order // top_k
        counts = (flat_e[:, None] == jnp.arange(num_experts)[None, :]
                  ).sum(0, dtype=jnp.int32)
    with jax.named_scope("moe_dispatch"):
        rows = _dispatch(x, token_of, inv, top_k)
        w_rows = _place(top_w.reshape(-1), inv)
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, e_gate.astype(dt), counts)
        up = grouped_matmul(rows, e_up.astype(dt), counts)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32) * w_rows[:, None])
        rows = grouped_matmul(act.astype(dt), e_down.astype(dt), counts)
    with jax.named_scope("moe_combine"):
        out = _combine(rows, token_of, inv, top_k)
    return out, logits, counts
