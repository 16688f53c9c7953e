"""Attention: reference jax implementation + Pallas flash-attention kernels.

The Pallas kernels are the TPU hot path: blocked online-softmax attention
that never materializes the [seq, seq] score matrix in HBM (VMEM-resident
tiles, MXU matmuls, fp32 accumulation). Grouped-query attention is supported
by mapping each query head to its KV group via the BlockSpec index maps.

Training uses ``flash_attention`` through a custom_vjp with FlashAttention-2
style Pallas *backward* kernels: the forward saves only O and the per-row
logsumexp; backward recomputes score tiles in VMEM and accumulates dQ in a
query-block kernel and dK/dV in a key-block kernel (per query head, reduced
over the GQA group outside). The reference ships no flash kernels at all
(SURVEY §5: NCCL/GPU paths only) — numerics oracle is ``attention_reference``
below.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.layers import repeat_kv
from ray_tpu.util import tracing

DEFAULT_BLOCK = 512
_NEG_INF = -1e30


def _auto_block(seq: int) -> int:
    """The block a call gets where its caller names none: the largest
    power of two that divides ``seq``, from 512 down to 128, whatever the
    ``window``. Measured on v5e (PR 31, ``benchmark/tests/window_scaling.py``
    under explicit blocks: forward + dQ + dK/dV, the kernels' own time at
    2 x 8,192 positions, 72 query heads on 8, head size 128): a 512-key
    band takes 21.2 ms in blocks of 512, 26.7 in 256 and 49.0 in 128,
    though 256 visits a quarter fewer score elements and 128 three eighths
    (``tile_plan``); causal 78.4, 132.7 and 303.6. A smaller tile loses
    more a score element (the MXU streams fewer rows a weight load, the
    accumulators' rescale weighs more beside the scores) than the band
    saves in elements; 4 x 4,096 positions read the same order. Wider
    tiles (1,024 either way) read 5-28% slower in the forward."""
    c = DEFAULT_BLOCK
    while c > 128 and seq % c:
        c //= 2
    return c


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Plain softmax attention (fp32 softmax), GQA-aware.

    q: [batch, seq_q, heads, head_dim]
    k, v: [batch, seq_k, kv_heads, head_dim]
    ``window`` (with ``causal``): a query sees the ``window`` keys that
    end at its own position, ``i - window < j <= i``.
    """
    _check_window(causal, window)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    # [b, h, sq, sk]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        seen = qi + (sk - sq) >= ki
        if window is not None:
            seen &= qi + (sk - sq) - ki < window
        scores = jnp.where(seen, scores, _NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _check_window(causal: bool, window: Optional[int]) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window!r} needs causal=True and at least "
                         "one key (the query's own position)")


# ---------------------------------------------------------------- pallas fwd
#
# A kernel's loop walks the tiles of one query block (forward, dQ) or of
# one key block (dK/dV) that hold a key some query of the tile sees, and
# skips the others: those ahead of the diagonal and, with a ``window``,
# those behind the band. ``_key_bounds`` and ``_query_bounds`` give the
# loop's bounds from positions (so ``causal_offset`` and unequal blocks
# need no case of their own), to the kernels as traced scalars and to
# ``tile_plan`` as numpy arrays over all blocks: what is counted is what
# runs. They also give the *interior* tiles among the visited, where every
# query sees every key. The kernels mask every tile they visit all the
# same: on the chip the mask is not what a tile waits for (its iotas and
# compares do not depend on the scores and run beside the matmul).
# Measured on v5e, PR 31, 2 x 8,192 positions: with the interior tiles run
# bare, as a second and third loop over the same body, the causal calls
# read 14.95 / 15.73 / 25.51 ms (forward / dQ / dK/dV, 48 heads, 120 of
# 136 tiles bare) for 14.83 / 15.82 / 25.42 masked throughout, and a
# 512-key band in blocks of 512 (no tile bare, three short loops a grid
# step) 7.65 / 6.18 / 12.11 for 6.74 / 6.32 / 11.56.
#
# With a ``window`` a later query of a tile the band's far edge cuts may
# see nothing of it: its row is all ``_NEG_INF`` there, the running maximum
# stays ``_NEG_INF`` and what it sums is wiped by ``alpha = 0`` at the next
# tile, which holds a key it sees.


def _div(x, block: int, xp):
    """``x // block`` for ``x >= 0``: in a kernel the truncating division,
    which is the floor there and half the scalar work of ``//`` (a grid
    step of a window call is a few microseconds long)."""
    return x // block if xp is not jnp else jax.lax.div(x, block)


def _key_bounds(qb, block_q: int, block_k: int, seq_k: int,
                causal_offset: int, causal: bool, window: Optional[int],
                xp=jnp):
    """The key blocks of query block ``qb``: ``(first, bare_first, bare_end,
    end)``. The loop visits ``[first, end)``; of those ``[bare_first,
    bare_end)`` are interior. With ``d = q_pos - k_pos`` a tile is visited
    where some ``0 <= d < window`` and interior where all are."""
    nk = seq_k // block_k
    if not causal:
        return 0, 0, nk, nk
    first_q = causal_offset + qb * block_q
    last_q = first_q + block_q - 1
    end = xp.minimum(
        nk, _div(xp.maximum(last_q + 1, 0) + block_k - 1, block_k, xp))
    # interior: the tile's last key at or before the first query ...
    bare_end = xp.minimum(
        _div(xp.maximum(first_q + 1, 0), block_k, xp), end)
    if window is None:
        return 0, 0, bare_end, end
    # ... and its first key inside the window of the last query
    first = _div(xp.maximum(first_q - window + 1, 0), block_k, xp)
    bare_first = xp.clip(
        _div(xp.maximum(last_q - window + 1, 0) + block_k - 1, block_k, xp),
        first, end)
    return first, bare_first, xp.maximum(bare_end, bare_first), end


def _query_bounds(kb, block_q: int, block_k: int, seq_q: int,
                  causal_offset: int, causal: bool, window: Optional[int],
                  xp=jnp):
    """The query blocks of key block ``kb`` (the dK/dV kernel's loop), as
    ``_key_bounds`` has a row's: the same tiles, walked down a column."""
    nq = seq_q // block_q
    if not causal:
        return 0, 0, nq, nq
    # as query rows: row i sits at position causal_offset + i
    first_k = kb * block_k - causal_offset
    last_k = first_k + block_k - 1
    first = xp.minimum(nq, _div(xp.maximum(first_k, 0), block_q, xp))
    # interior: the tile's first query at or after the last key ...
    bare_first = xp.minimum(
        nq, _div(xp.maximum(last_k, 0) + block_q - 1, block_q, xp))
    if window is None:
        return first, bare_first, nq, nq
    # ... and its last query inside the window of the first key
    end = xp.clip(
        _div(xp.maximum(last_k + window, 0) + block_q - 1, block_q, xp),
        first, nq)
    bare_first = xp.minimum(bare_first, end)
    bare_end = xp.clip(_div(xp.maximum(first_k + window, 0), block_q, xp),
                       bare_first, end)
    return first, bare_first, bare_end, end


def tile_plan(seq_q: int, seq_k: int, block_q: int, block_k: int,
              window: Optional[int] = None, causal: bool = True) -> dict:
    """What the kernels' loops do for one head, from shapes alone: the
    tiles they visit, the edge tiles among them (those the diagonal or the
    band's far edge cuts: the others are interior) and the share of the
    visited score elements that the mask keeps. Counted from
    ``_key_bounds``, the bounds the forward and dQ loops run;
    ``_query_bounds`` walks the same tiles by column."""
    import numpy as np

    first, bare_first, bare_end, end = (
        np.broadcast_to(b, (seq_q // block_q,)) for b in _key_bounds(
            np.arange(seq_q // block_q), block_q, block_k, seq_k,
            seq_k - seq_q, causal, window, xp=np))
    visited = int(np.sum(end - first))
    kept = seq_q * seq_k
    if causal:
        pos = seq_k - seq_q + np.arange(seq_q)      # a query sees (lo, pos]
        lo = -1 if window is None else pos - window
        kept = int(np.sum(np.maximum(pos - np.maximum(lo, -1), 0)))
    return {"tiles_visited": visited,
            "tiles_edge": visited - int(np.sum(bare_end - bare_first)),
            "kept_share": kept / max(1, visited * block_q * block_k)}


def _mask(s, first_q, first_k, window: Optional[int], q_axis: int = 0):
    """A tile's scores with what its queries do not see at ``_NEG_INF``.
    The tile's first query sits at position ``first_q`` and its first key
    at ``first_k`` (scalars); queries lie along ``q_axis`` of ``s``."""
    d = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
         - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    shift = first_q - first_k           # q_pos - k_pos = d + shift
    seen = d >= -shift
    if window is not None:
        seen &= d < window - shift
    return jnp.where(seen, s, _NEG_INF)


# A query block's softmax statistics (logsumexp; the backward's delta) lie
# in HBM as rows, [b*h, seq_q // block_q, block_q]: a [seq_q, 1] column is
# tiled to 128 lanes there, 128 times its bytes, and XLA spends a pass over
# all of them to squeeze it to the row it saves or to make it from one
# (measured on v5e, PR 31: 14 of the 22 ms a step that
# ``train-laguna-1chip`` spent beside its kernels). The forward and dQ
# kernels want them beside their [block_q, block_k] tiles as columns and
# turn them themselves, one small transpose a grid step (a plain
# ``reshape`` of the row costs four times as much).


def _row(col):
    """A [block_q, 1] column as a [1, block_q] row."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _cols(*rows):
    """[1, block_q] rows as [block_q, 1] columns: stacked to the eight
    sublanes of one tile and turned together."""
    pad = jnp.zeros((8 - len(rows), rows[0].shape[1]), rows[0].dtype)
    turned = jnp.concatenate(rows + (pad,), axis=0).T       # [block_q, 8]
    return [turned[:, i:i + 1] for i in range(len(rows))]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, sm_scale: float, seq_k: int, block_q: int,
                  causal_offset: int = 0, window: Optional[int] = None):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, seq_k, d]; o_ref: [1, block_q, d]
    # lse_ref: [1, seq_q // block_q, block_q], the head's per-row logsumexp
    # of the scaled scores (the only extra forward state the FA-2 backward
    # needs); the block stays while the head's query blocks write their
    # rows.
    # causal_offset = seq_k - seq_q: query row i sits at absolute key
    # position offset + i (decode/chunked-prefill alignment, matching
    # attention_reference).
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [block_q, d]
    d = q.shape[-1]
    first_q = causal_offset + qb * block_q

    def body(kb, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            s = _mask(s, first_q, kb * block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    init = (
        jnp.zeros((block_q, d), jnp.float32),
        jnp.full((block_q, 1), _NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
    )
    first, _, _, end = _key_bounds(qb, block_q, block_k, seq_k,
                                   causal_offset, causal, window)
    acc, m, l = jax.lax.fori_loop(first, end, body, init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, pl.ds(qb, 1), :] = _row(m + jnp.log(l_safe))


def _check_blocks(sq, sk, block_q, block_k):
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq}, {sk}) must be divisible by blocks "
            f"({block_q}, {block_k}); pad inputs first"
        )
    return block_q, block_k


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window=None):
    """q: [b, sq, h, d]; k/v: [b, sk, kvh, d] → ([b, sq, h, d], lse[b*h, sq]),
    the logsumexp written as one row a query block."""
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh

    # [b*h, s, d] layout for the kernel
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)

    def q_map(i, qb):
        return (i, qb, 0)

    def kv_map(i, qb):
        batch = i // h
        head = i % h
        return (batch * kvh + head // group, 0, 0)

    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, sm_scale=sm_scale,
        seq_k=sk, block_q=block_q, causal_offset=sk - sq, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        # the window calls carry names of their own, here and below, so a
        # trace tells them from the causal calls of the same step
        name="flash_fwd" if window is None else "flash_win_fwd",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq // block_q, block_q),
                                 jnp.float32),
        ],
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, sk, d), kv_map),
            pl.BlockSpec((1, sk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, sq // block_q, block_q),
                         lambda i, qb: (i, 0, 0)),
        ],
        interpret=interpret,
        **_dkv_vmem(sk, d, k.dtype, tile=4 << 20),
    )(qt, kt, vt)
    return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
            lse.reshape(b * h, sq))


# ---------------------------------------------------------------- pallas bwd
#
# FlashAttention-2 split backward: a query-block kernel for dQ and a
# key-block kernel for dK/dV, both recomputing P = exp(S - lse) tile by tile
# in VMEM. delta = rowsum(dO ⊙ O) is a cheap fused elementwise reduction
# left to XLA outside the kernels.


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         sm_scale: float, seq_k: int, block_q: int,
                         causal_offset: int, window: Optional[int] = None):
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                     # [block_q, d]
    do = do_ref[0].astype(jnp.float32)                   # [block_q, d]
    lse, delta = _cols(lse_ref[0, pl.ds(qb, 1), :],      # [block_q, 1]
                       delta_ref[0, pl.ds(qb, 1), :])
    d = q.shape[-1]
    first_q = causal_offset + qb * block_q

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                     # [block_q, block_k]
        if causal:
            s = _mask(s, first_q, kb * block_k, window)
        p = jnp.exp(s - lse)                             # [block_q, block_k]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_q, block_k]
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    first, _, _, end = _key_bounds(qb, block_q, block_k, seq_k,
                                   causal_offset, causal, window)
    dq = jax.lax.fori_loop(
        first, end, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          sm_scale: float, seq_q: int, block_k: int,
                          causal_offset: int, window: Optional[int] = None):
    # The tiles are the transposes of the other kernels', keys down and
    # queries across: S^T = K Q^T, dV += P^T dO and dK += dS^T Q are then
    # plain row-by-column products. On [block_q, block_k] tiles the last
    # two contract the tile's rows, and Mosaic transposes P and dS (two
    # 512 x 512 float32 transposes a tile) before it can: measured on v5e,
    # 25.4 -> 22.1 ms at 2 x 8,192 x 48 heads and 11.6 -> 9.3 ms for the
    # 512-key band at 72. A query block's statistics are a row of lse_ref
    # and delta_ref, which a [block_k, block_q] tile takes as it lies.
    import jax.experimental.pallas as pl

    kb = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)                 # [block_k, d]
    v_blk = v_ref[0].astype(jnp.float32)                 # [block_k, d]
    d = k_blk.shape[-1]

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb, 1), :]                # [1, block_q]
        delta = delta_ref[0, pl.ds(qb, 1), :]            # [1, block_q]
        s = jax.lax.dot_general(
            k_blk, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                     # [block_k, block_q]
        if causal:
            s = _mask(s, causal_offset + qb * block_q, kb * block_k, window,
                      q_axis=1)
        p = jnp.exp(s - lse)                             # [block_k, block_q]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_k, d]
        dp = jax.lax.dot_general(
            v_blk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_k, block_q]
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_k, d]
        return dk_new, dv_new

    first, _, _, end = _query_bounds(kb, block_q, block_k, seq_q,
                                     causal_offset, causal, window)
    dk, dv = jax.lax.fori_loop(
        first, end, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


_SCOPED_VMEM = 16 << 20        # Mosaic's default limit for one kernel


def _dkv_vmem(sq: int, d: int, dtype, tile: int = 8 << 20) -> dict:
    """The dK/dV kernel holds a head's whole q and dO, each twice for the
    pipeline (8.4 MB at 8,192 queries; the two statistics are rows of a
    few KB), beside some 7 MB of a tile's float32 temporaries: past
    Mosaic's default limit of 16 MB (v5e has 128 MiB) the call asks for
    what it needs; under it the call is the one it always was. The
    forward and the dQ kernel hold a head's whole k and v the same way
    beside less of a tile (``tile``: 4 MB). VMEM pads a row of fewer than
    128 lanes to 128: a head of 64 holds twice what it counts (33.5 MB at
    32,768 keys, read off the TPU compiler's refusals, PR 36), and the
    limit asked for is of what is held."""
    need = 2 * 2 * sq * d * jnp.dtype(dtype).itemsize
    held = need * max(1, 128 // d)
    if need + tile <= _SCOPED_VMEM and held + tile // 2 <= _SCOPED_VMEM:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=held + tile + (4 << 20))}


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                    interpret, window=None):
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    dot = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = dO_i · O_i  (rowwise), the softmax-jacobian correction term.
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)                             # [b*h, sq]
    stat_rows = (b * h, sq // block_q, block_q)      # a query block a row
    lse, delta = lse.reshape(stat_rows), delta.reshape(stat_rows)

    def q_map(i, qb):
        return (i, qb, 0)

    def kv_map(i, qb):
        batch = i // h
        head = i % h
        return (batch * kvh + head // group, 0, 0)

    def full_q_map(i, kb):
        return (i, 0, 0)

    stat_spec = pl.BlockSpec((1,) + stat_rows[1:], full_q_map)

    def k_map(i, kb):
        batch = i // h
        head = i % h
        return (batch * kvh + head // group, kb, 0)

    causal_offset = sk - sq
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, causal=causal,
            sm_scale=sm_scale, seq_k=sk, block_q=block_q,
            causal_offset=causal_offset, window=window),
        name="flash_bwd_dq" if window is None else "flash_win_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, sk, d), kv_map),
            pl.BlockSpec((1, sk, d), kv_map),
            pl.BlockSpec((1, block_q, d), q_map),
            stat_spec,
            stat_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        interpret=interpret,
        **_dkv_vmem(sk, d, k.dtype, tile=4 << 20),
    )(qt, kt, vt, dot, lse, delta)

    # dK/dV are computed per *query* head (grid over b*h) and reduced over
    # the GQA group afterwards — the group sum is a cheap XLA reduction and
    # keeps the kernel free of cross-program accumulation.
    dk_per, dv_per = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, causal=causal,
            sm_scale=sm_scale, seq_q=sq, block_k=block_k,
            causal_offset=causal_offset, window=window),
        name="flash_bwd_dkv" if window is None else "flash_win_bwd_dkv",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        grid=(b * h, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), full_q_map),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, sq, d), full_q_map),
            stat_spec,
            stat_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kb: (i, kb, 0)),
        ],
        interpret=interpret,
        **_dkv_vmem(sq, d, q.dtype),
    )(qt, kt, vt, dot, lse, delta)

    dq = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    # Sum query heads within each KV group: head = kv*group + g.
    dk = dk_per.reshape(b, kvh, group, sk, d).sum(axis=2)
    dv = dv_per.reshape(b, kvh, group, sk, d).sum(axis=2)
    dk = dk.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret, window):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret, window)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               window):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                              interpret, window)
    # Named where they are born, for a layer's remat policy
    # (models/llama.py REMAT_LADDER): a policy that keeps both drops the
    # backward's second run of the forward kernel; a name given outside
    # this rule would miss the residual. Inert under a plain
    # jax.checkpoint and outside one.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, window, res,
               g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q,
                           block_k, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False,
                    window: Optional[int] = None,
                    k_shared: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention. Layout: q [b, sq, heads, d]; k/v [b, sk, kv_heads, d].

    ``window`` (with ``causal``): a query sees the ``window`` keys that
    end at its own position; the kernels visit the band's key blocks
    alone, so their time grows with the window and not with the sequence.

    ``use_pallas=None`` auto-selects: the Pallas kernel on TPU backends, the
    reference path elsewhere (tests force the kernel with interpret=True).
    ``block_q``/``block_k`` default to the largest power-of-two divisor of
    the sequence length up to 512 (``_auto_block``). A call on the kernel
    path writes one kept span as it is traced, ``rtpu.flash.tiles``: its
    blocks, the head size and ``tile_plan``'s count of what its loops will
    visit.

    Keys and values may differ in width: q, k ``[.., d_k]``, v ``[..,
    d_v]`` give ``[b, sq, heads, d_v]`` through kernels of their own
    (``flash_kv_*``, below), and so does ``k_shared [b, sk, d_s]``, a part
    of every head's key that all heads share (latent attention's rope
    dims): k is then ``[.., d_k - d_s]`` and the last ``d_s`` dims of a
    query meet ``k_shared``. The default scale is ``d_k ** -0.5``.
    """
    _check_window(causal, window)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        use_pallas = jax.default_backend() not in ("cpu",)
    if not use_pallas:
        if k_shared is not None:
            k = with_shared_key(k, k_shared)
        return attention_reference(q, k, v, causal, sm_scale, window)
    if k_shared is not None or q.shape[-1] != v.shape[-1]:
        return _flash_kv(q, k, v, k_shared, causal, sm_scale, block_q,
                         block_k, interpret, window)
    if block_q is None:
        block_q = _auto_block(q.shape[1])
    if block_k is None:
        block_k = _auto_block(k.shape[1])
    block_q, block_k = _check_blocks(q.shape[1], k.shape[1], block_q, block_k)
    with tracing.span("rtpu.flash.tiles", keep=True, seq_q=q.shape[1],
                      seq_k=k.shape[1], block_q=block_q, block_k=block_k,
                      window=window, head_dim=q.shape[-1],
                      **tile_plan(q.shape[1], k.shape[1], block_q, block_k,
                                  window, causal)):
        pass
    return _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                  window)


# ------------------------------------------------ keys wider than values
#
# Latent attention (``ops/mla.py``) scores with keys of 128 + 64 dims, the
# 64 a rotated vector that every head shares, against values of 128. The
# kernels below are the three above with the widths apart: the scores are
# the sum of two products, ``q[:, :d_m] k^T`` and ``q[:, d_m:] kx^T`` (kx:
# ``k_shared``, fetched once a batch row, or absent), so every operand is
# 128 or 64 lanes wide, no key is broadcast to the heads in HBM and no
# value is padded; the accumulators and ``dV`` are ``d_v`` wide. They are
# kernels of their own, named ``flash_kv_*``, and not the ones above made
# general: the text of a call at equal widths stays what it was, for the
# programs that were measured with it.


def with_shared_key(k: jax.Array, k_shared: jax.Array) -> jax.Array:
    """k [b, sk, kvh, d_m] and the part all heads share [b, sk, d_s] ->
    whole keys [b, sk, kvh, d_m + d_s]: what the kernels never build."""
    return jnp.concatenate(
        [k, jnp.broadcast_to(k_shared[:, :, None, :],
                             k.shape[:3] + k_shared.shape[-1:])], axis=-1)


def _scores(q, qx, k, kx, transposed: bool = False):
    """A tile's scores from its one or two products: [block_q, block_k],
    or ``transposed`` (keys down) [block_k, block_q]."""
    def dot(a, b):
        a, b = (b, a) if transposed else (a, b)
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return dot(q, k) if qx is None else dot(q, k) + dot(qx, kx)


def _dot(a, b, contract=(1, 0)):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_kv_kernel(*refs, shared: bool, block_k: int, causal: bool,
                     sm_scale: float, seq_k: int, block_q: int,
                     causal_offset: int, window: Optional[int]):
    # refs: q [1, block_q, d_m], (qx [1, block_q, d_s]), k [1, seq_k, d_m],
    # (kx [1, seq_k, d_s]), v [1, seq_k, d_v]; o [1, block_q, d_v], lse
    import jax.experimental.pallas as pl

    if shared:
        q_ref, qx_ref, k_ref, kx_ref, v_ref, o_ref, lse_ref = refs
        qx = qx_ref[0].astype(jnp.float32) * sm_scale
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        qx = None
    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale
    first_q = causal_offset + qb * block_q

    def body(kb, carry):
        acc, m, l = carry
        keys = pl.ds(kb * block_k, block_k)
        s = _scores(q, qx, k_ref[0, keys, :].astype(jnp.float32),
                    kx_ref[0, keys, :].astype(jnp.float32) if shared
                    else None)
        if causal:
            s = _mask(s, first_q, kb * block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + _dot(
            p, v_ref[0, keys, :].astype(jnp.float32))
        return acc_new, m_new, l_new

    init = (jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32),
            jnp.full((block_q, 1), _NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32))
    first, _, _, end = _key_bounds(qb, block_q, block_k, seq_k,
                                   causal_offset, causal, window)
    acc, m, l = jax.lax.fori_loop(first, end, body, init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, pl.ds(qb, 1), :] = _row(m + jnp.log(l_safe))


def _flash_kv_dq_kernel(*refs, shared: bool, block_k: int, causal: bool,
                        sm_scale: float, seq_k: int, block_q: int,
                        causal_offset: int, window: Optional[int]):
    import jax.experimental.pallas as pl

    if shared:
        (q_ref, qx_ref, k_ref, kx_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dqx_ref) = refs
        qx = qx_ref[0].astype(jnp.float32)
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
        qx = None
    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                   # [block_q, d_v]
    lse, delta = _cols(lse_ref[0, pl.ds(qb, 1), :],
                       delta_ref[0, pl.ds(qb, 1), :])
    first_q = causal_offset + qb * block_q

    def body(kb, carry):
        dq, dqx = carry
        keys = pl.ds(kb * block_k, block_k)
        k_blk = k_ref[0, keys, :].astype(jnp.float32)
        kx_blk = kx_ref[0, keys, :].astype(jnp.float32) if shared else None
        s = _scores(q, qx, k_blk, kx_blk) * sm_scale
        if causal:
            s = _mask(s, first_q, kb * block_k, window)
        p = jnp.exp(s - lse)
        dp = _dot(do, v_ref[0, keys, :].astype(jnp.float32), (1, 1))
        ds = p * (dp - delta)
        return (dq + _dot(ds, k_blk),
                dqx + _dot(ds, kx_blk) if shared else dqx)

    first, _, _, end = _key_bounds(qb, block_q, block_k, seq_k,
                                   causal_offset, causal, window)
    dq, dqx = jax.lax.fori_loop(
        first, end, body,
        (jnp.zeros(q.shape, jnp.float32),
         jnp.zeros(qx.shape if shared else (1, 1), jnp.float32)))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)
    if shared:
        dqx_ref[0] = (dqx * sm_scale).astype(dqx_ref.dtype)


def _flash_kv_dkv_kernel(*refs, shared: bool, block_q: int, causal: bool,
                         sm_scale: float, seq_q: int, block_k: int,
                         causal_offset: int, window: Optional[int]):
    # keys down and queries across, as ``_flash_bwd_dkv_kernel``
    import jax.experimental.pallas as pl

    if shared:
        (q_ref, qx_ref, k_ref, kx_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dkx_ref, dv_ref) = refs
        kx_blk = kx_ref[0].astype(jnp.float32)
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref) = refs
        kx_blk = None
    kb = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)                 # [block_k, d_m]
    v_blk = v_ref[0].astype(jnp.float32)                 # [block_k, d_v]

    def body(qb, carry):
        dk, dkx, dv = carry
        rows = pl.ds(qb * block_q, block_q)
        q = q_ref[0, rows, :].astype(jnp.float32)
        qx = qx_ref[0, rows, :].astype(jnp.float32) if shared else None
        do = do_ref[0, rows, :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb, 1), :]                # [1, block_q]
        delta = delta_ref[0, pl.ds(qb, 1), :]
        s = _scores(q, qx, k_blk, kx_blk, transposed=True) * sm_scale
        if causal:
            s = _mask(s, causal_offset + qb * block_q, kb * block_k, window,
                      q_axis=1)
        p = jnp.exp(s - lse)                             # [block_k, block_q]
        dv_new = dv + _dot(p, do)
        ds = p * (_dot(v_blk, do, (1, 1)) - delta)
        return (dk + _dot(ds, q), dkx + _dot(ds, qx) if shared else dkx,
                dv_new)

    first, _, _, end = _query_bounds(kb, block_q, block_k, seq_q,
                                     causal_offset, causal, window)
    dk, dkx, dv = jax.lax.fori_loop(
        first, end, body,
        (jnp.zeros(k_blk.shape, jnp.float32),
         jnp.zeros(kx_blk.shape if shared else (1, 1), jnp.float32),
         jnp.zeros(v_blk.shape, jnp.float32)))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    if shared:
        dkx_ref[0] = (dkx * sm_scale).astype(dkx_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _held_vmem(seq: int, widths, dtype, tile: int) -> dict:
    """``_dkv_vmem`` for a kernel that holds a head's whole rows of
    several arrays, ``widths`` their last dims (each padded to 128 lanes
    in VMEM), twice for the pipeline, beside ``tile`` bytes of a tile's
    temporaries."""
    held = 2 * seq * jnp.dtype(dtype).itemsize * sum(
        -(-w // 128) * 128 for w in widths)
    if held + tile <= _SCOPED_VMEM:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=held + tile + (4 << 20))}


def _heads_first(x):
    """[b, s, h, d] -> [b * h, s, d], the kernels' layout."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _kv_specs(pl, h: int, kvh: int, sk: int, widths, block_k=None):
    """Maps of a grid ``(b * h, block)``: (a key or value head's rows
    whole or, with ``block_k``, the grid's block of them: one spec a width
    of ``widths``; the shared key's, one row of the batch)."""
    def kv_map(i, j):
        return (i // h * kvh + i % h // (h // kvh), j if block_k else 0, 0)

    def shared_map(i, j):
        return (i // h, j if block_k else 0, 0)

    rows = block_k or sk
    return ([pl.BlockSpec((1, rows, w), kv_map) for w in widths],
            lambda w: pl.BlockSpec((1, rows, w), shared_map))


def _flash_kv_forward(q, k, v, kx, causal, sm_scale, block_q, block_k,
                      interpret, window):
    """q [b, sq, h, d_m + d_s]; k [b, sk, kvh, d_m]; v [b, sk, kvh, d_v];
    kx [b, sk, d_s] or None -> (out [b, sq, h, d_v], lse [b * h, sq])."""
    import jax.experimental.pallas as pl

    b, sq, h, _ = q.shape
    _, sk, kvh, d_m = k.shape
    d_v, shared = v.shape[-1], kx is not None
    d_s = kx.shape[-1] if shared else 0
    qt, kt, vt = _heads_first(q), _heads_first(k), _heads_first(v)

    def q_spec(w):
        return pl.BlockSpec((1, block_q, w), lambda i, qb: (i, qb, 0))

    (k_spec, v_spec), kx_spec = _kv_specs(pl, h, kvh, sk, (d_m, d_v))
    ins = ([qt[..., :d_m], qt[..., d_m:], kt, kx, vt] if shared
           else [qt, kt, vt])
    specs = ([q_spec(d_m), q_spec(d_s), k_spec, kx_spec(d_s), v_spec]
             if shared else [q_spec(d_m), k_spec, v_spec])
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kv_kernel, shared=shared, block_k=block_k, causal=causal,
            sm_scale=sm_scale, seq_k=sk, block_q=block_q,
            causal_offset=sk - sq, window=window),
        name="flash_kv_fwd",
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq // block_q, block_q),
                                 jnp.float32)],
        grid=(b * h, sq // block_q),
        in_specs=specs,
        out_specs=[q_spec(d_v),
                   pl.BlockSpec((1, sq // block_q, block_q),
                                lambda i, qb: (i, 0, 0))],
        interpret=interpret,
        **_held_vmem(sk, (d_m, d_s, d_v) if shared else (d_m, d_v), k.dtype,
                     tile=4 << 20),
    )(*ins)
    return (out.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3),
            lse.reshape(b * h, sq))


def _flash_kv_backward(q, k, v, kx, out, lse, g, causal, sm_scale, block_q,
                       block_k, interpret, window):
    import jax.experimental.pallas as pl

    b, sq, h, d_q = q.shape
    _, sk, kvh, d_m = k.shape
    d_v, shared = v.shape[-1], kx is not None
    d_s = d_q - d_m if shared else 0
    group = h // kvh
    qt, kt, vt = _heads_first(q), _heads_first(k), _heads_first(v)
    dot, ot = _heads_first(g), _heads_first(out)
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)
    stat_rows = (b * h, sq // block_q, block_q)
    lse, delta = lse.reshape(stat_rows), delta.reshape(stat_rows)
    stat_spec = pl.BlockSpec((1,) + stat_rows[1:], lambda i, j: (i, 0, 0))

    def q_spec(w, whole=False):
        if whole:
            return pl.BlockSpec((1, sq, w), lambda i, kb: (i, 0, 0))
        return pl.BlockSpec((1, block_q, w), lambda i, qb: (i, qb, 0))

    def k_out(w):
        return pl.BlockSpec((1, block_k, w), lambda i, kb: (i, kb, 0))

    static = dict(shared=shared, causal=causal, sm_scale=sm_scale,
                  causal_offset=sk - sq, window=window, block_q=block_q,
                  block_k=block_k)
    qs = [qt[..., :d_m], qt[..., d_m:]] if shared else [qt]
    ks = [kt, kx] if shared else [kt]
    q_widths = (d_m, d_s) if shared else (d_m,)

    (k_spec, v_spec), kx_spec = _kv_specs(pl, h, kvh, sk, (d_m, d_v))
    dq = pl.pallas_call(
        functools.partial(_flash_kv_dq_kernel, seq_k=sk, **static),
        name="flash_kv_bwd_dq",
        out_shape=[jax.ShapeDtypeStruct((b * h, sq, w), q.dtype)
                   for w in q_widths],
        grid=(b * h, sq // block_q),
        in_specs=[q_spec(w) for w in q_widths]
        + [k_spec] + [kx_spec(d_s)] * shared
        + [v_spec, q_spec(d_v), stat_spec, stat_spec],
        out_specs=[q_spec(w) for w in q_widths],
        interpret=interpret,
        **_held_vmem(sk, (d_m, d_s, d_v) if shared else (d_m, d_v), k.dtype,
                     tile=4 << 20),
    )(*qs, *ks, vt, dot, lse, delta)
    dq = jnp.concatenate(dq, axis=-1) if shared else dq[0]

    # dK, dV and the shared key's gradient by *query* head, summed over
    # the heads outside as the kernels above sum a GQA group: the shared
    # key's sum is over every head, so its parts are float32
    (k_spec, v_spec), kx_spec = _kv_specs(pl, h, kvh, sk, (d_m, d_v),
                                          block_k=block_k)
    per = pl.pallas_call(
        functools.partial(_flash_kv_dkv_kernel, seq_q=sq, **static),
        name="flash_kv_bwd_dkv",
        out_shape=[jax.ShapeDtypeStruct((b * h, sk, d_m), k.dtype)]
        + [jax.ShapeDtypeStruct((b * h, sk, d_s), jnp.float32)] * shared
        + [jax.ShapeDtypeStruct((b * h, sk, d_v), v.dtype)],
        grid=(b * h, sk // block_k),
        in_specs=[q_spec(w, whole=True) for w in q_widths]
        + [k_spec] + [kx_spec(d_s)] * shared
        + [v_spec, q_spec(d_v, whole=True), stat_spec, stat_spec],
        out_specs=[k_out(d_m)] + [k_out(d_s)] * shared + [k_out(d_v)],
        interpret=interpret,
        **_held_vmem(sq, q_widths + (d_v,), q.dtype, tile=8 << 20),
    )(*qs, *ks, vt, dot, lse, delta)

    def by_kv_head(x, like):
        x = x.reshape(b, kvh, group, sk, x.shape[-1]).sum(axis=2)
        return x.transpose(0, 2, 1, 3).astype(like.dtype)

    dq = dq.reshape(b, h, sq, d_q).transpose(0, 2, 1, 3).astype(q.dtype)
    dkx = (per[1].reshape(b, h, sk, d_s).sum(axis=1).astype(kx.dtype)
           if shared else None)
    return dq, by_kv_head(per[0], k), by_kv_head(per[-1], v), dkx


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_kv_call(q, k, v, kx, causal, sm_scale, block_q, block_k,
                   interpret, window):
    return _flash_kv_forward(q, k, v, kx, causal, sm_scale, block_q,
                             block_k, interpret, window)[0]


def _flash_kv_fwd(q, k, v, kx, causal, sm_scale, block_q, block_k,
                  interpret, window):
    out, lse = _flash_kv_forward(q, k, v, kx, causal, sm_scale, block_q,
                                 block_k, interpret, window)
    # named as ``_flash_fwd`` names them, for the same rung of the ladder
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, kx, out, lse)


def _flash_kv_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                  res, g):
    return _flash_kv_backward(*res, g, causal, sm_scale, block_q, block_k,
                              interpret, window)


_flash_kv_call.defvjp(_flash_kv_fwd, _flash_kv_bwd)


def _flash_kv(q, k, v, k_shared, causal, sm_scale, block_q, block_k,
              interpret, window):
    """``flash_attention`` on the kernel path where the widths of keys and
    values differ or a part of the key is shared by the heads."""
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    if q.shape[-1] != k.shape[-1] + d_s or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"queries {q.shape} do not meet keys {k.shape}"
            + (f" with a shared part {k_shared.shape}" if d_s else ""))
    block_q, block_k = _check_blocks(
        q.shape[1], k.shape[1], block_q or _auto_block(q.shape[1]),
        block_k or _auto_block(k.shape[1]))
    with tracing.span("rtpu.flash.tiles", keep=True, seq_q=q.shape[1],
                      seq_k=k.shape[1], block_q=block_q, block_k=block_k,
                      window=window, head_dim=q.shape[-1],
                      value_dim=v.shape[-1], shared_key_dim=d_s,
                      **tile_plan(q.shape[1], k.shape[1], block_q, block_k,
                                  window, causal)):
        pass
    return _flash_kv_call(q, k, v, k_shared, causal, sm_scale, block_q,
                          block_k, interpret, window)
