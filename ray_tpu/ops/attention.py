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

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _auto_block(seq: int, target: int) -> int:
    """Largest power-of-two block <= target that divides seq (measured on
    v5e: 512 blocks are ~2-3x faster than 128 at long seq — MXU stays fed
    and the online-softmax VPU work amortizes)."""
    c = target
    while c > 128:
        if seq % c == 0:
            return c
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
# With a ``window`` a kernel skips the key blocks behind the band as the
# causal path skips those ahead of the diagonal: its loop starts at the
# first key block that holds a key inside the window of the block's first
# query (``_first_key_block``). A later query of the block may see nothing
# of that block: its row is all ``_NEG_INF`` there, the running maximum
# stays ``_NEG_INF`` and what it sums is wiped by ``alpha = 0`` at the next
# block, which holds the query's own position.


def _seen(q_pos, k_pos, window: Optional[int]):
    """The causal mask, and the window's where there is one."""
    mask = q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


def _first_key_block(qb, block_q: int, block_k: int, causal_offset: int,
                     window: Optional[int]):
    """The first key block the loop of query block ``qb`` visits."""
    if window is None:
        return 0
    first_q = causal_offset + qb * block_q
    return jnp.maximum(0, jax.lax.div(first_q - (window - 1), block_k))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, sm_scale: float, seq_k: int, block_q: int,
                  causal_offset: int = 0, window: Optional[int] = None):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, seq_k, d]; o_ref: [1, block_q, d]
    # lse_ref: [1, block_q] per-row logsumexp of the scaled scores (the only
    # extra forward state the FA-2 backward needs).
    # causal_offset = seq_k - seq_q: query row i sits at absolute key
    # position offset + i (decode/chunked-prefill alignment, matching
    # attention_reference).
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [block_q, d]
    d = q.shape[-1]

    num_kv_blocks = seq_k // block_k
    if causal:
        # only blocks whose start is <= the last query's absolute position
        last_q = causal_offset + (qb + 1) * block_q - 1

    def body(kb, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            qi = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            ki = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = _seen(causal_offset + qb * block_q + qi,
                         kb * block_k + ki, window)
            s = jnp.where(mask, s, _NEG_INF)
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
    if causal:
        upper = jax.lax.div(last_q, block_k) + 1
    else:
        upper = num_kv_blocks
    lower = _first_key_block(qb, block_q, block_k, causal_offset, window)
    acc, m, l = jax.lax.fori_loop(lower, upper, body, init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)  # [block_q, 1]


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
    """q: [b, sq, h, d]; k/v: [b, sk, kvh, d] → ([b, sq, h, d], lse[b*h, sq, 1]).

    The logsumexp rides in a trailing singleton lane dim — TPU block shapes
    need the last dim divisible by 128 *or* equal to the array dim, and a
    1-lane column costs 128x less HBM than broadcasting to MIN_BLOCK_SIZE
    lanes the way jax's in-tree kernel stores l/m."""
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh
    block_q, block_k = _check_blocks(sq, sk, block_q, block_k)

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
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, sk, d), kv_map),
            pl.BlockSpec((1, sk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse


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
    lse = lse_ref[0]                                     # [block_q, 1]
    delta = delta_ref[0]                                 # [block_q, 1]
    d = q.shape[-1]

    num_kv_blocks = seq_k // block_k
    if causal:
        last_q = causal_offset + (qb + 1) * block_q - 1

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                     # [block_q, block_k]
        if causal:
            qi = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            ki = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = _seen(causal_offset + qb * block_q + qi,
                         kb * block_k + ki, window)
            s = jnp.where(mask, s, _NEG_INF)
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

    upper = jax.lax.div(last_q, block_k) + 1 if causal else num_kv_blocks
    lower = _first_key_block(qb, block_q, block_k, causal_offset, window)
    dq = jax.lax.fori_loop(
        lower, upper, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          sm_scale: float, seq_q: int, block_k: int,
                          causal_offset: int, window: Optional[int] = None):
    import jax.experimental.pallas as pl

    kb = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)                 # [block_k, d]
    v_blk = v_ref[0].astype(jnp.float32)                 # [block_k, d]
    d = k_blk.shape[-1]

    num_q_blocks = seq_q // block_q

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]   # [block_q, 1]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                     # [block_q, block_k]
        if causal:
            qi = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            ki = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            mask = _seen(causal_offset + qb * block_q + qi,
                         kb * block_k + ki, window)
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse)                             # [block_q, block_k]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_k, d]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_q, block_k]
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # [block_k, d]
        return dk_new, dv_new

    if causal:
        # first q row that can see this key block: qrow >= k_start - offset
        lower = jnp.maximum(
            0, jax.lax.div(kb * block_k - causal_offset, block_q))
    else:
        lower = 0
    upper = num_q_blocks
    if window is not None:
        # the last q row that sees the block's last key: its position
        # less than that key's plus the window
        last_q = (kb + 1) * block_k - 1 + window - 1 - causal_offset
        upper = jnp.minimum(upper, jax.lax.div(last_q, block_q) + 1)
    dk, dv = jax.lax.fori_loop(
        lower, upper, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


_SCOPED_VMEM = 16 << 20        # Mosaic's default limit for one kernel


def _dkv_vmem(sq: int, d: int, dtype) -> dict:
    """The dK/dV kernel holds a head's whole q and dO and the two [sq, 1]
    float32 columns (128 lanes wide in VMEM), each twice for the pipeline:
    12 MB at 4,096 queries, 24.5 at 8,192, over Mosaic's default limit of
    16 (v5e has 128 MiB). Past the default the call asks for what it
    needs; under it the call is the one it always was."""
    need = 2 * (2 * sq * d * jnp.dtype(dtype).itemsize + 2 * sq * 128 * 4)
    if need + (2 << 20) <= _SCOPED_VMEM:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + (8 << 20))}


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
                    interpret, window=None):
    import jax.experimental.pallas as pl

    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh
    block_q, block_k = _check_blocks(sq, sk, block_q, block_k)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    dot = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = dO_i · O_i  (rowwise), the softmax-jacobian correction term.
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [b*h, sq, 1]

    def q_map(i, qb):
        return (i, qb, 0)

    def kv_map(i, qb):
        batch = i // h
        head = i % h
        return (batch * kvh + head // group, 0, 0)

    def full_q_map(i, kb):
        return (i, 0, 0)

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
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        interpret=interpret,
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
            pl.BlockSpec((1, sq, 1), full_q_map),
            pl.BlockSpec((1, sq, 1), full_q_map),
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
    # jax.checkpoint and outside one. The logsumexp is kept without its
    # singleton lane dim: as a saved [.., sq, 1] array XLA may tile it to
    # 128 lanes, 128 times its bytes.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, window, res,
               g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse[..., None], g, causal,
                           sm_scale, block_q, block_k, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention. Layout: q [b, sq, heads, d]; k/v [b, sk, kv_heads, d].

    ``window`` (with ``causal``): a query sees the ``window`` keys that
    end at its own position; the kernels visit the band's key blocks
    alone, so their time grows with the window and not with the sequence.

    ``use_pallas=None`` auto-selects: the Pallas kernel on TPU backends, the
    reference path elsewhere (tests force the kernel with interpret=True).
    ``block_q``/``block_k`` default to the largest power-of-two divisor of
    the sequence length up to 512.
    """
    _check_window(causal, window)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        use_pallas = jax.default_backend() not in ("cpu",)
    if not use_pallas:
        return attention_reference(q, k, v, causal, sm_scale, window)
    if block_q is None:
        block_q = _auto_block(q.shape[1], DEFAULT_BLOCK_Q)
    if block_k is None:
        block_k = _auto_block(k.shape[1], DEFAULT_BLOCK_K)
    return _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                  window)
