"""Paged-KV attention for serving decode: Pallas page-gather kernel.

vLLM-style paged KV re-thought for TPU (reference serves via torch/GPU
with no paging of its own; the vLLM PagedAttention paper is the public
analogue): the KV cache is a POOL of fixed-size pages ``[num_pages,
kv_heads, page_size, head_dim]`` shared by all sequences; each sequence
owns an ordered list of page ids (its block table). Decode attention for
slot s must read exactly s's pages — a data-dependent gather.

The XLA path (``paged_attention_reference``) materializes the gather:
pages → a dense [S, T] view → einsum. Correct everywhere (CPU,
GSPMD/tensor-parallel), but it writes the gathered copy to HBM before
reading it back — extra cache traffic the dense engine never pays.

The Pallas kernel streams pages straight from HBM into VMEM through the
BlockSpec pipeline: the grid walks (slot, kv_head, page), the page index
map reads the SCALAR-PREFETCHED block table, and an online-softmax
accumulator (flash-style m/l/acc scratch) folds each page as it arrives —
the gathered tensor never exists. Pages past a slot's context length are
clamped to the last valid page in the index map (no re-DMA: Pallas skips
the copy when consecutive grid steps map to the same block) and skipped
by ``pl.when``. The pool layout [P, KVH, page, hd] keeps (page, hd) as
the block's minor dims — the TPU tiling requirement (minor dims ÷(8,128)).

Both paths compute HISTORY attention only (positions < ctx_len); the
in-flight token's self-attention term is merged by the caller
(models/llama_paged.py) from the returned (acc, m, l) triple, mirroring
the dense decode design.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def paged_attention_reference(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              ctx_len: jax.Array,
                              sm_scale: Optional[float] = None):
    """History attention over paged KV, XLA gather path.

    q: [S, KVH, G, hd] (G = query heads per KV head, rope applied)
    k_pages/v_pages: [P, KVH, page, hd]
    block_table: [S, MAXP] int32 page ids (entries past a sequence's
        allocation may be arbitrary valid ids — they are masked)
    ctx_len: [S] int32 history length in tokens (EXCLUDING the in-flight
        token). Slots with ctx_len == 0 return zeros.
    Returns (acc f32 [S, KVH, G, hd], m [S, KVH, G], l [S, KVH, G]):
    the flash-style UN-normalized accumulator, row max, and softmax
    denominator over history only, so the caller can merge the in-flight
    token's self term exactly before normalizing.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    S, KVH, G, hd = q.shape
    page = k_pages.shape[2]
    MAXP = block_table.shape[1]
    T = MAXP * page
    # [S, MAXP, KVH, page, hd] -> [S, KVH, T, hd]
    ks = jnp.moveaxis(k_pages[block_table], 2, 1).reshape(S, KVH, T, hd)
    vs = jnp.moveaxis(v_pages[block_table], 2, 1).reshape(S, KVH, T, hd)
    scores = jnp.einsum("skgd,sktd->skgt", q, ks,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(T)[None] < ctx_len[:, None]          # [S, T]
    scores = jnp.where(mask[:, None, None], scores, _NEG_INF)
    m = jnp.max(scores, axis=-1)                           # [S, KVH, G]
    # all-masked rows (ctx 0): exp(-1e30 - -1e30) would be 1 — zero them
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(mask[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)                                # [S, KVH, G]
    acc = jnp.einsum("skgt,sktd->skgd", p.astype(vs.dtype), vs,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _paged_kernel(bt_ref, ctx_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref, mm_ref, ll_ref, *,
                  page: int, maxp: int, kvh: int, sm_scale: float):
    """Grid (S, MAXP); scratch acc [KVH*G, hd] f32, mm/ll [KVH*G, 1].

    q_ref: [1, KVH, G, hd]; k_ref/v_ref: [1, KVH, page, hd] — one whole
    page across ALL kv heads per step (一 ~512 KB DMA instead of KVH
    small ones; the per-head grid variant measured 30% slower at 1B).
    The KVH loop below is a python unroll over static slices.
    Outputs (written at the final page step): o [1,KVH,G,hd]
    un-normalized accumulator, m/l [1,KVH,G,1] row max and denominator.
    """
    import jax.experimental.pallas as pl

    s = pl.program_id(0)
    p = pl.program_id(1)
    ctx = ctx_ref[s]
    G = q_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mm_ref[...] = jnp.full_like(mm_ref, _NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    @pl.when(p * page < ctx)
    def _compute():
        pos = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (G, page), 1)
        valid = pos < ctx
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)            # [G, hd]
            k = k_ref[0, h].astype(jnp.float32)            # [page, hd]
            v = v_ref[0, h].astype(jnp.float32)            # [page, hd]
            s_blk = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s_blk = jnp.where(valid, s_blk, _NEG_INF)      # [G, page]
            row = slice(h * G, (h + 1) * G)
            m_old = mm_ref[row, :]
            m_new = jnp.maximum(m_old,
                                jnp.max(s_blk, axis=-1, keepdims=True))
            pr = jnp.exp(s_blk - m_new)
            pr = jnp.where(valid, pr, 0.0)
            alpha = jnp.exp(m_old - m_new)
            ll_ref[row, :] = ll_ref[row, :] * alpha + jnp.sum(
                pr, axis=-1, keepdims=True)
            acc_ref[row, :] = acc_ref[row, :] * alpha + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            mm_ref[row, :] = m_new

    @pl.when(p == maxp - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].reshape(kvh, G, -1).astype(o_ref.dtype)
        m_ref[0] = mm_ref[...].reshape(kvh, G, 1)
        l_ref[0] = ll_ref[...].reshape(kvh, G, 1)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, ctx_len: jax.Array,
                    sm_scale: Optional[float] = None,
                    interpret: bool = False):
    """Pallas page-gather history attention (see module docstring).

    Shapes as paged_attention_reference; returns the same
    (acc f32 [S, KVH, G, hd], m [S, KVH, G], l [S, KVH, G]) triple.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    S, KVH, G, hd = q.shape
    page = k_pages.shape[2]
    MAXP = block_table.shape[1]

    def q_map(s, p, bt, ctx):
        return (s, 0, 0, 0)

    def kv_map(s, p, bt, ctx):
        # clamp trailing pages to the last valid one: consecutive grid
        # steps with the same index skip the DMA, and pl.when skips the
        # compute, so fully-padded tables cost (almost) nothing
        last = jnp.maximum(ctx[s] - 1, 0) // page
        return (bt[s, jnp.minimum(p, last)], 0, 0, 0)

    kernel = functools.partial(_paged_kernel, page=page, maxp=MAXP,
                               kvh=KVH, sm_scale=sm_scale)
    out, m, l = pl.pallas_call(
        kernel,
        name="paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, MAXP),
            in_specs=[
                pl.BlockSpec((1, KVH, G, hd), q_map),
                pl.BlockSpec((1, KVH, page, hd), kv_map),
                pl.BlockSpec((1, KVH, page, hd), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, KVH, G, hd), q_map),
                pl.BlockSpec((1, KVH, G, 1),
                             lambda s, p, bt, ctx: (s, 0, 0, 0)),
                pl.BlockSpec((1, KVH, G, 1),
                             lambda s, p, bt, ctx: (s, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((KVH * G, hd), jnp.float32),
                pltpu.VMEM((KVH * G, 1), jnp.float32),
                pltpu.VMEM((KVH * G, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, KVH, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((S, KVH, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((S, KVH, G, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_table, ctx_len, q, k_pages, v_pages)
    return out, m[..., 0], l[..., 0]
