"""A learned index over keys and attention over the keys it chooses
(DeepSeek-V3.2-Exp's sparse attention: a "lightning indexer" beside
multi-head latent attention), for training.

Each query position ``t`` scores every key position ``s <= t`` with ``J``
small index heads that share ONE index key a position,

    ``I[t, s] = sum_j w[t, j] ReLU(q_i[t, j] . k_i[s])``    (float32),

keeps ``S_t``, the ``min(t + 1, topk)`` keys of largest ``I[t, s]`` (ties
to the lower position), and attends over those alone:

    ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . [k_n[s, h] |
    k_r[s]] * scale) v[s, h]``.

The choice is not differentiated. What trains the index is a term of its
own, ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])``, ``p_t`` the
attention's probabilities over ``S_t`` summed over the heads held here and
L1-normalised, under ``stop_gradient``: the index learns to rank keys as
the attention it stands in for weighs them, and nothing else receives that
term's gradient.

``sparse_attention`` walks blocks of ``block`` queries (``lax.map`` over a
``jax.checkpoint``ed block): a block's index scores ``[block, S]`` float32,
its choice (``choose``: an exact radix select of the ``topk``-th largest
score, 32 compare-and-count passes, no sort) and its masked softmax over
all heads live for that block alone, so the ``[T, T]`` scores never exist
whole and neither does a gather of the chosen latents. The blocks are
walked in ``tiers`` of equal length, a tier's blocks against the keys up
to the tier's end: four tiers skip three eighths of the pairs the causal
mask drops.

The scores have two forms, one equation; which runs is read from the call
and never set (``scores_plan``, written into ``rtpu.dsa.shapes``):

- ``kernel``, on a TPU backend for whole tiles (``score_kernels``): two
  Mosaic calls behind a ``custom_vjp``. A grid step takes ``SCORE_TILE``
  keys; the ``J`` head products of ``SCORE_ROWS`` queries with them are
  formed on the MXU into VMEM, ``ReLU``, the weights and the sum over the
  heads run on them there, and ``[block, tile]`` float32 is all that
  reaches HBM. The backward forms the products again and keeps nothing
  ``[block, J, S]`` either. The walk tells the calls where a block's
  diagonal lies, and a tile wholly above it is not scored (zeros, which
  nothing reads).
- ``xla`` elsewhere (the CPU, shapes that are not whole tiles):
  ``plain_scores``, the products ``[block, J, S]`` float32 in HBM and a
  second pass over them. The tests' yardstick.

Choice, attention over it and the term are XLA's: they cost the dense
causal attention's FLOPs whatever the choice keeps (the mask zeroes what
is not chosen); a kernel that visits the chosen keys alone is a later
change and is read by the same yardstick (needed work = the chosen pairs).

Named scopes: ``dsa_scores`` (the index's scores), ``dsa_select`` (the
choice), ``flash_sparse`` (scores, masked softmax and PV of the attention
over the choice), ``dsa_loss`` (the index's term). One kept span as the op
is traced, ``rtpu.dsa.shapes``. Training only.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.util import tracing

_NEG = -1e30


# the scores' kernels (``score_kernels``): keys a grid step takes, and
# queries a chunk of its body takes (all J heads of them at once: J x
# SCORE_ROWS rows of products are in VMEM at a time). Read on the chip at
# the cell's shapes, one layer's walk alone, forward / backward ms
# (``tools/index_sweep.py``; PERF.md 6, PR 47): tiles of 512 by chunks of 8
# 19.5 / 43.9, 16 17.2 / 41.6, 32 16.1 / 40.6, 64 15.5 / 39.9, 128 15.2 /
# 39.5 (three times the VMEM and the compile); 256 by 16 20.7 / 47.2, by
# 128 17.4 / 39.7; 1,024 by 16 16.5 / 41.8, by 64 15.7 / 40.7; 2,048 by 32
# 16.3 / 42.9; unrolling the chunks moved nothing (15.1 / 39.4); XLA's form
# 18.9 / 162.0
SCORE_TILE = 512
SCORE_ROWS = 64
# keys a register holds along its lanes: a tile is whole registers of them
# (tests patch it for small shapes in the interpreter)
KERNEL_LANES = 128


def plain_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array) -> jax.Array:
    """``index_scores`` as XLA runs it: the heads' products ``[n, J, S]``
    float32 whole, then ``ReLU``, the weights and the sum."""
    x = jnp.einsum("njd,sd->njs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(x) * w.astype(jnp.float32)[:, :, None]).sum(1)


def scores_plan(n: int, keys: int, heads: int, dim: int) -> Dict[str, Any]:
    """How ``index_scores`` runs ``n`` queries of ``heads`` index heads of
    ``dim`` against ``keys`` keys: ``scores_form`` "kernel" on a TPU
    backend (anything but the CPU) where the queries are whole chunks of
    ``SCORE_ROWS``, ``dim`` whole lanes and the keys whole tiles, with
    ``scores_tile`` the keys a grid step takes (the largest count of whole
    ``KERNEL_LANES`` up to ``SCORE_TILE`` that divides the keys); "xla" and
    no tile elsewhere."""
    tiles = [t for t in range(KERNEL_LANES, SCORE_TILE + 1, KERNEL_LANES)
             if keys % t == 0]
    if (jax.default_backend() == "cpu" or not tiles or n % SCORE_ROWS
            or dim % KERNEL_LANES):
        return {"scores_form": "xla", "scores_tile": None}
    return {"scores_form": "kernel", "scores_tile": tiles[-1]}


def _scores(q_i, k_i, w, first):
    """``index_scores``, told where the queries stand: ``first``, the
    first one's position (int32), or None. The kernels leave a tile of keys
    that lies wholly past the last query unscored (zeros), XLA's form
    scores every pair."""
    n, J, d = q_i.shape
    plan = scores_plan(n, k_i.shape[0], J, d)
    if plan["scores_form"] == "xla":
        return plain_scores(q_i, k_i, w)
    # looked up at trace time: a test hands it the interpreter
    return score_kernels(q_i, k_i, w, first, plan["scores_tile"])


def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array) -> jax.Array:
    """q_i [n, J, d], k_i [S, d], w [n, J] float32 -> ``I [n, S]`` float32:
    ``sum_j w[., j] ReLU(q_i[., j] . k_i)``, the products accumulated in
    float32. No mask: a caller drops the pairs its queries do not see.
    Which form runs is read from the call (``scores_plan``)."""
    return _scores(q_i, k_i, w, None)


_INDEX_SCORES = index_scores


# ---- the scores as Pallas (Mosaic) kernels. The queries lie heads first,
# q [J, n, d], so that a chunk's products ``[J rows, tile]`` float32 (one
# MXU product of ``J x rows`` rows against the tile's keys) are ``J`` slabs
# of ``[rows, tile]`` and the sum over the heads adds registers and turns
# nothing. The head weights are spread along the lanes once a call (``wb
# [J, n, lanes]``, scratch). The grid walks the tiles of keys; the block of
# queries stays in VMEM. ``first`` (SMEM) says where the first query stands:
# a tile past the last query is not scored.


def _nt(a, b):
    """a [m, d], b [n, d] -> a b^T [m, n], float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _spread_weights(w_ref, wb_ref):
    """w [n, J] -> wb [J, n, lanes], a head's weights down the sublanes
    and the same along every lane."""
    for j in range(w_ref.shape[1]):
        wb_ref[j] = jnp.broadcast_to(w_ref[:, j:j + 1], wb_ref.shape[1:])


def _scores_fwd_kernel(first_ref, q_ref, k_ref, w_ref, o_ref, wb_ref, *,
                       rows):
    import jax.experimental.pallas as pl

    J, n, d = q_ref.shape
    tile, lanes = k_ref.shape[0], wb_ref.shape[-1]
    t = pl.program_id(0)
    seen = t * tile < first_ref[0] + n

    @pl.when(t == 0)
    def _():
        _spread_weights(w_ref, wb_ref)

    @pl.when(seen)
    def _():
        k = k_ref[...]

        def chunk(c, carry):
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            x = jnp.maximum(
                _nt(q_ref[:, at, :].reshape(J * rows, d), k), 0.0
            ).reshape(J, rows, tile)
            wb = wb_ref[:, at, :]
            o_ref[at, :] = jnp.concatenate(
                [(x[:, :, i:i + lanes] * wb).sum(0)
                 for i in range(0, tile, lanes)], axis=-1)
            return carry

        jax.lax.fori_loop(0, n // rows, chunk, 0)

    @pl.when(jnp.logical_not(seen))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _scores_bwd_kernel(first_ref, q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref,
                       dw_ref, wb_ref, dwb_ref, dk_acc, *, rows):
    """A tile of keys of the backward: the products again, ``y = g w (x >
    0)`` to the MXU in the inputs' dtype, ``dq += y k`` (the output's block,
    float32, in VMEM all through the call), ``dk = y^T q`` summed over the
    chunks, and ``dw``'s terms ``g ReLU(x)`` added lane by lane (``dwb``),
    the lanes summed after the last tile."""
    import jax.experimental.pallas as pl

    J, n, d = q_ref.shape
    tile, lanes = k_ref.shape[0], wb_ref.shape[-1]
    t = pl.program_id(0)
    seen = t * tile < first_ref[0] + n

    @pl.when(t == 0)
    def _():
        _spread_weights(w_ref, wb_ref)
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    @pl.when(seen)
    def _():
        k = k_ref[...]
        dk_acc[...] = jnp.zeros_like(dk_acc)

        def chunk(c, carry):
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            q = q_ref[:, at, :].reshape(J * rows, d)
            x = _nt(q, k).reshape(J, rows, tile)
            g, wb = g_ref[at, :], wb_ref[:, at, :]
            ys, terms = [], 0.0
            for i in range(0, tile, lanes):
                xi = x[:, :, i:i + lanes]
                gi = jnp.where(xi > 0, g[:, i:i + lanes][None], 0.0)
                ys.append((gi * wb).astype(q.dtype))
                terms = terms + gi * xi
            dwb_ref[:, at, :] += terms
            y = jnp.concatenate(ys, axis=-1).reshape(J * rows, tile)
            dq_ref[:, at, :] += jnp.dot(
                y, k, preferred_element_type=jnp.float32
            ).reshape(J, rows, d)
            dk_acc[...] += jax.lax.dot_general(
                y, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n // rows, chunk, 0)
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)

    @pl.when(jnp.logical_not(seen))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        head = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape, 1)
        dw = jnp.zeros(dw_ref.shape, jnp.float32)
        for j in range(J):
            dw = jnp.where(head == j, dwb_ref[j].sum(-1, keepdims=True), dw)
        dw_ref[...] = dw


def _score_specs(q, k, tile):
    """What both calls share: the grid (tiles of keys) and the operands'
    blocks."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    J, n, d = q.shape
    return {
        "grid": (k.shape[0] // tile,),
        "first": pl.BlockSpec(memory_space=pltpu.SMEM),
        "q": pl.BlockSpec((J, n, d), lambda t: (0, 0, 0)),
        "k": pl.BlockSpec((tile, d), lambda t: (t, 0)),
        "w": pl.BlockSpec((n, J), lambda t: (0, 0)),
        "scores": pl.BlockSpec((n, tile), lambda t: (0, t)),
        "wb": pltpu.VMEM((J, n, KERNEL_LANES), jnp.float32),
        "params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 << 20)}


def _scores_forward(first, q, k, w, tile, interpret):
    import jax.experimental.pallas as pl

    at = _score_specs(q, k, tile)
    return pl.pallas_call(
        functools.partial(_scores_fwd_kernel, rows=SCORE_ROWS),
        name="dsa_scores_fwd",
        out_shape=jax.ShapeDtypeStruct((q.shape[1], k.shape[0]),
                                       jnp.float32),
        grid=at["grid"],
        in_specs=[at["first"], at["q"], at["k"], at["w"]],
        out_specs=at["scores"], scratch_shapes=[at["wb"]],
        compiler_params=at["params"], interpret=interpret,
    )(first, q, k, w)


def _scores_backward(first, q, k, w, g, tile, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    at = _score_specs(q, k, tile)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_scores_bwd_kernel, rows=SCORE_ROWS),
        name="dsa_scores_bwd",
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(w.shape, f32)],
        grid=at["grid"],
        in_specs=[at["first"], at["q"], at["k"], at["w"], at["scores"]],
        out_specs=[at["q"], at["k"], at["w"]],
        scratch_shapes=[at["wb"], at["wb"],
                        pltpu.VMEM((tile, k.shape[1]), f32)],
        compiler_params=at["params"], interpret=interpret,
    )(first, q, k, w, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _score_calls(first, q, k, w, tile, interpret):
    return _scores_forward(first, q, k, w, tile, interpret)


def _score_calls_fwd(first, q, k, w, tile, interpret):
    # the inputs alone are kept: the backward forms the products again
    return (_scores_forward(first, q, k, w, tile, interpret),
            (first, q, k, w))


def _score_calls_bwd(tile, interpret, res, g):
    # the barrier keeps the call a call: XLA otherwise folds the walk's
    # update of its stacked ``dw`` into it, and the fusion it makes of both
    # loses the call's VMEM limit ("scoped allocation ... limit 16.00M")
    dq, dk, dw = jax.lax.optimization_barrier(
        _scores_backward(*res, g, tile, interpret))
    return None, dq.astype(res[1].dtype), dk, dw


_score_calls.defvjp(_score_calls_fwd, _score_calls_bwd)


def score_kernels(q_i, k_i, w, first, tile: int, interpret: bool = False):
    """``index_scores`` as two Mosaic calls, ``dsa_scores_fwd`` and
    ``dsa_scores_bwd`` behind a ``custom_vjp``: the products of a chunk of
    queries with a tile of keys, all heads', live in VMEM alone, and ``[n,
    S]`` float32 is written once. The products are the MXU's of the arrays
    as they are, float32 sums; ``ReLU``, the weights and the sum over the
    heads are float32 in a fixed order of the heads, so a pair's score does
    not depend on the block or the tile it was scored in. The backward
    keeps the three inputs and takes the cotangent ``g [n, S]`` float32:
    ``y = g w (x > 0)`` goes to the MXU in the inputs' dtype (as XLA's
    default precision sends the float32 cotangent), ``dq_i = y k_i``,
    ``dk_i = y^T q_i``, ``dw = sum_s g ReLU(x)`` float32 throughout.
    ``first``: the first query's position, int32 (None: every tile is
    scored); a tile that starts past the last query is zeros forward and
    adds nothing backward, whatever ``g`` holds there."""
    f32 = jnp.float32
    first = jnp.asarray(k_i.shape[0] if first is None else first,
                        jnp.int32).reshape(1)
    return _score_calls(first, jnp.swapaxes(q_i, 0, 1), k_i, w.astype(f32),
                        tile, interpret)


def choose(scores: jax.Array, first_q, topk: int) -> jax.Array:
    """scores [n, S] float32 of the queries at positions ``first_q + 0 ..
    n - 1`` over the keys at ``0 .. S - 1`` -> bool [n, S]: for each query
    the ``topk`` causal keys of largest score, ties to the lower position,
    all of them where it sees no more than ``topk``.

    Exact and without a sort: the scores become unsigned keys of the same
    order, the ``topk``-th largest key is found bit by bit from the top (32
    passes that compare and count), and the ties at that key are taken in
    order of position until the row holds ``topk``."""
    n, S = scores.shape
    t = first_q + jnp.arange(n, dtype=jnp.int32)[:, None]
    causal = jnp.arange(S, dtype=jnp.int32)[None, :] <= t
    # + 0.0: a negative zero is a zero
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    key = jnp.where(causal, key, jnp.uint32(0))

    def one_bit(i, kth):
        cand = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (key >= cand[:, None]).sum(-1, dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, one_bit, jnp.zeros((n,), jnp.uint32))
    above = key > kth[:, None]
    tied = key == kth[:, None]
    room = topk - above.sum(-1, dtype=jnp.int32)
    rank = jnp.cumsum(tied, axis=-1, dtype=jnp.int32)       # 1-based
    return causal & (above | (tied & (rank <= room[:, None])))


def kl_target(p: jax.Array) -> jax.Array:
    """p [H, n, S], the heads' attention probabilities -> ``p_t`` [n, S]:
    their sum over the heads, L1-normalised, under ``stop_gradient``."""
    target = jax.lax.stop_gradient(p.sum(0))
    return target / target.sum(-1, keepdims=True)


def walk_plan(seq: int, block: int, tiers: int) -> Tuple[int, int]:
    """(block, tiers) as the walk takes them: the largest divisor of
    ``seq`` up to ``block``, the largest count up to ``tiers`` that divides
    the blocks."""
    block = max(b for b in range(1, min(block, seq) + 1) if seq % b == 0)
    blocks = seq // block
    return block, max(g for g in range(1, min(tiers, blocks) + 1)
                      if blocks % g == 0)


def _walk(q, k_n, v, k_r, q_i, k_i, w, *, scale: float, topk: int,
          block: int, tiers: int, keep_choice: bool):
    """One sequence: q [s, H, d_n + d_r], k_n [s, H, d_n], v [s, H, d_v],
    k_r [s, d_r], q_i [s, J, d_i], k_i [s, d_i], w [s, J] -> (o [s, H,
    d_v], the sequence's sum of KL terms, pairs chosen, and under
    ``keep_choice`` the choice packed eight keys a byte [s, s / 8])."""
    s, H, _ = q.shape
    dn = k_n.shape[-1]
    block, tiers = walk_plan(s, block, tiers)
    per_tier = s // block // tiers

    def one_block(keys, args):
        q_b, qi_b, w_b, first = args
        kn_t, v_t, kr_t, ki_t = keys
        with jax.named_scope("dsa_scores"):
            # [block, S']. ``index_scores`` is looked up as the block is
            # traced: a control of benchmark/tests/sparse_limits.py replaces
            # it and is called as it stands; the module's own is told where
            # the block's diagonal lies (what lies past it is never read:
            # ``choose`` masks by position, the term reads under ``chosen``)
            index = (_scores(qi_b, ki_t, w_b, first)
                     if index_scores is _INDEX_SCORES
                     else index_scores(qi_b, ki_t, w_b))
        with jax.named_scope("dsa_select"):
            chosen = choose(jax.lax.stop_gradient(index), first, topk)
        with jax.named_scope("flash_sparse"):
            sc = (jnp.einsum("qhd,khd->hqk", q_b[..., :dn], kn_t,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("qhd,kd->hqk", q_b[..., dn:], kr_t,
                               preferred_element_type=jnp.float32)) * scale
            p = jax.nn.softmax(jnp.where(chosen[None], sc, _NEG), axis=-1)
            out = jnp.einsum("hqk,khd->qhd", p.astype(v_t.dtype), v_t,
                             preferred_element_type=jnp.float32
                             ).astype(q_b.dtype)
        with jax.named_scope("dsa_loss"):
            target = kl_target(p)
            log_q = jax.nn.log_softmax(jnp.where(chosen, index, _NEG), -1)
            kl = jnp.where(
                target > 0,
                target * (jnp.log(jnp.where(target > 0, target, 1.0))
                          - log_q), 0.0).sum()
        said = (out, kl, chosen.sum(dtype=jnp.int32))
        if keep_choice:
            said += (jnp.packbits(jnp.pad(
                chosen, ((0, 0), (0, s - chosen.shape[1]))), axis=-1),)
        return said

    def by_block(x):
        return x.reshape((tiers, per_tier, block) + x.shape[1:])

    q_t, qi_t, w_t = by_block(q), by_block(q_i), by_block(w)
    firsts = (jnp.arange(s // block, dtype=jnp.int32) * block
              ).reshape(tiers, per_tier)
    parts = []
    for g in range(tiers):
        end = (g + 1) * per_tier * block
        keys = (k_n[:end], v[:end], k_r[:end], k_i[:end])
        parts.append(jax.lax.map(
            jax.checkpoint(lambda a, keys=keys: one_block(keys, a)),
            (q_t[g], qi_t[g], w_t[g], firsts[g])))
    out, kl, pairs, *choice = (
        jnp.concatenate(xs) for xs in zip(*parts))
    return (out.reshape(s, H, -1), kl.sum(), pairs.sum(),
            *(c.reshape(s, -1) for c in choice))


def sparse_attention(q, k_n, v, k_r, q_i, k_i, w, *, scale: float,
                     topk: int, block: int = 128, tiers: int = 4,
                     mesh=None, keep_choice: bool = False):
    """Attention of q [b, s, H, d_n + d_r] over the keys the index chooses
    for each position (the module's docstring): keys ``[k_n | k_r]`` (k_n
    [b, s, H, d_n], k_r [b, s, d_r] shared by the heads), values v [b, s,
    H, d_v]; the index's queries q_i [b, s, J, d_i], keys k_i [b, s, d_i]
    and head weights w [b, s, J] float32. -> (o [b, s, H, d_v]; ``kl [b]``,
    each sequence's sum over its positions of ``KL(p_t || softmax_{S_t}
    I)``; ``pairs [b]`` int32, the pairs chosen; under ``keep_choice`` the
    choice packed eight keys a byte, uint8 [b, s, s / 8], key ``8 i + j``
    the bit ``7 - j`` of byte ``i``). Under a mesh each chip walks its own
    rows of the batch, as ``mla._attend`` does."""
    b, s, H, _ = q.shape
    blk, trs = walk_plan(s, block, tiers)
    with tracing.span("rtpu.dsa.shapes", keep=True,
                      index_heads=q_i.shape[2], index_head_dim=q_i.shape[3],
                      topk=topk, positions=s, block=blk, tiers=trs,
                      **scores_plan(blk, s // trs, *q_i.shape[2:]),
                      pairs_scored=b * s * (s + 1) // 2,
                      pairs_chosen=b * sum(min(t + 1, topk)
                                           for t in range(s))):
        pass

    def rows(*a):
        return jax.vmap(lambda *r: _walk(
            *r, scale=scale, topk=topk, block=block, tiers=tiers,
            keep_choice=keep_choice))(*a)

    args = (q, k_n, v, k_r, q_i, k_i, w)
    if mesh is None:
        return rows(*args)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import resolve_axis

    by_row = P(resolve_axis("batch", mesh))
    return jax.shard_map(
        rows, mesh=mesh, in_specs=(by_row,) * len(args),
        out_specs=(by_row,) * (3 + keep_choice), check_vma=False)(*args)


def unpack_choice(packed, s: Optional[int] = None):
    """``sparse_attention``'s packed choice [.., s, s / 8] -> bool [.., s,
    s]."""
    bits = jnp.unpackbits(packed, axis=-1).astype(bool)
    return bits if s is None else bits[..., :s]
