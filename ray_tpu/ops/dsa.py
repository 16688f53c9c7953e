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

``sparse_attention`` walks blocks of at most ``block`` queries (a
``lax.map`` a tier forward and a scan a tier backward, under a rule of the
walk's own, ``_walk``; ``walk_plan`` fits the block to the
sequence and, on a TPU backend, to what the four calls below hold in VMEM:
``walk_needs`` under ``VMEM_CEILING``): a block's index scores ``[block,
S]`` float32,
its choice (``choose``: an exact radix select of the ``topk``-th largest
score, 32 compare-and-count passes, no sort) and its masked softmax over
all heads live for that block alone, so the ``[T, T]`` scores never exist
whole and neither does a gather of the chosen latents. The blocks are
walked in ``tiers`` of equal length, a tier's blocks against the keys up
to the tier's end: four tiers skip three eighths of the pairs the causal
mask drops.

What a block's forward computed is kept, and the block's backward reads it
(``KEPT_NAMES``): the choice packed eight keys a byte (``_pack``: 21 MB a
sequence of 16,384 in four tiers), the heads' log-sum-exp float32 and the
output. The backward runs no ``choose`` and no attention forward: its
attention call takes the kept choice, log-sum-exp and ``delta = sum_d dO
out`` and hands out the heads' summed probabilities once more, which is
what the index term's gradient reads (through ``jax.vjp`` of the scores and
the term: the scores' own two calls, their products formed again). The kept
set carries names for the policy of a layer's ``jax.checkpoint``
(``sparse_attention(named=True)``: ``models/llama.REMAT_LADDER``'s first
rung holds them, and that layer's backward then runs none of the walk's
forward; a layer whose policy holds none of them runs the forward once
more and the backward after it). XLA's form keeps the choice alone and
differentiates ``plain_attend`` where it stands.

The scores and the attention over the choice have two forms each, one
equation; which runs is read from the call and never set (``scores_plan``,
``attend_plan``, written into ``rtpu.dsa.shapes``):

- ``kernel``, on a TPU backend for whole tiles. The scores
  (``score_kernels``): two Mosaic calls behind a ``custom_vjp``. A grid
  step takes ``SCORE_TILE`` keys; the ``J`` head products of
  ``SCORE_ROWS`` queries with them are formed on the MXU into VMEM,
  ``ReLU``, the weights and the sum over the heads run on them there, and
  ``[block, tile]`` float32 is all that reaches HBM. The backward forms the
  products again and keeps nothing ``[block, J, S]`` either. The attention
  (``attend_kernels``): two more, on arrays the walk turns heads first once
  for all its blocks. A grid step takes ``ATTEND_TILE`` keys of every
  head; a head's scores of them ``[tile, block]`` float32 (keys down: the
  MXU holds the block's queries and the keys stream past them), the choice
  as their mask, the softmax and ``p`` live in VMEM alone. The forward
  walks the tiles twice, once for each head's maximum and sum a query and
  once for ``out`` and the heads' summed probabilities ``[block, S]``
  float32, which is what the index's term reads (``kl_target``, with a
  leading axis of one); the backward forms the products again from
  ``out`` and the queries' log-sum-exp and sums the rope key's gradient
  over the heads itself. The walk tells all four calls where a block's
  diagonal lies, and a tile wholly above it is not visited (zeros, which
  nothing reads).
- ``xla`` elsewhere (the CPU, shapes that are not whole tiles):
  ``plain_scores``, the products ``[block, J, S]`` float32 in HBM and a
  second pass over them; ``plain_attend``, the heads' scores and
  probabilities ``[H, block, S]`` float32 in HBM. The tests' yardstick.

The choice (``choose``) and the term are XLA's. The attention costs the
dense causal attention's FLOPs whatever the choice keeps (the mask zeroes
what is not chosen: with ``topk`` of at most 16,384 keys chosen by each of
a block's queries no tile is empty for a whole block); it is read by the
yardstick of the needed work, the chosen pairs.

Keys and values come in two layouts (``attend_layout``): ``per_head`` above,
or ``grouped``, ``k, v [G, S, d]`` each serving ``H / G`` query heads, with no
rope key (the section "grouped keys" below). Named scopes: ``dsa_scores``,
``dsa_select`` (the choice), ``flash_sparse`` (the attention's calls, a
block's ``delta``, the walk's turns of its arrays), ``dsa_loss``. One kept
span, ``rtpu.dsa.shapes`` (forms, layout, ``kv_groups``, block and tiers,
``block_asked``, ``vmem_need_bytes``: the most a call holds;
``block_forwards``: how often a block's attention forward runs a step and
layer under the walk's rule alone, ``kept_bytes_a_layer``). Training only.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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
# 18.9 / 162.0. Over the queries a block of the walk (``index_sweep.py
# --block 128 --block 256 --block 512``; PERF.md 6, PR 55), forward /
# backward ms a layer and ms a step over two layers: 128 queries 15.6 /
# 40.0, 173.7; 256 15.7 / 41.2, 176.5; 512 15.5 / 41.0, 175.0: the scores do
# not care. At 256 queries, tiles of 512 by chunks of 32 16.2 / 41.6, of 128
# 15.4 / 40.8; tiles of 1,024 by 64 15.7 / 41.8, of 256 17.9 / 42.2
SCORE_TILE = 512
SCORE_ROWS = 64
# the attention's kernels (``attend_kernels``): keys a grid step takes (all
# heads' keys and values of them are in VMEM), keys of them a head's
# products take at a time, and heads a step of the heads' loop lays out as
# straight-line code. Read on the chip at the cell's shapes (16 heads, keys
# of 128 | 64, values of 128, blocks of 128 queries in four tiers, bfloat16:
# PR 49's walk),
# one layer's walk alone, forward / forward + the blocks' forward again +
# backward ms, and what a step spends over two layers (``tools/
# index_sweep.py --attend``; PERF.md 6, PR 49): XLA's form 86.1 / 207.5, 587
# a step. Tiles of 512 whole, the loop not unrolled 42.8 / 115.3, 316; two
# heads a step 39.9 / 110.3, 301; four 35.1 / 99.2, 269; eight 34.5 / 94.3,
# 258; sixteen 33.5 / 91.7, 250: a head's products of 128 queries are bound
# by the MXU's latency a product and not by its rate, and straight-line
# code lets the scheduler overlap one head's products with another's
# softmax. By chunks of 256 keys 58.6 / 137.5, by 128 93.4 / 183.6 (every
# chunk pays that latency again); tiles of 256 58.3 / 135.3 (the only
# setting whose blocks fit Mosaic's default 16 MB), of 1,024 31.2 / 101.6,
# 266, of 2,048 36.7 / 132.5. Queries down and keys across (the keys held,
# the queries streamed) 41.4 / 132.0 at chunks of 128 queries, 64.4 / 225.3
# at 64. Over the queries a block of the walk (``index_sweep.py --block 128
# --block 256 --block 512``; PERF.md 6, PR 55): 128 queries 33.5 / 91.8,
# 251 a step; 256 22.0 / 65.7, 176; 512 21.6 / 58.8, 161 (where the choice
# beside it reads 100 a step for 83 and the scores' backward holds 88 MB of
# VMEM). At 256 queries: tiles of 512 whole, sixteen heads a step 22.0 /
# 65.6, 175; eight heads 23.1 / 67.3, 181; by chunks of 256 keys 25.6 /
# 71.7, 195; tiles of 1,024 whole 22.3 / 69.5, 184, by chunks of 512 22.5 /
# 69.8, 185; tiles of 256 26.2 / 68.5, 190: the constants stand
ATTEND_TILE = 512
ATTEND_ROWS = 512
ATTEND_UNROLL = 16
# what a call's blocks and scratch may hold of VMEM (``walk_plan`` takes
# fewer queries a block while one of the four holds more): half a v5e
# core's 128 MiB, which leaves the other half to a tile's temporaries (a
# chunk's products ``[J x SCORE_ROWS, tile]`` float32 and their gradient's,
# 8 MB each) and keeps the scores' backward, the largest of the four, under
# the 100 MB its calls ask for
VMEM_CEILING = 64 << 20
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
    ``SCORE_ROWS``, ``dim`` whole half lanes and the keys whole tiles, with
    ``scores_tile`` the keys a grid step takes (the largest count of whole
    ``KERNEL_LANES`` up to ``SCORE_TILE`` that divides the keys); "xla" and
    no tile elsewhere."""
    tiles = [t for t in range(KERNEL_LANES, SCORE_TILE + 1, KERNEL_LANES)
             if keys % t == 0]
    if (jax.default_backend() == "cpu" or not tiles or n % SCORE_ROWS
            or dim % (KERNEL_LANES // 2)):
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


def _score_blocks(J: int, n: int, d: int, tile: int, dtype,
                  lanes: Optional[int] = None):
    """(shape, dtype) of what the scores' two calls hold in VMEM, by name:
    the operands' blocks and the scratch (``lanes``: ``KERNEL_LANES``)."""
    f32 = jnp.float32
    return {"q": ((J, n, d), dtype), "k": ((tile, d), dtype),
            "w": ((n, J), f32), "scores": ((n, tile), f32),
            "wb": ((J, n, lanes or KERNEL_LANES), f32),
            "dq": ((J, n, d), f32), "dk_acc": ((tile, d), f32)}


def _score_needs(J: int, n: int, d: int, tile: int, dtype):
    """Bytes of VMEM the scores' forward and backward calls hold, their
    blocks twice for the pipeline and their scratch (the chunks' products
    beside them: the calls ask a fixed 100 MB)."""
    at = {name: _padded(*b) for name, b in
          _score_blocks(J, n, d, tile, dtype).items()}
    ins = at["q"] + at["k"] + at["w"] + at["scores"]
    return {"dsa_scores_fwd": 2 * ins + at["wb"],
            "dsa_scores_bwd": (2 * (ins + at["dq"] + at["k"] + at["w"])
                               + 2 * at["wb"] + at["dk_acc"])}


class _ScoreHow(NamedTuple):
    """What a scores' call is built from beside its arrays (static)."""
    tile: int
    rows: int
    lanes: int
    interpret: bool


def _score_specs(q, k, how: _ScoreHow):
    """What both calls share: the grid (tiles of keys) and the operands'
    blocks."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    J, n, d = q.shape
    tile = how.tile
    at = _score_blocks(J, n, d, tile, q.dtype, how.lanes)
    return {
        "grid": (k.shape[0] // tile,),
        "first": pl.BlockSpec(memory_space=pltpu.SMEM),
        "q": pl.BlockSpec(at["q"][0], lambda t: (0, 0, 0)),
        "k": pl.BlockSpec(at["k"][0], lambda t: (t, 0)),
        "w": pl.BlockSpec(at["w"][0], lambda t: (0, 0)),
        "scores": pl.BlockSpec(at["scores"][0], lambda t: (0, t)),
        "wb": pltpu.VMEM(*at["wb"]),
        "dk_acc": pltpu.VMEM(*at["dk_acc"]),
        "params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=100 << 20)}


def _traced_once(scope: str):
    """A builder of a Mosaic call as a ``jax.jit`` of its own, static in
    ``how`` (what the call is built from beside its arrays): a kernel's
    body is traced once a distinct shape and lowered once a program,
    whoever calls it (a rule's primal, its ``fwd``, a second layer of the
    same shapes, a layer's recomputation). What a test or a control plants
    lies above (``score_kernels``, ``attend_kernels``, ...: looked up as a
    step traces). The one lowered function carries its ``scope`` itself,
    whoever's path its first caller's is. The barrier keeps the call a
    call: XLA otherwise folds a consumer that updates a stack in place
    (the walk's ``lax.map`` laying a block's output down, its scan summing
    ``dw``) into one fusion with it, which loses the call's VMEM limit
    ("scoped allocation ... limit 16.00M"), its name in a device trace
    (``fusion.N`` for ``dsa_attend_fwd.N``) and its scope (the update's).
    Entered with the abstract mesh spelled out: jax traces a scan's body
    and a checkpoint's linearization under the empty mesh and the rest
    under none, and the two are different keys of jit's cache."""
    def wrap(builder):
        @functools.wraps(builder)
        def scoped(*args, how):
            with jax.named_scope(scope):
                return jax.lax.optimization_barrier(builder(*args, how))

        jitted = jax.jit(scoped, static_argnames=("how",))

        @functools.wraps(builder)
        def call(*args):
            *arrays, how = args
            with jax.sharding.use_abstract_mesh(
                    jax.sharding.get_abstract_mesh()):
                return jitted(*arrays, how=how)

        call.clear_cache = jitted.clear_cache
        return call

    return wrap


@_traced_once("dsa_scores")
def _scores_forward(first, q, k, w, how: _ScoreHow):
    import jax.experimental.pallas as pl

    at = _score_specs(q, k, how)
    return pl.pallas_call(
        functools.partial(_scores_fwd_kernel, rows=how.rows),
        name="dsa_scores_fwd",
        out_shape=jax.ShapeDtypeStruct((q.shape[1], k.shape[0]),
                                       jnp.float32),
        grid=at["grid"],
        in_specs=[at["first"], at["q"], at["k"], at["w"]],
        out_specs=at["scores"], scratch_shapes=[at["wb"]],
        compiler_params=at["params"], interpret=how.interpret,
    )(first, q, k, w)


@_traced_once("dsa_scores")
def _scores_backward(first, q, k, w, g, how: _ScoreHow):
    import jax.experimental.pallas as pl

    at = _score_specs(q, k, how)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_scores_bwd_kernel, rows=how.rows),
        name="dsa_scores_bwd",
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(w.shape, f32)],
        grid=at["grid"],
        in_specs=[at["first"], at["q"], at["k"], at["w"], at["scores"]],
        out_specs=[at["q"], at["k"], at["w"]],
        scratch_shapes=[at["wb"], at["wb"], at["dk_acc"]],
        compiler_params=at["params"], interpret=how.interpret,
    )(first, q, k, w, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _score_calls(first, q, k, w, how):
    return _scores_forward(first, q, k, w, how)


def _score_calls_fwd(first, q, k, w, how):
    # the inputs alone are kept: the backward forms the products again
    return _scores_forward(first, q, k, w, how), (first, q, k, w)


def _score_calls_bwd(how, res, g):
    dq, dk, dw = _scores_backward(*res, g, how)
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
                        _ScoreHow(tile, SCORE_ROWS, KERNEL_LANES, interpret))


# ---- attention over the choice as Pallas (Mosaic) kernels. Queries, keys
# and values lie heads first (q [H, n, d_n + d_r], k_n [H, S, d_n], v [H, S,
# d_v]; the rope key k_r [S, d_r] is the heads' one), so a head's operands
# are whole slabs. The grid walks the tiles of keys, all heads of a tile in
# one step; a head's scores ``[rows, tile]`` float32 live in VMEM alone.
# ``first`` (a prefetched scalar) says where the first query stands: a tile
# past the last query is neither fetched nor scored.


def plain_attend(q_b, kn_t, v_t, kr_t, chosen, scale: float):
    """Attention of a block over its choice as XLA runs it: q_b [n, H, d_n
    + d_r], kn_t [S, H, d_n], v_t [S, H, d_v], kr_t [S, d_r], chosen bool
    [n, S] -> (out [n, H, d_v], the heads' probabilities [H, n, S] float32
    whole)."""
    dn = kn_t.shape[-1]
    sc = (jnp.einsum("qhd,khd->hqk", q_b[..., :dn], kn_t,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("qhd,kd->hqk", q_b[..., dn:], kr_t,
                       preferred_element_type=jnp.float32)) * scale
    p = jax.nn.softmax(jnp.where(chosen[None], sc, _NEG), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", p.astype(v_t.dtype), v_t,
                     preferred_element_type=jnp.float32).astype(q_b.dtype)
    return out, p


def attend_plan(n: int, keys: int, d_n: int, d_v: int) -> Dict[str, Any]:
    """How a block of ``n`` queries attends over its choice among ``keys``
    keys of ``d_n`` lanes beside the rope's and values of ``d_v``:
    ``attend_form`` "kernel" on a TPU backend (anything but the CPU) where
    the queries are whole ``KERNEL_LANES`` (the kernels lay them along the
    lanes), both widths whole lanes and the keys whole tiles, with
    ``attend_tile`` the keys a grid step takes (the largest count of whole
    lanes up to ``ATTEND_TILE`` that divides the keys, in whole chunks of
    ``ATTEND_ROWS`` where it is more than one); "xla" and no tile
    elsewhere."""
    tiles = [t for t in range(KERNEL_LANES, ATTEND_TILE + 1, KERNEL_LANES)
             if keys % t == 0 and t % min(ATTEND_ROWS, t) == 0]
    if (jax.default_backend() == "cpu" or not tiles or n % KERNEL_LANES
            or d_n % KERNEL_LANES or d_v % KERNEL_LANES):
        return {"attend_form": "xla", "attend_tile": None}
    return {"attend_form": "kernel", "attend_tile": tiles[-1]}


def _last_tile(first_ref, n: int, tile: int):
    """The last tile of keys that holds a pair some query of the block
    sees (``first_ref``: the prefetched position of the first query)."""
    return (first_ref[0] + n - 1) // tile


def _bias_turned(chosen_ref):
    """chosen [n, tile] -> [tile, n] float32: 0 on the chosen pairs,
    ``_NEG`` off them."""
    c = chosen_ref[...].astype(jnp.int32)
    return jnp.where(c != 0, 0.0, _NEG).astype(jnp.float32).T


def _scores_turned(q, kn, kr, bias, scale, dn):
    """A head's scores of ``rows`` keys, keys down and queries across
    [rows, n] float32: the MXU holds the queries and the keys stream."""
    return (_nt(kn, q[:, :dn]) + _nt(kr, q[:, dn:])) * scale + bias


def _over_heads(H: int, body, init, unroll: int):
    """``body(h, carry)`` over the heads, ``unroll`` of them a step of the
    loop: traced once, laid out as straight-line code as the call is
    lowered, which the scheduler may overlap."""
    u = math.gcd(H, unroll)

    def step(i, carry):
        return jax.lax.fori_loop(
            0, u, lambda j, c: body(i * u + j, c), carry, unroll=True)

    return jax.lax.fori_loop(0, H // u, step, init)


def _row(ref, h):
    """Row ``h`` of a [H, n] block of rows' statistics, [1, n]."""
    import jax.experimental.pallas as pl

    return ref[pl.ds(h, 1), :]


def _attend_fwd_kernel(first_ref, q_ref, kn_ref, kr_ref, vt_ref, chosen_ref,
                       ot_ref, lse_ref, ps_ref, m_ref, l_ref, acc_ref,
                       bias_ref, *, scale, rows, unroll):
    """A grid step ``(phase, tile)``, keys down and queries across. Phase
    0: every head's scores of the tile, the running maximum ``m`` and sum
    ``l`` of each query [H, n]. Phase 1: the scores again, ``p = exp(s - m)
    / l``, ``acc += v^T p`` [H, d_v, n] and the heads' sum of ``p``, turned,
    written to the tile's block of ``ps``."""
    import jax.experimental.pallas as pl

    H, n, _ = q_ref.shape
    tile, dn = kn_ref.shape[1:]
    phase, t = pl.program_id(0), pl.program_id(1)
    seen = t <= _last_tile(first_ref, n, tile)
    chunks = [slice(i, i + rows) for i in range(0, tile, rows)]

    def scores(h, at):
        return _scores_turned(q_ref[h], kn_ref[h, at, :], kr_ref[at, :],
                              bias_ref[at, :], scale, dn)

    def heads(body, init=0):
        return _over_heads(H, body, init, unroll)

    @pl.when((phase == 0) & (t == 0))
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(seen)
    def _():
        bias_ref[...] = _bias_turned(chosen_ref)

    @pl.when((phase == 0) & seen)
    def _():
        for at in chunks:
            def head(h, carry, at=at):
                s = scores(h, at)
                m = _row(m_ref, h)
                m_new = jnp.maximum(m, s.max(0, keepdims=True))
                l_ref[pl.ds(h, 1), :] = (
                    _row(l_ref, h) * jnp.exp(m - m_new)
                    + jnp.exp(s - m_new).sum(0, keepdims=True))
                m_ref[pl.ds(h, 1), :] = m_new
                return carry

            heads(head)

    @pl.when((phase == 1) & (t == 0))
    def _():
        # ``l`` is held inverted through phase 1
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])
        l_ref[...] = 1.0 / l_ref[...]

    @pl.when((phase == 1) & seen)
    def _():
        for at in chunks:
            def head(h, total, at=at):
                p = jnp.exp(scores(h, at) - _row(m_ref, h)) * _row(l_ref, h)
                acc_ref[h] += jnp.dot(
                    vt_ref[h, :, at], p.astype(vt_ref.dtype),
                    preferred_element_type=jnp.float32)
                return total + p

            ps_ref[:, at] = heads(head, jnp.zeros((rows, n), jnp.float32)).T

    @pl.when((phase == 1) & jnp.logical_not(seen))
    def _():
        ps_ref[...] = jnp.zeros_like(ps_ref)

    @pl.when((phase == 1) & (t == pl.num_programs(1) - 1))
    def _():
        ot_ref[...] = acc_ref[...].astype(ot_ref.dtype)


def _attend_bwd_kernel(first_ref, q_ref, kn_ref, kr_ref, v_ref, chosen_ref,
                       dot_ref, lse_ref, delta_ref, dq_ref, dkn_ref, dkr_ref,
                       dv_ref, ps_ref, bias_ref, dq_acc, *, scale, rows,
                       unroll):
    """A tile of keys of the backward, keys down and queries across as the
    forward: a head's scores of ``rows`` keys again, ``p = exp(s - lse)``,
    ``dv = p dO``, ``dS = p (v dO^T - delta) scale`` to the MXU in the
    inputs' dtype, ``dk_n = dS q_n``, ``dk_r`` summed over the heads, ``dq
    += dS^T [k_n | k_r]`` (float32, in VMEM through the call), and the
    heads' sum of ``p``, turned, to the tile's block of ``ps`` as the
    forward writes it."""
    import jax.experimental.pallas as pl

    H, n, _ = q_ref.shape
    tile, dn = kn_ref.shape[1:]
    t = pl.program_id(0)
    seen = t <= _last_tile(first_ref, n, tile)

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(seen)
    def _():
        bias_ref[...] = _bias_turned(chosen_ref)
        for at in [slice(i, i + rows) for i in range(0, tile, rows)]:
            kr = kr_ref[at, :]

            def head(h, carry, at=at, kr=kr):
                dkr, total = carry
                q, dot = q_ref[h], dot_ref[h]
                kn, v = kn_ref[h, at, :], v_ref[h, at, :]
                p = jnp.exp(_scores_turned(q, kn, kr, bias_ref[at, :], scale,
                                           dn) - _row(lse_ref, h))
                dv_ref[h, at, :] = _nt(p.astype(dot.dtype), dot
                                       ).astype(dv_ref.dtype)
                ds = p * (jnp.dot(v, dot, preferred_element_type=jnp.float32)
                          - _row(delta_ref, h)) * scale
                ds_t = ds.T.astype(q.dtype)
                ds = ds.astype(q.dtype)
                dkn_ref[h, at, :] = jnp.dot(
                    ds, q[:, :dn], preferred_element_type=jnp.float32
                ).astype(dkn_ref.dtype)
                dq_acc[h, :, :dn] += jnp.dot(
                    ds_t, kn, preferred_element_type=jnp.float32)
                dq_acc[h, :, dn:] += jnp.dot(
                    ds_t, kr, preferred_element_type=jnp.float32)
                return (dkr + jnp.dot(ds, q[:, dn:],
                                      preferred_element_type=jnp.float32),
                        total + p)

            dkr, total = _over_heads(
                H, head, (jnp.zeros((rows, kr.shape[1]), jnp.float32),
                          jnp.zeros((rows, n), jnp.float32)), unroll)
            dkr_ref[at, :] = dkr.astype(dkr_ref.dtype)
            ps_ref[:, at] = total.T

    @pl.when(jnp.logical_not(seen))
    def _():
        dkn_ref[...] = jnp.zeros_like(dkn_ref)
        dkr_ref[...] = jnp.zeros_like(dkr_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        ps_ref[...] = jnp.zeros_like(ps_ref)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _attend_blocks(H: int, n: int, dn: int, dr: int, dv: int, tile: int,
                   dtype):
    """(blocks, scratch) of the attention's forward and of its backward
    call, (shape, dtype) each: what ``_vmem`` reckons their VMEM from."""
    f32, d = jnp.float32, dn + dr
    q, kn, kr = ((H, n, d), dtype), ((H, tile, dn), dtype), ((tile, dr), dtype)
    chosen, stat = ((n, tile), jnp.int8), ((H, n), f32)
    return {
        "dsa_attend_fwd": (
            [q, kn, kr, ((H, dv, tile), dtype), chosen, ((H, dv, n), dtype),
             stat, ((n, tile), f32)],
            [stat, stat, ((H, dv, n), f32), ((tile, n), f32)]),
        "dsa_attend_bwd": (
            [q, kn, kr, ((H, tile, dv), dtype), chosen, ((H, dv, n), dtype),
             stat, stat, q, kn, kr, ((H, tile, dv), dtype),
             ((n, tile), f32)],
            [((tile, n), f32), ((H, n, d), f32)])}


def _need(blocks, scratch) -> int:
    """Bytes of VMEM a call holds: its blocks twice, for the pipeline, and
    its scratch."""
    return (2 * sum(_padded(*b) for b in blocks)
            + sum(_padded(*b) for b in scratch))


def _vmem(blocks, scratch, grid_dims: int):
    """Compiler parameters of a call with these blocks and scratch, (shape,
    dtype) each: Mosaic's default 16 MB where they fit beside a tile's
    temporaries, what they take and 8 MB else."""
    from jax.experimental.pallas import tpu as pltpu

    need = _need(blocks, scratch)
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * grid_dims,
        **({} if need + (4 << 20) <= 16 << 20
           else {"vmem_limit_bytes": need + (8 << 20)}))


def _padded(shape, dtype) -> int:
    """Bytes of a block in VMEM: its last dim in whole 128 lanes."""
    size = jnp.dtype(dtype).itemsize
    for d in shape[:-1]:
        size *= d
    return size * -(-shape[-1] // 128) * 128


def _last_seen(n: int, tile: int):
    """Index maps' clamp: a tile past the last one seen is not fetched
    (the block index stands)."""
    return lambda t, first: jnp.minimum(t, _last_tile(first, n, tile))


class _How(NamedTuple):
    """What an attention call is built from beside its arrays (static)."""
    scale: float
    tile: int
    rows: int
    unroll: int
    interpret: bool


@_traced_once("flash_sparse")
def _attend_forward(first, q, kn, kr, vt, chosen, how: _How):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, n, d = q.shape
    S, dn, dv, dr = kn.shape[1], kn.shape[2], vt.shape[1], kr.shape[1]
    f32, tile = jnp.float32, how.tile
    last = _last_seen(n, tile)
    blocks, scratch = _attend_blocks(H, n, dn, dr, dv, tile,
                                     q.dtype)["dsa_attend_fwd"]
    return pl.pallas_call(
        functools.partial(_attend_fwd_kernel, scale=how.scale, rows=how.rows,
                          unroll=how.unroll),
        name="dsa_attend_fwd",
        out_shape=[jax.ShapeDtypeStruct((H, dv, n), q.dtype),
                   jax.ShapeDtypeStruct((H, n), f32),
                   jax.ShapeDtypeStruct((n, S), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2, S // tile),
            in_specs=[
                pl.BlockSpec((H, n, d), lambda p, t, f: (0, 0, 0)),
                pl.BlockSpec((H, tile, dn), lambda p, t, f: (0, last(t, f), 0)),
                pl.BlockSpec((tile, dr), lambda p, t, f: (last(t, f), 0)),
                # the values wait at their first tile through phase 0
                pl.BlockSpec((H, dv, tile),
                             lambda p, t, f: (0, 0, last(t, f) * p)),
                pl.BlockSpec((n, tile), lambda p, t, f: (0, last(t, f)))],
            out_specs=[
                pl.BlockSpec((H, dv, n), lambda p, t, f: (0, 0, 0)),
                pl.BlockSpec((H, n), lambda p, t, f: (0, 0)),
                pl.BlockSpec((n, tile), lambda p, t, f: (0, t * p))],
            scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in scratch]),
        compiler_params=_vmem(blocks, scratch, 2), interpret=how.interpret,
    )(first, q, kn, kr, vt, chosen)


@_traced_once("flash_sparse")
def _attend_backward(first, q, kn, kr, v, chosen, dot, lse, delta,
                     how: _How):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, n, d = q.shape
    S, dn, dv, dr = kn.shape[1], kn.shape[2], v.shape[2], kr.shape[1]
    f32, tile = jnp.float32, how.tile
    last = _last_seen(n, tile)
    blocks, scratch = _attend_blocks(H, n, dn, dr, dv, tile,
                                     q.dtype)["dsa_attend_bwd"]
    whole3 = pl.BlockSpec((H, n, d), lambda t, f: (0, 0, 0))
    stat = pl.BlockSpec((H, n), lambda t, f: (0, 0))
    return pl.pallas_call(
        functools.partial(_attend_bwd_kernel, scale=how.scale, rows=how.rows,
                          unroll=how.unroll),
        name="dsa_attend_bwd",
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((n, S), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S // tile,),
            in_specs=[
                whole3,
                pl.BlockSpec((H, tile, dn), lambda t, f: (0, last(t, f), 0)),
                pl.BlockSpec((tile, dr), lambda t, f: (last(t, f), 0)),
                pl.BlockSpec((H, tile, dv), lambda t, f: (0, last(t, f), 0)),
                pl.BlockSpec((n, tile), lambda t, f: (0, last(t, f))),
                pl.BlockSpec((H, dv, n), lambda t, f: (0, 0, 0)),
                stat, stat],
            out_specs=[
                whole3,
                pl.BlockSpec((H, tile, dn), lambda t, f: (0, t, 0)),
                pl.BlockSpec((tile, dr), lambda t, f: (t, 0)),
                pl.BlockSpec((H, tile, dv), lambda t, f: (0, t, 0)),
                pl.BlockSpec((n, tile), lambda t, f: (0, t))],
            scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in scratch]),
        compiler_params=_vmem(blocks, scratch, 1), interpret=how.interpret,
    )(first, q, kn, kr, v, chosen, dot, lse, delta)


def _delta(dot, out_t):
    """``sum_d dO out`` of a block as the kernels lay both, [.., d_v, n] ->
    [.., n] float32."""
    return (dot.astype(jnp.float32) * out_t.astype(jnp.float32)).sum(-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _attend_calls(first, q, kn, kr, v, vt, chosen, how):
    return _attend_forward(first, q, kn, kr, vt, chosen, how)


def _attend_calls_fwd(first, q, kn, kr, v, vt, chosen, how):
    out_t, lse, ps = _attend_forward(first, q, kn, kr, vt, chosen, how)
    return (out_t, lse, ps), (first, q, kn, kr, v, chosen, out_t, lse)


def _attend_calls_bwd(how, res, g):
    # ``lse`` and ``ps`` are targets (``kl_target`` stops the sum's
    # gradient): their cotangents are dropped, and so is the backward
    # call's own ``ps``. ``vt`` is ``v`` turned: the values' gradient is
    # ``v``'s
    *ins, out_t, lse = res
    dq, dkn, dkr, dv, _ = _attend_backward(
        *ins, g[0], lse, _delta(g[0], out_t), how)
    return None, dq, dkn, dkr, dv, None, None


_attend_calls.defvjp(_attend_calls_fwd, _attend_calls_bwd)


def attend_kernels(q, kn, v, kr, chosen, first, scale: float, tile: int,
                   v_t=None, interpret: bool = False, back=None):
    """Attention of a block over its choice as two Mosaic calls,
    ``dsa_attend_fwd`` and ``dsa_attend_bwd`` behind a ``custom_vjp``, on
    arrays that lie heads first: q [H, n, d_n + d_r], kn [H, S, d_n], v [H,
    S, d_v], kr [S, d_r], chosen [n, S] (bool or int8) -> (out turned [H,
    d_v, n], the queries' log-sum-exp a head [H, n] float32, ``sum_h p``
    [n, S] float32). ``v_t``: ``v`` with its last two
    axes swapped, [H, d_v, S], what the forward reads (a walk turns it once
    for all its blocks; made here when not given); the values' gradient is
    ``v``'s whole. ``back = (dO turned [H, d_v, n], the log-sum-exp,
    delta [H, n] = sum_d dO out)``: the backward call itself, for a caller
    that kept the forward's -> (dq, dk_n, dk_r, dv, ``sum_h p`` once more):
    what the walk's rule calls, and no forward runs.

    Both calls hold the scores keys down and queries across, so that the
    MXU holds a head's queries and the tile's keys stream past them. The
    forward walks the tiles of keys twice: once for every head's maximum
    and sum a query, once more for ``p = exp(s - m) / l`` float32, ``out +=
    p.astype(v.dtype) v`` (float32 sums) and the heads' sum of ``p``: a
    head's normaliser is not known before its last tile, and the heads' sum
    does not factor. The products are the MXU's of the arrays as they are,
    float32 sums; the mask is the choice. The backward keeps the inputs,
    ``out`` and the queries' log-sum-exp, forms the products again and sends
    ``dS = p (dP - delta) scale`` to the MXU in the inputs' dtype; ``dk_r``
    is summed over the heads in the call; the heads' sum receives no
    gradient. ``first``: the first query's position, int32 (None: every
    tile is visited); a tile that starts past the last query is not fetched,
    its block of the heads' sum and of ``dk_n``, ``dk_r``, ``dv`` is
    zeros."""
    first = jnp.asarray(kn.shape[1] if first is None else first,
                        jnp.int32).reshape(1)
    how = _How(float(scale), tile, min(ATTEND_ROWS, tile), ATTEND_UNROLL,
               interpret)
    chosen = chosen.astype(jnp.int8)
    if back is not None:
        return _attend_backward(first, q, kn, kr, v, chosen, *back, how)
    if v_t is None:
        v_t = jnp.swapaxes(v, 1, 2)
    return _attend_calls(first, q, kn, kr, v, jax.lax.stop_gradient(v_t),
                         chosen, how)


# ---- grouped keys: the same attention where ``G`` key/value heads each
# serve ``R = H / G`` query heads (grouped-query attention) and the heads
# share no rope key (``d_r = 0``). k, v [G, S, d] are read once a group, and
# a group's R heads go through as rows of ONE product: q [G, R x n, d], head
# ``g R + r`` the rows ``r n .. (r + 1) n - 1`` of group ``g``. The MXU then
# sees ``R x n`` queries a product where a head alone gives it ``n``, and the
# latency a product that bound the per-head walk (PERF.md 6, PR 55) is paid
# once for R heads. ``dk`` and ``dv`` are contractions over those rows, so
# they come summed over the group out of the call, as ``k_r``'s gradient
# comes summed over the heads out of the per-head call. A block's choice [n,
# S] is all heads' alike: its bias is laid R times along the lanes once a
# tile. ``p_t`` sums over all H heads: over the groups in the loop, over the
# R slabs of lanes after it. No key or value is repeated in HBM.


def plain_attend_grouped(q_b, k_t, v_t, chosen, scale: float):
    """``plain_attend`` under grouped keys: q_b [n, H, d], k_t [S, G, d],
    v_t [S, G, d_v], chosen bool [n, S] -> (out [n, H, d_v], the heads'
    probabilities [H, n, S] float32 whole). A group's heads are an axis of
    one product: nothing is repeated."""
    n, H, d = q_b.shape
    G = k_t.shape[1]
    sc = jnp.einsum("qgrd,kgd->grqk", q_b.reshape(n, G, H // G, d), k_t,
                    preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(chosen[None, None], sc, _NEG), axis=-1)
    out = jnp.einsum("grqk,kgd->qgrd", p.astype(v_t.dtype), v_t,
                     preferred_element_type=jnp.float32).astype(q_b.dtype)
    return out.reshape(n, H, -1), p.reshape(H, n, -1)


def _bias_grouped(chosen_ref, heads: int):
    """``_bias_turned`` [tile, n], laid ``heads`` times along the lanes."""
    bias = _bias_turned(chosen_ref)
    return jnp.concatenate([bias] * heads, axis=1) if heads > 1 else bias


def _grouped_fwd_kernel(first_ref, q_ref, k_ref, vt_ref, chosen_ref, ot_ref,
                        lse_ref, ps_ref, m_ref, l_ref, acc_ref, bias_ref, *,
                        scale, rows, unroll):
    """``_attend_fwd_kernel`` under grouped keys: a grid step ``(phase,
    tile)``, keys down and a group's ``R x n`` queries across; the
    statistics ``m``, ``l`` [G, R x n] are a query's of a head as there."""
    import jax.experimental.pallas as pl

    G, N, _ = q_ref.shape
    n, tile = chosen_ref.shape
    phase, t = pl.program_id(0), pl.program_id(1)
    seen = t <= _last_tile(first_ref, n, tile)
    chunks = [slice(i, i + rows) for i in range(0, tile, rows)]

    def scores(g, at):
        return _nt(k_ref[g, at, :], q_ref[g]) * scale + bias_ref[at, :]

    def groups(body, init=0):
        return _over_heads(G, body, init, unroll)

    @pl.when((phase == 0) & (t == 0))
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(seen)
    def _():
        bias_ref[...] = _bias_grouped(chosen_ref, N // n)

    @pl.when((phase == 0) & seen)
    def _():
        for at in chunks:
            def group(g, carry, at=at):
                s = scores(g, at)
                m = _row(m_ref, g)
                m_new = jnp.maximum(m, s.max(0, keepdims=True))
                l_ref[pl.ds(g, 1), :] = (
                    _row(l_ref, g) * jnp.exp(m - m_new)
                    + jnp.exp(s - m_new).sum(0, keepdims=True))
                m_ref[pl.ds(g, 1), :] = m_new
                return carry

            groups(group)

    @pl.when((phase == 1) & (t == 0))
    def _():
        # ``l`` is held inverted through phase 1
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])
        l_ref[...] = 1.0 / l_ref[...]

    @pl.when((phase == 1) & seen)
    def _():
        for at in chunks:
            def group(g, total, at=at):
                p = jnp.exp(scores(g, at) - _row(m_ref, g)) * _row(l_ref, g)
                acc_ref[g] += jnp.dot(
                    vt_ref[g, :, at], p.astype(vt_ref.dtype),
                    preferred_element_type=jnp.float32)
                return total + p

            total = groups(group, jnp.zeros((rows, N), jnp.float32))
            ps_ref[:, at] = sum(total[:, i:i + n] for i in range(0, N, n)).T

    @pl.when((phase == 1) & jnp.logical_not(seen))
    def _():
        ps_ref[...] = jnp.zeros_like(ps_ref)

    @pl.when((phase == 1) & (t == pl.num_programs(1) - 1))
    def _():
        ot_ref[...] = acc_ref[...].astype(ot_ref.dtype)


def _grouped_bwd_kernel(first_ref, q_ref, k_ref, v_ref, chosen_ref, dot_ref,
                        lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, ps_ref,
                        bias_ref, dq_acc, *, scale, rows, unroll):
    """``_attend_bwd_kernel`` under grouped keys: ``dk = dS q`` and ``dv =
    p dO`` contract a group's ``R x n`` rows, its heads and queries at once,
    so a group's key and value gradients leave the call summed; ``ps`` sums
    a group's heads' ``p`` into the loop's carry [rows, n]."""
    import jax.experimental.pallas as pl

    G, N, _ = q_ref.shape
    n, tile = chosen_ref.shape
    t = pl.program_id(0)
    seen = t <= _last_tile(first_ref, n, tile)

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(seen)
    def _():
        bias_ref[...] = _bias_grouped(chosen_ref, N // n)
        for at in [slice(i, i + rows) for i in range(0, tile, rows)]:
            def group(g, total, at=at):
                q, dot = q_ref[g], dot_ref[g]
                k, v = k_ref[g, at, :], v_ref[g, at, :]
                p = jnp.exp(_nt(k, q) * scale + bias_ref[at, :]
                            - _row(lse_ref, g))
                dv_ref[g, at, :] = _nt(p.astype(dot.dtype), dot
                                       ).astype(dv_ref.dtype)
                ds = p * (jnp.dot(v, dot, preferred_element_type=jnp.float32)
                          - _row(delta_ref, g)) * scale
                ds_t = ds.T.astype(q.dtype)
                dk_ref[g, at, :] = jnp.dot(
                    ds.astype(q.dtype), q, preferred_element_type=jnp.float32
                ).astype(dk_ref.dtype)
                dq_acc[g] += jnp.dot(ds_t, k,
                                     preferred_element_type=jnp.float32)
                return total + sum(p[:, i:i + n] for i in range(0, N, n))

            ps_ref[:, at] = _over_heads(
                G, group, jnp.zeros((rows, n), jnp.float32), unroll).T

    @pl.when(jnp.logical_not(seen))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        ps_ref[...] = jnp.zeros_like(ps_ref)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _grouped_blocks(G: int, R: int, n: int, d: int, dv: int, tile: int,
                    dtype):
    """``_attend_blocks`` of the grouped calls: (blocks, scratch), (shape,
    dtype) each."""
    f32, N = jnp.float32, R * n
    q, k = ((G, N, d), dtype), ((G, tile, d), dtype)
    chosen, stat = ((n, tile), jnp.int8), ((G, N), f32)
    return {
        "dsa_attend_gqa_fwd": (
            [q, k, ((G, dv, tile), dtype), chosen, ((G, dv, N), dtype), stat,
             ((n, tile), f32)],
            [stat, stat, ((G, dv, N), f32), ((tile, N), f32)]),
        "dsa_attend_gqa_bwd": (
            [q, k, ((G, tile, dv), dtype), chosen, ((G, dv, N), dtype), stat,
             stat, q, k, ((G, tile, dv), dtype), ((n, tile), f32)],
            [((tile, N), f32), ((G, N, d), f32)])}


def _grouped_need(blocks, scratch, rows: int, N: int) -> int:
    """Bytes of VMEM a grouped call holds: ``_need`` and six ``[rows,
    R x n]`` float32 temporaries of a chunk (scores, probabilities, their
    gradient and its turn: 4 MB each at 512 keys by 8 x 256 queries, past
    what ``_vmem``'s 8 MB allows for); the backward's heads' sum of ``p``
    [rows, n] is an eighth of one and rides in that allowance."""
    return _need(blocks, scratch) + 6 * rows * N * 4


def _grouped_params(name: str, G: int, R: int, n: int, d: int, dv: int,
                    dtype, how: "_How", grid_dims: int):
    """(blocks, scratch, compiler parameters) of the grouped call
    ``name``."""
    from jax.experimental.pallas import tpu as pltpu

    blocks, scratch = _grouped_blocks(G, R, n, d, dv, how.tile, dtype)[name]
    need = _grouped_need(blocks, scratch, how.rows, R * n)
    return blocks, scratch, pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * grid_dims,
        vmem_limit_bytes=max(need, 16 << 20))


@_traced_once("flash_sparse")
def _grouped_forward(first, q, k, vt, chosen, how: _How):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, N, d = q.shape
    n, S, dv = chosen.shape[0], k.shape[1], vt.shape[1]
    f32, tile = jnp.float32, how.tile
    last = _last_seen(n, tile)
    _, scratch, params = _grouped_params(
        "dsa_attend_gqa_fwd", G, N // n, n, d, dv, q.dtype, how, 2)
    return pl.pallas_call(
        functools.partial(_grouped_fwd_kernel, scale=how.scale,
                          rows=how.rows, unroll=how.unroll),
        name="dsa_attend_gqa_fwd",
        out_shape=[jax.ShapeDtypeStruct((G, dv, N), q.dtype),
                   jax.ShapeDtypeStruct((G, N), f32),
                   jax.ShapeDtypeStruct((n, S), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2, S // tile),
            in_specs=[
                pl.BlockSpec((G, N, d), lambda p, t, f: (0, 0, 0)),
                pl.BlockSpec((G, tile, d), lambda p, t, f: (0, last(t, f), 0)),
                # the values wait at their first tile through phase 0
                pl.BlockSpec((G, dv, tile),
                             lambda p, t, f: (0, 0, last(t, f) * p)),
                pl.BlockSpec((n, tile), lambda p, t, f: (0, last(t, f)))],
            out_specs=[
                pl.BlockSpec((G, dv, N), lambda p, t, f: (0, 0, 0)),
                pl.BlockSpec((G, N), lambda p, t, f: (0, 0)),
                pl.BlockSpec((n, tile), lambda p, t, f: (0, t * p))],
            scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in scratch]),
        compiler_params=params, interpret=how.interpret,
    )(first, q, k, vt, chosen)


@_traced_once("flash_sparse")
def _grouped_backward(first, q, k, v, chosen, dot, lse, delta, how: _How):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, N, d = q.shape
    n, S, dv = chosen.shape[0], k.shape[1], v.shape[2]
    f32, tile = jnp.float32, how.tile
    last = _last_seen(n, tile)
    _, scratch, params = _grouped_params(
        "dsa_attend_gqa_bwd", G, N // n, n, d, dv, q.dtype, how, 1)
    whole3 = pl.BlockSpec((G, N, d), lambda t, f: (0, 0, 0))
    stat = pl.BlockSpec((G, N), lambda t, f: (0, 0))
    return pl.pallas_call(
        functools.partial(_grouped_bwd_kernel, scale=how.scale,
                          rows=how.rows, unroll=how.unroll),
        name="dsa_attend_gqa_bwd",
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((n, S), f32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S // tile,),
            in_specs=[
                whole3,
                pl.BlockSpec((G, tile, d), lambda t, f: (0, last(t, f), 0)),
                pl.BlockSpec((G, tile, dv), lambda t, f: (0, last(t, f), 0)),
                pl.BlockSpec((n, tile), lambda t, f: (0, last(t, f))),
                pl.BlockSpec((G, dv, N), lambda t, f: (0, 0, 0)),
                stat, stat],
            out_specs=[
                whole3,
                pl.BlockSpec((G, tile, d), lambda t, f: (0, t, 0)),
                pl.BlockSpec((G, tile, dv), lambda t, f: (0, t, 0)),
                pl.BlockSpec((n, tile), lambda t, f: (0, t))],
            scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in scratch]),
        compiler_params=params, interpret=how.interpret,
    )(first, q, k, v, chosen, dot, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _grouped_calls(first, q, k, v, vt, chosen, how):
    return _grouped_forward(first, q, k, vt, chosen, how)


def _grouped_calls_fwd(first, q, k, v, vt, chosen, how):
    out_t, lse, ps = _grouped_forward(first, q, k, vt, chosen, how)
    return (out_t, lse, ps), (first, q, k, v, chosen, out_t, lse)


def _grouped_calls_bwd(how, res, g):
    # as ``_attend_calls_bwd``: ``lse`` and ``ps`` are targets, ``vt`` is
    # ``v`` turned
    *ins, out_t, lse = res
    dq, dk, dv, _ = _grouped_backward(
        *ins, g[0], lse, _delta(g[0], out_t), how)
    return None, dq, dk, dv, None, None


_grouped_calls.defvjp(_grouped_calls_fwd, _grouped_calls_bwd)


def attend_kernels_grouped(q, k, v, chosen, first, scale: float, tile: int,
                           v_t=None, interpret: bool = False, back=None):
    """``attend_kernels`` under grouped keys, two Mosaic calls
    ``dsa_attend_gqa_fwd`` and ``dsa_attend_gqa_bwd`` behind a
    ``custom_vjp``: q [G, R x n, d] (a group's R heads one after another,
    ``n`` queries each), k [G, S, d], v [G, S, d_v], chosen [n, S] (bool or
    int8) -> (out turned [G, d_v, R x n], the log-sum-exp [G, R x n]
    float32, ``sum_h p`` [n, S] float32 over all ``G x R`` heads). ``v_t``,
    ``first``, ``back`` (-> dq, dk, dv, ``sum_h p``) and the arithmetic are
    ``attend_kernels``'; the keys' and values' gradients are the group's,
    summed over its heads in the call."""
    first = jnp.asarray(k.shape[1] if first is None else first,
                        jnp.int32).reshape(1)
    how = _How(float(scale), tile, min(ATTEND_ROWS, tile), ATTEND_UNROLL,
               interpret)
    chosen = chosen.astype(jnp.int8)
    if back is not None:
        return _grouped_backward(first, q, k, v, chosen, *back, how)
    if v_t is None:
        v_t = jnp.swapaxes(v, 1, 2)
    return _grouped_calls(first, q, k, v, jax.lax.stop_gradient(v_t), chosen,
                          how)


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


class Widths(NamedTuple):
    """What a position is to the walk's four calls: ``heads`` of keys
    ``d_n | d_r`` and values ``d_v``, ``index_heads`` of ``index_dim``,
    all of ``dtype``; ``groups``: under grouped keys the key/value heads
    that serve the ``heads`` (``d_r`` is then 0), None per head."""
    heads: int
    d_n: int
    d_r: int
    d_v: int
    index_heads: int
    index_dim: int
    dtype: Any
    groups: Optional[int] = None

    @classmethod
    def of(cls, q, k_n, v, q_i, grouped: bool = False) -> "Widths":
        """From the walk's arrays [.., s, heads, width]."""
        dn = k_n.shape[-1]
        return cls(q.shape[-2], dn, q.shape[-1] - dn, v.shape[-1],
                   q_i.shape[-2], q_i.shape[-1], q.dtype,
                   k_n.shape[-2] if grouped else None)


def walk_needs(block: int, keys: int, widths: Widths) -> Dict[str, int]:
    """Bytes of VMEM each Mosaic call of a block of ``block`` queries
    against a tier of ``keys`` keys holds, by the call's name: the blocks
    and scratch the calls are built from (``_score_needs``,
    ``_attend_blocks``). A form that is XLA's has no call and no entry."""
    H, dn, dr, dv, J, di, dtype, G = widths
    needs = {}
    tile = scores_plan(block, keys, J, di)["scores_tile"]
    if tile:
        needs.update(_score_needs(J, block, di, tile, dtype))
    tile = attend_plan(block, keys, dn, dv)["attend_tile"]
    if tile and G:
        rows, N = min(ATTEND_ROWS, tile), H // G * block
        needs.update({name: _grouped_need(*at, rows, N)
                      for name, at in _grouped_blocks(
                          G, H // G, block, dn, dv, tile, dtype).items()})
    elif tile:
        needs.update({name: _need(*at) for name, at in _attend_blocks(
            H, block, dn, dr, dv, tile, dtype).items()})
    return needs


def kept_bytes(seq: int, block: int, tiers: int, widths: Widths,
               kernels: bool) -> int:
    """Bytes a sequence's walk keeps of its forward beside its inputs: a
    tier's choices packed eight keys a byte over the tier's keys and, where
    the attention runs as ``kernels``, the heads' log-sum-exp float32 and
    the output."""
    per_tier = seq // tiers
    choice = sum(per_tier * -(-(g + 1) * per_tier // 8)
                 for g in range(tiers))
    if not kernels:
        return choice
    return choice + seq * widths.heads * (
        4 + widths.d_v * jnp.dtype(widths.dtype).itemsize)


def walk_plan(seq: int, block: int, tiers: int,
              widths: Optional[Widths] = None) -> Tuple[int, int]:
    """(block, tiers) as the walk takes them: the largest divisor of
    ``seq`` up to ``block``, the largest count up to ``tiers`` that divides
    the blocks. Given the ``widths``, the block steps down to the next
    divisor of whole ``KERNEL_LANES`` while a call's ``walk_needs`` stands
    over ``VMEM_CEILING`` (the smallest such block where none fits)."""
    def tiers_of(b):
        return max(g for g in range(1, min(tiers, seq // b) + 1)
                   if seq // b % g == 0)

    fits = [b for b in range(min(block, seq), 0, -1) if seq % b == 0]
    took = fits[0]
    if widths is not None:
        for b in fits[:1] + [b for b in fits[1:] if b % KERNEL_LANES == 0]:
            took = b
            if max(walk_needs(b, seq // tiers_of(b), widths).values(),
                   default=0) <= VMEM_CEILING:
                break
    return took, tiers_of(took)


# What a walk keeps of its forward for its backward beside its inputs, and
# the names it carries where the caller asks (``sparse_attention(named=)``):
# the output, the heads' log-sum-exp and the choice packed eight keys a
# byte. A layer's ``jax.checkpoint`` whose policy holds these names
# (``models/llama.REMAT_LADDER``'s first rung) runs no call of the walk's
# forward in its backward; one that does not runs the forward once for them.
KEPT_NAMES = ("flash_out", "flash_lse", "dsa_choice")


def _pack(chosen):
    """chosen bool [n, S] -> uint8 [n, ceil(S / 8)], what the walk keeps of
    a block's choice: bit ``j`` of byte ``i`` is key ``j W + i``, ``W`` the
    bytes a row. Eight planes of neighbouring keys and not
    ``jnp.packbits``' bytes of eight neighbours: packing and unpacking are
    shifts of whole slices along the lanes, where a byte of neighbours
    would turn every register."""
    n, S = chosen.shape
    W = -(-S // 8)
    planes = jnp.pad(chosen, ((0, 0), (0, 8 * W - S))).astype(jnp.uint8)
    return functools.reduce(jnp.bitwise_or, (
        planes[:, j * W:(j + 1) * W] << j for j in range(8)))


def _unpack(packed, S: int):
    """``_pack``'s bytes -> bool [n, S]."""
    return jnp.concatenate([(packed >> j) & 1 for j in range(8)],
                           axis=1)[:, :S] != 0


class _Walk(NamedTuple):
    """What a walk is built from beside its arrays (static)."""
    scale: float
    topk: int
    block: int
    tiers: int
    keep_choice: bool
    named: bool


class _Laid(NamedTuple):
    """A walk's arrays as its blocks take them: a tier's blocks of queries
    ``q`` (heads first under the kernels), index queries ``qi`` and head
    weights ``w`` [tiers, blocks a tier, ..], the first positions
    ``firsts``, and a tier's ``keys`` (k_n, v, k_r or None, and under the
    kernels v turned) and index keys ``ki`` up to its end."""
    q: Any
    qi: Any
    w: Any
    firsts: Any
    keys: Any
    ki: Any
    ends: Any
    tile: Optional[int]


def _by_block(x, how: _Walk, at: int = 0):
    """x [.., s, ..], the positions at axis ``at`` (0, or 1 behind the
    heads) -> [tiers, blocks a tier, .., block, ..]."""
    per_tier = x.shape[at] // how.block // how.tiers
    x = x.reshape(x.shape[:at] + (how.tiers, per_tier, how.block)
                  + x.shape[at + 1:])
    return jnp.moveaxis(x, 0, 2) if at else x


def _lay_out(how: _Walk, q, k_n, v, k_r, q_i, k_i, w) -> _Laid:
    s = q.shape[0]
    per_tier = s // how.block // how.tiers
    ends = [(g + 1) * per_tier * how.block for g in range(how.tiers)]
    tile = attend_plan(how.block, s // how.tiers, k_n.shape[-1],
                       v.shape[-1])["attend_tile"]
    # where the positions lie in q, k_n and v: the kernels take them heads
    # first, and the values also turned [H, d_v, s] (the forward call's)
    at = int(tile is not None)
    with jax.named_scope("flash_sparse"):
        # turned once a walk, not once a block
        if at:
            q, k_n, v = (jnp.swapaxes(x, 0, 1) for x in (q, k_n, v))
            v_turned = jnp.swapaxes(v, 1, 2)
        keys = [(jax.lax.slice_in_dim(k_n, 0, end, axis=at),
                 jax.lax.slice_in_dim(v, 0, end, axis=at),
                 None if k_r is None else k_r[:end])
                + ((v_turned[..., :end],) if at else ()) for end in ends]
        q = _by_block(q, how, at)
    firsts = (jnp.arange(s // how.block, dtype=jnp.int32) * how.block
              ).reshape(how.tiers, per_tier)
    return _Laid(q, _by_block(q_i, how), _by_block(w, how), firsts, keys,
                 [k_i[:end] for end in ends], ends, tile)


def _block_scores(qi_b, ki_t, w_b, first):
    """A block's index scores [block, S']. ``index_scores`` is looked up
    as the block is traced: a control of benchmark/tests/sparse_limits.py
    replaces it and is called as it stands; the module's own is told where
    the block's diagonal lies (what lies past it is never read: ``choose``
    masks by position, the term reads under ``chosen``)."""
    with jax.named_scope("dsa_scores"):
        return (_scores(qi_b, ki_t, w_b, first)
                if index_scores is _INDEX_SCORES
                else index_scores(qi_b, ki_t, w_b))


def _index_term(index, chosen, target):
    """A block's sum of ``KL(p_t || softmax_{S_t} I)``."""
    with jax.named_scope("dsa_loss"):
        log_q = jax.nn.log_softmax(jnp.where(chosen, index, _NEG), -1)
        return jnp.where(
            target > 0,
            target * (jnp.log(jnp.where(target > 0, target, 1.0)) - log_q),
            0.0).sum()


def _block_attend(how: _Walk, tile, q_b, chosen, first, keys, back=None):
    """A block's attention over its choice, in the form and layout the
    walk's arrays have. Forward -> (out, the log-sum-exp or None, p: the
    heads' probabilities [H, block, S'] (XLA's form) or their sum over the
    heads [1, block, S'] (the kernels')). ``back``, the block's cotangent
    and what the forward kept -> ((dq, dk_n, dv, dk_r or None), p): the
    kernels' backward call alone, which hands out the sum once more; XLA's
    form through ``jax.vjp``, its products formed again. The four entry
    points are looked up as the block is traced: a test hands the kernels
    the interpreter, a control replaces one and is differentiated as it
    stands."""
    kn_t, v_t, kr_t, *turned = keys
    grouped = kr_t is None
    with jax.named_scope("flash_sparse"):
        if tile is None:
            def plain(q_b, kn_t, v_t, kr_t):
                if grouped:
                    return plain_attend_grouped(q_b, kn_t, v_t, chosen,
                                                how.scale)
                return plain_attend(q_b, kn_t, v_t, kr_t, chosen, how.scale)

            if back is None:
                out, p = plain(q_b, kn_t, v_t, kr_t)
                return out, None, p
            _, pull, p = jax.vjp(plain, q_b, kn_t, v_t, kr_t, has_aux=True)
            return pull(back[0]), p
        if grouped:
            # [H, block, d] -> [G, R x block, d]: a group's heads in a row
            q_g = q_b.reshape(kn_t.shape[0], -1, q_b.shape[-1])
            if back is None:
                out, lse, p_sum = attend_kernels_grouped(
                    q_g, kn_t, v_t, chosen, first, how.scale, tile, *turned)
                return out, lse, p_sum[None]
            dq, dk, dv, p_sum = attend_kernels_grouped(
                q_g, kn_t, v_t, chosen, first, how.scale, tile, back=back)
            return (dq.reshape(q_b.shape), dk, dv, None), p_sum[None]
        if back is None:
            out, lse, p_sum = attend_kernels(
                q_b, kn_t, v_t, kr_t, chosen, first, how.scale, tile, *turned)
            return out, lse, p_sum[None]
        dq, dkn, dkr, dv, p_sum = attend_kernels(
            q_b, kn_t, v_t, kr_t, chosen, first, how.scale, tile, back=back)
        return (dq, dkn, dv, dkr), p_sum[None]


def _walk_forward(how: _Walk, q, k_n, v, k_r, q_i, k_i, w):
    """``_walk``'s forward -> (what it returns, what its backward reads
    beside the inputs: a tier's choices packed [blocks a tier, block, S' /
    8] uint8 and, under the kernels, log-sum-exps [blocks a tier, H,
    block] float32)."""
    s, H, _ = q.shape
    G = k_n.shape[-2]
    at = _lay_out(how, q, k_n, v, k_r, q_i, k_i, w)

    def one_block(g, args):
        q_b, qi_b, w_b, first = args
        index = _block_scores(qi_b, at.ki[g], w_b, first)
        with jax.named_scope("dsa_select"):
            chosen = choose(index, first, how.topk)
            packed = _pack(chosen)
        out, lse, p = _block_attend(how, at.tile, q_b, chosen, first,
                                    at.keys[g])
        kl = _index_term(index, chosen, kl_target(p))
        said = (out, kl, chosen.sum(dtype=jnp.int32), packed, lse)
        if how.keep_choice:
            with jax.named_scope("dsa_select"):
                said += (jnp.packbits(jnp.pad(
                    chosen, ((0, 0), (0, s - chosen.shape[1]))), axis=-1),)
        return said

    parts = [jax.lax.map(functools.partial(one_block, g),
                         (at.q[g], at.qi[g], at.w[g], at.firsts[g]))
             for g in range(how.tiers)]
    out, kl, pairs = (jnp.concatenate([part[i] for part in parts])
                      for i in range(3))
    if at.tile:
        # [blocks, H, d_v, block], as the kernels leave it; under grouped
        # keys [blocks, G, d_v, R x block]
        with jax.named_scope("flash_sparse"):
            if k_r is None:
                out = out.reshape(out.shape[:3] + (H // G, how.block))
                out = jnp.transpose(out, (0, 4, 1, 3, 2))
            else:
                out = jnp.transpose(out, (0, 3, 1, 2))
    said = (out.reshape(s, H, -1), kl.sum(), pairs.sum())
    if how.keep_choice:
        said += (jnp.concatenate([part[5] for part in parts]
                                 ).reshape(s, -1),)
    return said, ([part[3] for part in parts],
                  [part[4] for part in parts] if at.tile else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk_rule(how: _Walk, q, k_n, v, k_r, q_i, k_i, w):
    return _walk_forward(how, q, k_n, v, k_r, q_i, k_i, w)[0]


def _walk_rule_fwd(how: _Walk, q, k_n, v, k_r, q_i, k_i, w):
    said, (packed, lse) = _walk_forward(how, q, k_n, v, k_r, q_i, k_i, w)
    o = said[0]
    if how.named:
        o, lse, packed = (
            jax.tree.map(lambda x, name=name: checkpoint_name(x, name), kept)
            for kept, name in zip((o, lse, packed), KEPT_NAMES))
    # the output is kept where the kernels' backward reads it (``delta``)
    return (o,) + said[1:], ((q, k_n, v, k_r, q_i, k_i, w),
                             o if lse is not None else None, lse, packed)


def _walk_rule_bwd(how: _Walk, kept, cotangents):
    """The walk's backward: a block's attention backward over the kept
    choice, log-sum-exp and output, the heads' summed probabilities out of
    that call, and the index term's gradient through the scores from them
    (``jax.vjp`` of the scores and the term: the scores' forward and
    backward calls, or whatever stands in ``index_scores``' place). No
    attention forward and no ``choose`` runs here. The keys' gradients are
    summed over a tier's blocks in the arrays' own dtype, as a scan's
    transposition sums them."""
    inputs, o, lse, packed = kept
    d_o, d_kl = cotangents[:2]
    q, k_n, v, k_r, q_i, k_i, w = inputs
    s, H, _ = q.shape
    G = k_n.shape[-2]
    grouped = k_r is None
    at = _lay_out(how, *inputs)
    per_tier = s // how.block // how.tiers

    with jax.named_scope("flash_sparse"):
        # the blocks' cotangents as the forward laid their outputs out
        d_out = d_o.reshape(-1, how.block, H, d_o.shape[-1])
        if at.tile:
            delta = (d_o.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
            delta = delta.reshape(-1, how.block, H)
            if grouped:
                d_out = jnp.transpose(d_out.reshape(
                    d_out.shape[:2] + (G, H // G, -1)), (0, 2, 4, 3, 1))
                d_out = d_out.reshape(d_out.shape[:3] + (-1,))
                delta = jnp.transpose(delta.reshape(
                    delta.shape[:2] + (G, H // G)), (0, 2, 3, 1))
                delta = delta.reshape(delta.shape[:2] + (-1,))
            else:
                d_out = jnp.transpose(d_out, (0, 2, 3, 1))
                delta = jnp.swapaxes(delta, 1, 2)
            delta = delta.reshape((how.tiers, per_tier) + delta.shape[1:])
        d_out = d_out.reshape((how.tiers, per_tier) + d_out.shape[1:])

    def one_block(g, sums, args):
        q_b, qi_b, w_b, first, packed_b, dout_b, *stat = args
        keys = at.keys[g]
        with jax.named_scope("dsa_select"):
            chosen = _unpack(packed_b, at.ends[g])
        grads, p = _block_attend(how, at.tile, q_b, chosen, first, keys,
                                 back=(dout_b, *stat))
        target = kl_target(p)
        _, pull = jax.vjp(
            lambda qi_b, ki_t, w_b: _index_term(
                _block_scores(qi_b, ki_t, w_b, first), chosen, target),
            qi_b, at.ki[g], w_b)
        dqi, dki, dw = pull(d_kl)
        dq, *dkeys = grads
        sums = jax.tree.map(lambda a, d: a + d.astype(a.dtype), sums,
                            (dkeys, dki))
        return sums, (dq, dqi, dw)

    parts = [jax.lax.scan(
        functools.partial(one_block, g),
        jax.tree.map(jnp.zeros_like, (list(at.keys[g][:3]), at.ki[g])),
        (at.q[g], at.qi[g], at.w[g], at.firsts[g], packed[g], d_out[g])
        + ((lse[g], delta[g]) if at.tile else ()))
        for g in range(how.tiers)]

    def whole(xs, axis=0):
        """The tiers' sums over their keys [.., S', ..] -> one over all
        keys [.., s, ..]."""
        return sum(jnp.pad(x, [(0, s - x.shape[a] if a == axis else 0)
                               for a in range(x.ndim)]) for x in xs)

    with jax.named_scope("flash_sparse"):
        dq = jnp.concatenate([ys[0] for _, ys in parts])
        heads_first = int(at.tile is not None)
        if heads_first:
            # [blocks, H, block, d] -> [s, H, d]
            dq = jnp.swapaxes(dq, 1, 2)
        dq = dq.reshape(q.shape)
        of_keys, of_index = zip(*(sums for sums, _ in parts))
        dkn, dv = (whole([tier[i] for tier in of_keys], heads_first)
                   for i in range(2))
        dkr = None if grouped else whole([tier[2] for tier in of_keys])
        if heads_first:
            dkn, dv = jnp.swapaxes(dkn, 0, 1), jnp.swapaxes(dv, 0, 1)
    dki = whole(of_index)
    dqi, dw = (jnp.concatenate([ys[i] for _, ys in parts]).reshape(x.shape)
               for i, x in ((1, q_i), (2, w)))
    return dq, dkn, dv, dkr, dqi, dki, dw


_walk_rule.defvjp(_walk_rule_fwd, _walk_rule_bwd)


def _walk(q, k_n, v, k_r, q_i, k_i, w, *, scale: float, topk: int,
          block: int, tiers: int, keep_choice: bool, named: bool = False):
    """One sequence: q [s, H, d_n + d_r], k_n [s, H, d_n], v [s, H, d_v],
    k_r [s, d_r], or under grouped keys q [s, H, d], k_n [s, G, d], v [s,
    G, d_v] and ``k_r`` None; q_i [s, J, d_i], k_i [s, d_i], w [s, J] -> (o
    [s, H, d_v], the sequence's sum of KL terms, pairs chosen, and under
    ``keep_choice`` the choice packed eight keys a byte [s, s / 8]).
    ``block`` and ``tiers`` are ``walk_plan``'s. A rule of its own
    (``custom_vjp``): the forward is a ``lax.map`` a tier over its blocks,
    the backward a scan a tier over the same blocks that reads what the
    forward kept (``KEPT_NAMES``) and runs no block's forward again."""
    return _walk_rule(_Walk(float(scale), topk, block, tiers, keep_choice,
                            named), q, k_n, v, k_r, q_i, k_i, w)


def sparse_attention(q, k_n, v, k_r, q_i, k_i, w, *, scale: float,
                     topk: int, block: int = 256, tiers: int = 4,
                     mesh=None, keep_choice: bool = False,
                     named: bool = False):
    """Attention of q [b, s, H, d_n + d_r] over the keys the index chooses
    for each position (the module's docstring): keys ``[k_n | k_r]`` (k_n
    [b, s, H, d_n], k_r [b, s, d_r] shared by the heads), values v [b, s,
    H, d_v]; or, ``k_r`` None, grouped keys k_n [b, s, G, d] and values v
    [b, s, G, d_v], each serving ``H / G`` query heads of q [b, s, H, d];
    the index's queries q_i [b, s, J, d_i], keys k_i [b, s, d_i]
    and head weights w [b, s, J] float32. -> (o [b, s, H, d_v]; ``kl [b]``,
    each sequence's sum over its positions of ``KL(p_t || softmax_{S_t}
    I)``; ``pairs [b]`` int32, the pairs chosen; under ``keep_choice`` the
    choice packed eight keys a byte, uint8 [b, s, s / 8], key ``8 i + j``
    the bit ``7 - j`` of byte ``i``: ``jnp.packbits``' layout, which the
    benchmark's references read with ``jnp.unpackbits``; the walk's own
    kept choice lies in ``_pack``'s planes, 0.065 ms a block of 256 x
    16,384 on the chip for ``packbits``' 0.111, PR 58, and the measured
    step hands no choice out). ``named``: what the walk keeps for its
    backward carries ``KEPT_NAMES``, for the policy of a layer's
    ``jax.checkpoint`` to hold. A caller says so whose ``keeps`` counts
    ``kept_bytes`` on the ladder's first rung (``llama.attention_part``);
    ``ops/mla.py``'s latent layers do not yet (ROADMAP S18 (b), the
    layer's half: their first rung is the two latents, and a name the plan
    does not count would be held unreckoned). Under a mesh each chip walks
    its own rows of the batch, as ``mla._attend`` does."""
    b, s, H, _ = q.shape
    grouped = k_r is None
    if grouped and H % k_n.shape[2]:
        raise ValueError(f"{k_n.shape[2]} key/value heads do not divide "
                         f"{H} query heads")
    widths = Widths.of(q, k_n, v, q_i, grouped)
    blk, trs = walk_plan(s, block, tiers, widths)
    attend = attend_plan(blk, s // trs, k_n.shape[-1], v.shape[-1])
    with tracing.span("rtpu.dsa.shapes", keep=True,
                      attend_layout="grouped" if grouped else "per_head",
                      kv_groups=k_n.shape[2],
                      index_heads=q_i.shape[2], index_head_dim=q_i.shape[3],
                      topk=topk, positions=s, block=blk, tiers=trs,
                      # the block before the guard, and what it was held to
                      block_asked=walk_plan(s, block, tiers)[0],
                      vmem_need_bytes=max(
                          walk_needs(blk, s // trs, widths).values(),
                          default=0),
                      **scores_plan(blk, s // trs, *q_i.shape[2:]),
                      **attend,
                      # how often the walk's own rule runs a block's
                      # attention forward a step and layer (XLA's form
                      # forms its products again in the backward), and what
                      # it keeps for that. The rule's count, not the
                      # step's: a layer whose ``jax.checkpoint`` holds none
                      # of ``KEPT_NAMES`` (dots3's) runs the rule's forward
                      # once more; ``tools/step_program.py`` counts the
                      # compiled step's calls
                      block_forwards=1 if attend["attend_tile"] else 2,
                      kept_bytes_a_layer=b * kept_bytes(
                          s, blk, trs, widths, bool(attend["attend_tile"])),
                      pairs_scored=b * s * (s + 1) // 2,
                      pairs_chosen=b * sum(min(t + 1, topk)
                                           for t in range(s))):
        pass

    def rows(*a):
        if grouped:
            a = a[:3] + (None,) + a[3:]

        # a row a call of the walk, whatever the batch: the same program a
        # row as a batch of one's (what the cells run and the chip timed;
        # under ``jax.vmap`` the walk's scans and calls read 3.3 s more of
        # trace on the bench host, PERF.md 6, PR 58), and a second row's
        # kernels are the first's (``_traced_once``)
        said = [_walk(*(x if x is None else x[i] for x in a), scale=scale,
                      topk=topk, block=blk, tiers=trs,
                      keep_choice=keep_choice, named=named)
                for i in range(a[0].shape[0])]
        return tuple(jnp.stack(xs) for xs in zip(*said))

    args = tuple(x for x in (q, k_n, v, k_r, q_i, k_i, w) if x is not None)
    if mesh is None:
        return rows(*args)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import resolve_axis

    by_row = P(resolve_axis("batch", mesh))
    return jax.shard_map(
        rows, mesh=mesh, in_specs=(by_row,) * len(args),
        out_specs=(by_row,) * (3 + keep_choice), check_vma=False)(*args)


# ---- the index as a part of a layer: what ``ops/mla.
# latent_attention_part(index=True)`` and ``models/llama.attention_part(
# index=True)`` share. The query side reads whatever the layer has: the
# normed query latent ``c_q`` (latent attention) or the layer's normed input
# ``u`` itself (a layer without a query latent).

INDEX_LEAVES = ("wi_q", "wi_k", "wi_k_norm", "wi_k_bias", "wi_w")


def index_leaves(cfg, query_width: int) -> Dict[str, Any]:
    """The index's five leaves: ``wi_q [query_width, J d_i]`` from the
    width its queries are read at, ``wi_k [hidden, d_i]``, the key's
    LayerNorm and ``wi_w [hidden, J]``. Not divided under a mesh."""
    from ray_tpu.ops.layers import Leaf

    h, J, di = cfg.hidden_size, cfg.index_heads, cfg.index_head_dim
    return {"wi_q": Leaf((query_width, J * di), query_width, (None, None)),
            "wi_k": Leaf((h, di), h, ("embed", None)),
            "wi_k_norm": Leaf((di,), "ones", (None,)),
            "wi_k_bias": Leaf((di,), "zeros", (None,)),
            "wi_w": Leaf((h, J), h, ("embed", None))}


def index_inputs(cfg, u, q_from, p, rotate, key_norm):
    """The index's queries [b, s, J, d_i], keys [b, s, d_i] and head
    weights [b, s, J] float32: ``q_i = q_from W_iq``, ``k_i = key_norm(u
    W_ik)`` (a LayerNorm with weight and bias at ``cfg.index_norm_eps``),
    both through ``rotate`` (the layer's own rope over the dims it rotates,
    of x [b, s, J, d_i] and of the one key a position [b, s, d_i]), ``w =
    (u W_iw) J ** -0.5 d_i ** -0.5``.
    Neither ``u`` nor ``q_from`` receives a gradient from here."""
    dt = cfg.dtype
    b, s, _ = u.shape
    J, di = cfg.index_heads, cfg.index_head_dim
    u, q_from = jax.lax.stop_gradient(u), jax.lax.stop_gradient(q_from)

    def dot(a, w):
        return jnp.dot(a, w.astype(dt), preferred_element_type=jnp.float32)

    q_i = dot(q_from, p["wi_q"]).astype(dt).reshape(b, s, J, di)
    k_i = key_norm(dot(u, p["wi_k"]).astype(dt), p["wi_k_norm"],
                   p["wi_k_bias"], cfg.index_norm_eps)
    return (rotate(q_i), rotate(k_i),
            dot(u, p["wi_w"]) * (J ** -0.5 * di ** -0.5))


def index_report(b: int, s: int, kl, pairs, kept=None) -> Dict[str, Any]:
    """What an index layer reports under "dsa": its ``kl`` and ``pairs``
    [b], the causal pairs and positions they are shares of, and ``kept``
    (asked for: the choice and the index's inputs)."""
    return {"dsa": {"kl": kl, "pairs": pairs,
                    "causal": jnp.asarray(b * s * (s + 1) // 2, jnp.float32),
                    "positions": jnp.asarray(b * s, jnp.float32),
                    **(kept or {})}}


def index_terms(cfg, said):
    """``Part.terms`` of a stack's index layers: ``said``'s ``kl`` and
    ``pairs`` [Lf, b], ``causal`` and ``positions`` [Lf] -> (the term the
    loss gains, the step's counters)."""
    loss = (said["kl"].sum(-1) / said["positions"]).sum()
    share = said["pairs"].sum() / said["causal"].sum()
    return cfg.index_loss_coef * loss, {"dsa_index_loss": loss,
                                        "dsa_pairs_chosen_share": share}


def walk_rows(cfg, tokens: int, widths: Widths) -> int:
    """Bytes of one block of the walk while its backward runs, as each
    form holds it in HBM, float32 (a part's ``keeps``: ``rows``). The
    index's scores: XLA's products [block, J, s] and their gradient, the
    kernels' [block, s] and its. The attention: XLA's heads' scores and
    probabilities [H, block, s] twice, the kernels' summed [block, s] and
    the target."""
    blk, trs = walk_plan(tokens, cfg.index_block, cfg.index_tiers, widths)
    scores = scores_plan(blk, tokens // trs, widths.index_heads,
                         widths.index_dim)["scores_form"]
    attend = attend_plan(blk, tokens // trs, widths.d_n,
                         widths.d_v)["attend_form"]
    return blk * tokens * 4 * (
        (3 * widths.index_heads if scores == "xla" else 2)
        + (4 * widths.heads if attend == "xla" else 2))


def unpack_choice(packed, s: Optional[int] = None):
    """``sparse_attention``'s packed choice [.., s, s / 8] -> bool [.., s,
    s]."""
    bits = jnp.unpackbits(packed, axis=-1).astype(bool)
    return bits if s is None else bits[..., :s]
