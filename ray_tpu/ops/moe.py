"""Routed mixture of experts (SwiGLU, or two matrices around a squared
ReLU): sorted, dropless, every shape static.

The layer the many-small-expert models share (OLMoE, Mixtral, Laguna,
LFM2). A token picks
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

``held=(first, count)`` is the layer expert parallelism needs, run
without its exchange: the weights of experts ``first .. first + count``
of ``E`` live here, the router, the softmax, the top-k and ``counts`` are
over all ``E``, and the result is the part those experts give. The pairs
are sorted with the held experts' first; what follows them is never
gathered or multiplied. How many rows that is, is data, and a buffer for
the worst case (every row routed here) would be ``n * top_k`` rows wide,
a gigabyte at Laguna's cell: the held rows are taken in passes of a
static ``chunk`` of rows (``_held_chunk``: the balanced share and an
eighth of it, a larger part for fewer than 16 experts or where the
configuration's ``held_headroom`` says so), as many passes as
the rows need, each a gather, three grouped matmuls over the pass's groups
and a scatter-add into the tokens' float32 sums. The sums are one ``[n,
h]`` array up to 4,096 columns and past that blocks of at most 1,280
columns, carried apart through the passes and joined in the last cast
(``_sum_columns``): XLA's scatter-add walks the whole table, and what that
costs a column is a sawtooth in the width, 15.9 ms for a pass's 3,072 rows
into ``[8192, 5120]`` where four sums of 1,280 take 2.2 (v5e, PR 44; the
same adds of the same rows, so the same sums). One pass at a balanced routing
and up to that part over it, none where no row is held; no routing,
however uneven, drops a row or compiles anything. The grouped matmuls touch the row tiles the
pass's groups fill and no other; the gathers, the activation's pass,
the masks and the scatter-adds run over all ``chunk`` rows of a pass,
held or padding, which is why a pass is no wider than that
(``_HELD_HEADROOM``). The plan of a traced layer is one kept span,
``rtpu.moe.held_pass`` (pairs, count, num_experts, balanced_share,
chunk, sum_blocks); ``rows_passed`` counts a step's passes from its expert
counts.
The loop's trip count is data, so its gradient is written out
(``_held_experts``): the same passes, each the transpose of its forward.

Named scopes (metadata only, nested under the caller's ``mlp``; a
backward operation carries the scope of the call it transposes):
``moe_route`` (router matmul, scores, top-k, sort), ``moe_dispatch``
(gather into expert order), ``moe_experts`` (grouped matmuls and the
activation, the gate weighting in it), ``moe_combine`` (gather back, sum).

Experts in a latent (``routed_part(latent=..)``, ``nemotron_h``'s
LatentMoE): the router reads the hidden state, the rows that are gathered,
multiplied and summed are its down-projection to ``cfg.<latent>`` columns,
and the tokens' sums go up again once (scope ``moe_latent``, both
projections); a held share's ``r_share W_up`` is its part of the layer's
sum, ``W_up`` being linear. Two-matrix experts (``act="relu2"``: ``relu(l
W1)^2 W2``, no ``e_gate`` leaf) run the same passes with one grouped matmul
fewer each way (``_expert_rows``).
"""

from __future__ import annotations

from fractions import Fraction
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.layers import (Leaf, Part, _add_rows, _divisor_tile,
                                _join_sums, _sum_columns, kept, norm_start,
                                relu2_kept, relu2_mlp, rms_norm, swiglu,
                                swiglu_kept)
from ray_tpu.util import tracing


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
# A tile that does not divide its dimension leaves the last one part
# empty too: at 3072 a tile of 2048 cost 12-19% over one of 1536 (v5e,
# 16 groups of 640 and 1,280 rows, PR 30), so both are divisors.
_ROW_TILE = 256


def _gmm_tiles(k: int, n: int) -> Tuple[int, int, int]:
    tk = _divisor_tile(k, 2048)
    return _ROW_TILE, tk, _divisor_tile(n, (2 << 20) // tk)


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
          renormalize: bool = False, scale: float = 1.0,
          score: str = "softmax", select_bias: Optional[jax.Array] = None,
          renorm_eps: float = 0.0, groups: Optional[Tuple[int, int]] = None,
          group_score: str = "max"
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(router_logits [n, E] float32, top_w [n, K] float32, top_e [n, K]
    int32): logits accumulate in float32, the scores are their softmax
    over all E or (``score="sigmoid"``) each logit's sigmoid, in float32,
    then the K largest; ``renormalize`` divides the K weights by their
    sum plus ``renorm_eps`` (Mixtral does, OLMoE does not); ``scale``
    multiplies them after that (Laguna's routed scaling factor).
    ``select_bias [E]`` float32 is added to the scores for the choice of
    experts alone (loss-free balancing, arXiv:2408.15664): the gate
    weights are the scores without it, and no gradient reaches it.
    ``groups=(n_group, topk_group)`` limits the choice (DeepSeek-V2's
    ``group_limited_greedy``): the E experts are ``n_group`` groups of
    neighbours, a group's score is the largest of its experts'
    (``group_score="max"``) or the sum of its two largest (``"top2"``:
    DeepSeek-V3's ``noaux_tc``), and the K are the largest scores inside
    the ``topk_group`` best groups. With a ``select_bias`` the groups are
    scored and the K chosen on ``score + bias``, the weights being the
    scores without it."""
    logits = jnp.dot(x, router_w.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router score {score!r} (softmax | sigmoid)")
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if group_score not in ("max", "top2"):
        raise ValueError(f"unknown group score {group_score!r} (max | top2)")
    if groups is not None and (select_bias is not None
                               or group_score != "max"):
        # the choice is made on ``score + bias`` (the scores without one):
        # the groups' scores and the K inside the kept groups; the weights
        # are the scores. ``route_choice`` is looked up at trace time
        top_e = route_choice(scores, select_bias, top_k, groups, group_score)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    elif groups is not None:
        n_group, kept_groups = groups
        by_group = scores.reshape(scores.shape[0], n_group, -1)
        _, best = jax.lax.top_k(
            jax.lax.stop_gradient(by_group.max(-1)), kept_groups)
        allowed = (best[..., None] == jnp.arange(n_group)).any(-2)
        # a score is positive, so one outside the kept groups is never
        # among the K while K experts lie inside them
        top_w, top_e = jax.lax.top_k(jnp.where(
            allowed[..., None], by_group, 0.0).reshape(scores.shape), top_k)
    elif select_bias is None:
        top_w, top_e = jax.lax.top_k(scores, top_k)
    else:
        _, top_e = jax.lax.top_k(
            jax.lax.stop_gradient(scores + select_bias.astype(jnp.float32)),
            top_k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if renormalize:
        norm = top_w.sum(-1, keepdims=True)
        top_w = top_w / (norm + renorm_eps if renorm_eps else norm)
    if scale != 1.0:
        top_w = top_w * scale
    return logits, top_w, top_e


def route_choice(scores: jax.Array, select_bias: Optional[jax.Array],
                 top_k: int, groups: Tuple[int, int], group_score: str
                 ) -> jax.Array:
    """``route``'s choice under a group limit with a selection bias (or a
    group scored by its two largest): scores [n, E] float32 -> the K
    experts [n, K] with the largest ``score + bias`` inside the
    ``topk_group`` groups whose own score (their largest ``score + bias``,
    or the sum of their two largest) is best. No gradient passes."""
    n_group, kept_groups = groups
    on = jax.lax.stop_gradient(scores if select_bias is None else
                               scores + select_bias.astype(jnp.float32))
    by_group = on.reshape(on.shape[0], n_group, -1)
    of_group = (by_group.max(-1) if group_score == "max"
                else jax.lax.top_k(by_group, 2)[0].sum(-1))
    _, best = jax.lax.top_k(of_group, kept_groups)
    allowed = (best[..., None] == jnp.arange(n_group)).any(-2)
    # ``score + bias`` may be negative: -inf outside the kept groups
    return jax.lax.top_k(jnp.where(
        allowed[..., None], by_group, -jnp.inf).reshape(on.shape), top_k)[1]


def _swiglu_rows(rows, w_rows, sizes, e_gate, e_up, e_down):
    """rows [m, h] in groups of ``sizes``, their gate weights w_rows [m]
    float32 -> [m, h]: the experts' three grouped matmuls, the gate
    weight inside the activation's elementwise pass (the module's
    docstring)."""
    dt = rows.dtype
    # named as ops/layers.swiglu names its two products, for the MLP rung
    # of a layer's remat level (models/llama.py REMAT_LADDER): kept, the
    # backward runs neither grouped matmul again. Inert without a level,
    # and inside the held experts' passes, whose residuals are their inputs.
    gate = checkpoint_name(grouped_matmul(rows, e_gate.astype(dt), sizes),
                           "mlp_gate")
    up = checkpoint_name(grouped_matmul(rows, e_up.astype(dt), sizes),
                         "mlp_up")
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32) * w_rows[:, None])
    return grouped_matmul(act.astype(dt), e_down.astype(dt), sizes)


def _relu2_rows(rows, w_rows, sizes, e_up, e_down):
    """``_swiglu_rows`` for experts of two matrices around a squared ReLU:
    rows [m, l] -> [m, l], the gate weight inside the activation's pass."""
    dt = rows.dtype
    up = checkpoint_name(grouped_matmul(rows, e_up.astype(dt), sizes),
                         "mlp_up")
    act = jnp.square(jax.nn.relu(up.astype(jnp.float32))) * w_rows[:, None]
    return grouped_matmul(act.astype(dt), e_down.astype(dt), sizes)


def _expert_rows(rows, w_rows, sizes, *weights):
    """The experts' pass over their rows: three matrices are a SwiGLU's
    (gate, up, down), two a squared-ReLU MLP's (up, down)."""
    rows_of = _swiglu_rows if len(weights) == 3 else _relu2_rows
    return rows_of(rows, w_rows, sizes, *weights)


# A pass of the held experts' rows takes their balanced share and one part
# in this many of it. From a sweep of the op alone on v5e (forward and
# backward, 16,384 tokens, PR 35), the pass's rows a variable: at LFM2's
# widths (16 of 32 experts of 1792, 4 a token) 4.0 ms + 3.4 ms a pass +
# 0.33 us a row of the pass + 0.43 us a held row, at Laguna's (16 of 256
# of 1024, 10 a token) 6.2 + 5.3 + 0.39 + 0.39: a pass costs as much to
# start as 10,000-13,000 of its rows, so one pass with headroom beats
# several under the share (a quarter of the share: 42.6 ms for 34.0 at
# balance), and a padding row costs what a held one does outside the
# kernels, so twice the share read 42.7 (LFM2) and 23.5 (Laguna). Over held
# rows of 0.9, 1.0 and 1.1 times the share an eighth was the cheapest of
# 0, 1/16, 1/8, 3/16, 1/4, 1/2 and 1 at both: 33.9 and 20.2 ms (1/16 pays
# a second pass at +10%: 38.4; 1/4 reads 35.2).
# A share of fewer experts wanders more beside its mean: the held rows are
# the sum of ``count`` experts' loads, whose relative spread falls as
# ``1 / sqrt(count)``, so the headroom is one part in ``2 sqrt(count)`` (an
# eighth at the 16 both sweeps held). At 8 of 160 experts, 6 a token, 8,192
# tokens (v5e, PR 43) a layer's held rows read 2,458 +- ~220 from seed to
# seed (a seeded router's experts draw 0.6-1.7 times their share); an eighth
# over the share (2,816 rows) sent a layer of every few steps into a second
# pass, 35 ms on a step of 704, and a cell's rate apart by 1.2% between two
# seeds.
# The rule reckons with experts that draw 0.6-1.7 times their share. Where a
# router's loads lie farther out the configuration says so
# (``held_headroom``, one part in that many): at 64 of 512 experts, 10 a
# token, 32,768 tokens (v5e, PR 50, ``benchmark/tests/held_share_spread.py``)
# a seeded router's experts draw up to 4-5 times their share and a layer's
# held rows read 0.82-1.18 of the share over 160 layers of 40 seeds, 6.5% a
# standard deviation where the rule expects 3; an eighth sent a layer of 5
# of the 40 seeds into a second pass (a step of 1,080.6 ms for 1,058.5), a
# quarter none, for 7 ms of padding rows a step (1,065.5; a third 1,064.5, a
# half 1,079.7).
_HELD_HEADROOM = 8


def _held_chunk(num_pairs: int, count: int, num_experts: int,
                headroom=None) -> int:
    """Rows a pass of the held experts takes: their balanced share of the
    ``num_pairs`` (token, choice) pairs and one part in ``2 sqrt(count)``
    of it (an eighth, ``_HELD_HEADROOM``, from 16 experts up) or, where a
    configuration says how far its routers' loads lie from balance
    (``held_headroom``), one part in ``headroom`` (a half: twice the share
    over it, for a share of few experts whose loads lie far apart); in whole
    row tiles, at most all the pairs."""
    part = headroom or min(_HELD_HEADROOM, max(2, round(2 * count ** 0.5)))
    # (a whole number stays the arithmetic it was: p / 1)
    p, q = Fraction(part).limit_denominator(64).as_integer_ratio()
    rows = -(-num_pairs * count * (p + q) // (num_experts * p))
    return min(-(-rows // _ROW_TILE), -(-num_pairs // _ROW_TILE)) * _ROW_TILE


def rows_passed(expert_counts, held: Optional[Tuple[int, int]],
                headroom: Optional[int] = None) -> int:
    """Of ``expert_counts [Lr, E]`` on the host, the rows the passes over
    the ``held`` experts' rows took: each layer's held rows in whole
    passes of ``_held_chunk`` rows (the ``moe_rows_passed`` counter; the
    held rows over it is how full the passes were). ``held=None`` has no
    passes and multiplies every row once."""
    counts = np.asarray(expert_counts)
    if held is None:
        return int(counts.sum())
    first, count = held
    chunk = _held_chunk(int(counts[0].sum()), count, counts.shape[-1],
                        headroom)
    rows = counts[:, first:first + count].sum(-1)
    return int((-(-rows // chunk) * chunk).sum())


def _zero_sums(n: int, h: int):
    """The tokens' sums before a pass: float32 ``[n, _sum_columns(h)]``
    blocks of columns, carried apart."""
    width = _sum_columns(h)
    return tuple(jnp.zeros((n, width), jnp.float32)
                 for _ in range(h // width))


def _held_passes(sizes, chunk: int):
    """Passes the held rows need: none where no row is held."""
    return -(-sizes.sum() // chunk)


def _held_pass(i, x, w_pairs, order, sizes, top_k: int, chunk: int):
    """Pass ``i`` over the held rows: (pair ids [chunk], which of them are
    held rows [chunk], their tokens, their rows of ``x``, their gate
    weights, the pass's group sizes [count])."""
    lo = i * chunk
    with jax.named_scope("moe_dispatch"):
        ends = jnp.cumsum(sizes)
        pairs = jax.lax.dynamic_slice(order, (lo,), (chunk,))
        valid = lo + jnp.arange(chunk) < ends[-1]
        tokens = pairs // top_k
        in_pass = (jnp.clip(ends, lo, lo + chunk)
                   - jnp.clip(ends - sizes, lo, lo + chunk))
        return pairs, valid, tokens, x[tokens], w_pairs[pairs], in_pass


def _given(*weights):
    """The experts' matrices that are there (no ``e_gate``: two)."""
    return tuple(w for w in weights if w is not None)


@partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _held_experts(x, w_pairs, e_gate, e_up, e_down, order, sizes, top_k,
                  chunk):
    """x [n, h], w_pairs [n * top_k] float32 (pair = token * top_k +
    choice), the held experts' weights [count, ...] (``e_gate`` None:
    experts of two matrices, ``_expert_rows``), order [n * top_k +
    chunk]: pair ids, the held experts' first and in expert order, then
    padding; sizes [count]: rows of each held expert -> [n, h], the sum
    over a token's held choices, accumulated in float32."""

    def one_pass(i, out):
        _, valid, tokens, rows, w_rows, in_pass = _held_pass(
            i, x, w_pairs, order, sizes, top_k, chunk)
        with jax.named_scope("moe_experts"):
            y = _expert_rows(rows, w_rows, in_pass, *_given(e_gate, e_up,
                                                            e_down))
        with jax.named_scope("moe_combine"):
            # a row past the pass's groups is written by no kernel
            y = jnp.where(valid[:, None], y.astype(jnp.float32), 0.0)
            return _add_rows(out, tokens, y)

    out = jax.lax.fori_loop(0, _held_passes(sizes, chunk), one_pass,
                            _zero_sums(*x.shape))
    return _join_sums(out, x.dtype)


def _held_experts_fwd(x, w_pairs, e_gate, e_up, e_down, order, sizes, top_k,
                      chunk):
    return (_held_experts(x, w_pairs, e_gate, e_up, e_down, order, sizes,
                          top_k, chunk),
            (x, w_pairs, e_gate, e_up, e_down, order, sizes))


def _held_experts_bwd(top_k, chunk, res, d_out):
    x, w_pairs, e_gate, e_up, e_down, order, sizes = res
    weights = _given(e_gate, e_up, e_down)

    def one_pass(i, carry):
        d_x, d_w, d_weights = carry
        pairs, valid, tokens, rows, w_rows, in_pass = _held_pass(
            i, x, w_pairs, order, sizes, top_k, chunk)
        with jax.named_scope("moe_combine"):
            d_y = jnp.where(valid[:, None], d_out[tokens], 0)
        with jax.named_scope("moe_experts"):
            _, transpose = jax.vjp(
                lambda r, w, *ws: _expert_rows(r, w, in_pass, *ws),
                rows, w_rows, *weights)
            d_rows, d_w_rows, *d_ws = transpose(d_y)
        with jax.named_scope("moe_dispatch"):
            d_rows = jnp.where(valid[:, None], d_rows.astype(jnp.float32), 0.0)
            d_w_rows = jnp.where(valid, d_w_rows, 0.0)
            return (_add_rows(d_x, tokens, d_rows),
                    d_w.at[pairs].add(d_w_rows),
                    tuple(a + b for a, b in zip(d_weights, d_ws)))

    d_x, d_w, d_weights = jax.lax.fori_loop(
        0, _held_passes(sizes, chunk), one_pass,
        (_zero_sums(*x.shape), jnp.zeros_like(w_pairs),
         tuple(jnp.zeros_like(w) for w in weights)))
    return ((_join_sums(d_x, x.dtype), d_w) + (None,) * (3 - len(weights))
            + d_weights + (None, None))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def routed_experts(x: jax.Array, router_w: jax.Array, e_gate: jax.Array,
                   e_up: jax.Array, e_down: jax.Array, top_k: int,
                   renormalize: bool = False,
                   held: Optional[Tuple[int, int]] = None,
                   scale: float = 1.0, score: str = "softmax",
                   select_bias: Optional[jax.Array] = None,
                   renorm_eps: float = 0.0, keep_choices: bool = False,
                   groups: Optional[Tuple[int, int]] = None,
                   headroom: Optional[int] = None,
                   router_x: Optional[jax.Array] = None,
                   group_score: str = "max"
                   ) -> Tuple[jax.Array, ...]:
    """x [n, h], router_w [h, E], e_gate / e_up [E, h, f], e_down
    [E, f, h] -> (out [n, h], router_logits [n, E] float32, counts [E]
    int32: rows routed to each expert, n * top_k in all). A row's gate
    weight goes into its activation, before the down projection (above).
    ``held=(first, count)``: the expert weights are those of experts
    ``first .. first + count`` alone, ``[count, ...]``, and ``out`` is
    their part of the result (the module's docstring) in passes of
    ``_held_chunk(.., headroom)`` rows; ``None``: all
    ``E`` are here. ``score``, ``select_bias``, ``renorm_eps``,
    ``groups`` and ``group_score`` are ``route``'s; ``keep_choices``
    appends ``route``'s own ``top_e [n, K]`` and ``top_w [n, K]`` to the
    result, for a check of what was chosen and how it was weighted.
    ``e_gate=None``: experts of two matrices around a squared ReLU
    (``_expert_rows``). ``router_x
    [n, hidden]``: what the router reads where that is not the rows the
    experts multiply (experts in a latent: ``x`` is then ``[n, latent]``,
    and so are ``out`` and the experts' outer widths)."""
    num_experts = router_w.shape[-1]
    weights = _given(e_gate, e_up, e_down)
    with jax.named_scope("moe_route"):
        logits, top_w, top_e = route(
            x if router_x is None else router_x, router_w, top_k,
            renormalize, scale, score=score,
            select_bias=select_bias, renorm_eps=renorm_eps, groups=groups,
            group_score=group_score)
        choices = (top_e, top_w) if keep_choices else ()
        flat_e = top_e.reshape(-1)
        if held is not None:
            first, count = held
            counts = (flat_e[:, None] == jnp.arange(num_experts)[None, :]
                      ).sum(0, dtype=jnp.int32)
            # the held experts' pairs first, an expert's in token order
            local = flat_e - first
            key = jnp.where((local >= 0) & (local < count), local, count)
            chunk = _held_chunk(flat_e.size, count, num_experts, headroom)
            with tracing.span("rtpu.moe.held_pass", keep=True,
                              pairs=flat_e.size, count=count,
                              num_experts=num_experts,
                              balanced_share=flat_e.size * count
                              / num_experts, chunk=chunk,
                              sum_blocks=x.shape[1]
                              // _sum_columns(x.shape[1])):
                pass
            order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                            (0, chunk))
            out = _held_experts(
                x, top_w.reshape(-1), e_gate, e_up, e_down, order,
                jax.lax.dynamic_slice(counts, (first,), (count,)), top_k,
                chunk)
            return (out, logits, counts) + choices
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
        rows = _expert_rows(rows, w_rows, counts, *weights)
    with jax.named_scope("moe_combine"):
        out = _combine(rows, token_of, inv, top_k)
    return (out, logits, counts) + choices


def routed_experts_on(mesh, x: jax.Array, router_w: jax.Array,
                      e_gate: jax.Array, e_up: jax.Array, e_down: jax.Array,
                      top_k: int, select_bias: Optional[jax.Array] = None,
                      router_x: Optional[jax.Array] = None,
                      **how) -> Tuple[jax.Array, ...]:
    """``routed_experts`` for x [b, s, h] -> (out [b, s, h], router_logits
    [b * s, E] float32, counts [E] and, under ``keep_choices``, the
    choices and their weights [b * s, K]); ``how`` is its keywords,
    ``router_x [b, s, hidden]`` what the router reads where ``x`` is a
    latent. On a mesh every chip routes its own rows of the batch to all
    the experts here (their weights gathered whole, as fsdp gathers any
    weight): the sort and the grouped matmuls stay local, which a Mosaic
    call under a sharded jit needs anyway, and ``counts`` are summed over
    the batch axes."""
    h = x.shape[-1]
    weights = (router_w, e_gate, e_up, e_down) + (
        () if select_bias is None else (select_bias,))

    def local(x_, router, e_gate, e_up, e_down, bias=None, read=None):
        out, *stats = routed_experts(
            x_.reshape(-1, h), router, e_gate, e_up, e_down, top_k,
            select_bias=bias, **how,
            **({} if read is None else
               {"router_x": read.reshape(-1, read.shape[-1])}))
        return (out.reshape(x_.shape), *stats)

    if mesh is None:
        return local(x, *weights, read=router_x)
    if router_x is not None or e_gate is None:
        raise NotImplementedError(
            "experts in a latent and experts of two matrices run without a "
            "mesh: the latent rows' exchange is not built (parallel/)")
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import resolve_axis

    rows = resolve_axis("batch", mesh)

    def sharded(x_, *w):
        out, logits, counts, *chosen = local(x_, *w)
        return (out, logits, jax.lax.psum(counts, rows) if rows else counts,
                *chosen)

    by_row = (P(rows),) * 2 * bool(how.get("keep_choices"))
    return jax.shard_map(
        sharded, mesh=mesh, in_specs=(P(rows),) + (P(),) * len(weights),
        out_specs=(P(rows), P(rows), P()) + by_row,
        check_vma=False)(x, *weights)


def router_stats(logits: jax.Array, counts: jax.Array
                 ) -> Dict[str, jax.Array]:
    """What ``router_losses`` reads of one layer: the rows routed to each
    expert, the mean router probability [E] and the mean squared
    logsumexp of the router logits [n, E]."""
    with jax.named_scope("moe_route"):
        return {"counts": counts,
                "prob": jax.nn.softmax(logits, axis=-1).mean(0),
                "z": jnp.square(jax.nn.logsumexp(logits, axis=-1)).mean()}


def router_losses(cfg, router: Dict[str, jax.Array]
                  ) -> Tuple[jax.Array, jax.Array]:
    """(load-balancing loss, router z-loss), before their coefficients,
    of the layers' ``router_stats`` stacked. Both are over all layers'
    tokens together, as transformers concatenates the layers' router
    logits."""
    counts = router["counts"].astype(jnp.float32)
    share = counts.sum(0) / (counts.sum() / cfg.top_k)     # f_e, sums to K
    balance = cfg.num_experts * jnp.sum(share * router["prob"].mean(0))
    return balance, router["z"].mean()


def sequence_balance(cfg, router: Dict[str, jax.Array]) -> jax.Array:
    """DeepSeek-V2's ``seq_aux`` balancing loss before its coefficient, of
    the routed layers' reports stacked (``seq_counts`` and ``seq_prob``
    [Lr, b, E]): for each layer and each sequence ``sum_e f_e P_e``, ``f_e``
    the times the sequence's tokens chose expert ``e`` x E / (K x length)
    and ``P_e`` the sequence's mean score; the mean over the sequences,
    summed over the layers (each layer adds its own term to the loss)."""
    counts = router["seq_counts"].astype(jnp.float32)
    share = counts * (cfg.num_experts / counts.sum(-1, keepdims=True))
    return (share * router["seq_prob"]).sum(-1).mean(-1).sum()


def _token_gated(out, u, w):
    """out and u [b, s, h], w [h] -> ``sigmoid(u . w) * out``, one number
    a token, float32 inside."""
    gate = jax.nn.sigmoid(jnp.dot(u, w, preferred_element_type=jnp.float32))
    return (out.astype(jnp.float32) * gate[..., None]).astype(out.dtype)


def _to_latent(x, w):
    """x [.., a] @ w [a, b], float32 inside: a latent layer's down- and
    up-projection."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def routed_part(shared=False, score: str = "softmax",
                bias: bool = False, renorm_eps: Optional[str] = None,
                balance=False, width: str = "moe_intermediate_size",
                renormalize: bool = True, groups: bool = False,
                latent: Optional[str] = None, act: str = "silu",
                group_score: str = "max") -> Part:
    """A routed mixture as a layer's MLP (or, in a table of one-part
    kinds, the whole layer): ``x + [shared(u)] + routed(u)``,
    ``u = RMSNorm(x)``: ``cfg.num_experts`` experts of ``width`` (the
    config's field), SwiGLUs of three matrices (``act="silu"``) or
    ``relu(. W1)^2 W2`` of two (``"relu2"``: no ``e_gate`` leaf),
    ``cfg.top_k`` a token, the gate weights renormalised
    (or, ``renormalize=False``, left the scores they are) and times
    ``cfg.routed_scale``, ``cfg.experts_held`` of them here (a
    config without the field holds them all), their rows in passes with
    ``cfg.held_headroom`` over the balanced share where the config has the
    field (``_held_chunk``). ``shared``: a SwiGLU of
    ``cfg.shared_intermediate_size`` beside them, added ungated (True:
    Laguna) or, "gated", times ``sigmoid(u . s_sigmoid)``, one number a
    token from a vector of its own (Qwen3-Next; the scope
    ``moe_shared_gate`` inside ``moe_shared``) or, "relu2", a squared-ReLU
    MLP of that width, ungated (``nemotron_h``). ``latent`` names the
    config's field of a latent's width: the router reads ``u``, the experts
    multiply ``u l_down`` (``[.., latent]`` rows through the gathers, the
    held passes and their float32 sums) and the tokens' sums go through
    ``l_up`` once, both projections under the scope ``moe_latent``; a
    traced layer writes the kept span ``rtpu.moe.latent_plan`` once. The
    norm is as the config's are (``cfg.zero_centred_norm``).
    ``score="sigmoid"`` and ``bias`` (a ``router_bias`` that takes part in
    the choice alone, float32, no optimizer's) are LFM2's router,
    ``renorm_eps`` names its field. ``balance``: a layer reports
    ``router_stats`` and the loss gains ``cfg.router_aux_coef`` x
    ``router_losses``' load-balancing term; ``balance="sequence"``: a layer
    reports each sequence's counts and mean scores and the loss gains
    ``cfg.router_aux_coef`` x ``sequence_balance``; without, the counts
    alone. ``groups``: the choice is limited to ``cfg.topk_group`` of
    ``cfg.n_group`` groups of experts (``route``; ``group_score`` is its
    too, and with ``bias`` the groups are scored on ``score + bias``). A
    layer reports under "router"; asked for (``ctx.keep_router_logits``),
    the router's logits too and, where a bias or a group limit took part
    in them, ``route``'s own choices and the weights it gave them."""
    by_sequence = balance == "sequence"
    if act not in ("silu", "relu2"):
        raise ValueError(f"unknown expert activation {act!r} (silu | relu2)")
    if shared not in (False, True, "gated", "relu2"):
        raise ValueError(f"unknown shared expert {shared!r}")

    def held(cfg):
        return getattr(cfg, "experts_held", None)

    def headroom(cfg):
        return getattr(cfg, "held_headroom", None)

    def leaves(cfg):
        h, E, f = cfg.hidden_size, cfg.num_experts, getattr(cfg, width)
        here = held(cfg)[1] if held(cfg) else E
        experts = ("expert", "embed", "mlp")
        out = {"mlp_norm": Leaf((h,), norm_start(cfg), ("embed",)),
               "router": Leaf((h, E), h, ("embed", None))}
        if bias:
            out["router_bias"] = Leaf((E,), "zeros_float32", (None,))
        # the width of an expert's rows: the latent's, or the hidden state's
        l = getattr(cfg, latent) if latent else h
        if latent:
            out.update(l_down=Leaf((h, l), h, ("embed", "mlp")),
                       l_up=Leaf((l, h), l, ("mlp", "embed")))
        if act == "silu":
            out["e_gate"] = Leaf((here, l, f), l, experts)
        out.update(e_up=Leaf((here, l, f), l, experts),
                   e_down=Leaf((here, f, l), f, ("expert", "mlp", "embed")))
        if shared:
            sf = cfg.shared_intermediate_size
            if shared != "relu2":
                out["s_gate"] = Leaf((h, sf), h, ("embed", "mlp"))
            out.update(s_up=Leaf((h, sf), h, ("embed", "mlp")),
                       s_down=Leaf((sf, h), sf, ("mlp", "embed")))
        if shared == "gated":
            out["s_sigmoid"] = Leaf((h,), h, ("embed",))
        return out

    def body(cfg, x, p, ctx):
        dt = cfg.dtype
        with jax.named_scope("mlp"):
            h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps,
                          cfg.zero_centred_norm)
            if shared == "relu2":
                with jax.named_scope("moe_shared"):
                    beside = relu2_mlp(h2, p["s_up"].astype(dt),
                                       p["s_down"].astype(dt))
            elif shared:
                with jax.named_scope("moe_shared"):
                    beside = swiglu(h2, p["s_gate"].astype(dt),
                                    p["s_up"].astype(dt),
                                    p["s_down"].astype(dt))
                    if shared == "gated":
                        with jax.named_scope("moe_shared_gate"):
                            # looked up at trace time (delta_moe_limits.py)
                            beside = _token_gated(beside, h2,
                                                  p["s_sigmoid"].astype(dt))
            rows, read = h2, {}
            if latent:
                with jax.named_scope("moe_latent"):
                    # looked up at trace time (scan_moe_limits.py)
                    rows = _to_latent(h2, p["l_down"].astype(dt))
                read = {"router_x": h2}
                with tracing.span(
                        "rtpu.moe.latent_plan", keep=True,
                        hidden=x.shape[-1], latent=rows.shape[-1],
                        experts=p["router"].shape[-1],
                        held=p["e_up"].shape[0], top_k=cfg.top_k, act=act,
                        rows_a_pass=_held_chunk(
                            x.shape[0] * x.shape[1] * cfg.top_k,
                            held(cfg)[1], p["router"].shape[-1],
                            headroom(cfg)) if held(cfg) else None):
                    pass
            out, logits, counts, *chosen = routed_experts_on(
                ctx.mesh, rows, p["router"], p.get("e_gate"), p["e_up"],
                p["e_down"], cfg.top_k, **read, renormalize=renormalize,
                select_bias=p["router_bias"] if bias else None,
                held=held(cfg), scale=cfg.routed_scale, score=score,
                renorm_eps=getattr(cfg, renorm_eps) if renorm_eps else 0.0,
                keep_choices=by_sequence or (
                    (bias or groups) and ctx.keep_router_logits),
                groups=(cfg.n_group, cfg.topk_group) if groups else None,
                headroom=headroom(cfg), group_score=group_score)
            if by_sequence:
                with jax.named_scope("moe_route"):
                    b, E = x.shape[0], counts.shape[0]
                    router = {
                        "counts": counts,
                        "seq_counts": (chosen[0].reshape(b, -1, 1)
                                       == jnp.arange(E)).sum(
                                           1, dtype=jnp.int32),
                        "seq_prob": jax.nn.softmax(logits, -1).reshape(
                            b, -1, E).mean(1)}
            else:
                router = (router_stats(logits, counts) if balance
                          else {"counts": counts})
            if ctx.keep_router_logits:
                router["logits"] = logits
                if bias or groups:
                    router["chosen"], router["weights"] = chosen
            if latent:
                with jax.named_scope("moe_latent"):
                    out = _to_latent(out, p["l_up"].astype(dt))
            return (x + beside if shared else x) + out, {"router": router}

    def keeps(cfg, shape, tokens, mesh):
        # h: the width of an expert's rows (a latent's, or the hidden
        # state's); mats: an expert's [rows, f] arrays (gate, up, act)
        mats = 3 if "e_gate" in shape else 2
        h, f = shape["e_gate" if mats == 3 else "e_up"][-2:]
        act = jnp.dtype(cfg.dtype).itemsize
        pairs, mlp, rows = tokens * cfg.top_k, 0, 0
        if held(cfg) is None:
            # the two products carry the MLP rung's names (``_swiglu_rows``),
            # a row a (token, choice) pair
            mlp = (mats - 1) * pairs * f * act
        else:
            # a pass's rows alone are gathered and multiplied, and the
            # passes add into two float32 [T, h] sums; nothing of a pass is
            # kept (``_held_experts``: its residuals are its inputs)
            pairs = _held_chunk(pairs, held(cfg)[1], shape["router"][-1],
                                headroom(cfg))
            rows = 2 * tokens * h * 4
        # the rows and their gradient, the three [pairs, f] arrays of the
        # experts' SwiGLU and theirs
        rows += pairs * (2 * h + 2 * mats * f) * act
        beside = (relu2_kept(tokens, shape["s_up"][-1], act)
                  if shared == "relu2"
                  else swiglu_kept(tokens, shape["s_gate"][-1], act)
                  if shared else kept())
        # a latent: the rows, the sums and the gradients of both
        return kept(mlp=mlp + beside["rungs"][2],
                    width=beside["width"] + (4 * h if latent else 0),
                    rows=rows)

    def terms(cfg, router):
        counts = {"expert_counts": router["counts"]}
        if not balance:
            return None, counts
        load = (sequence_balance(cfg, router) if by_sequence
                else router_losses(cfg, router)[0])
        return cfg.router_aux_coef * load, {"load_balance": load, **counts}

    return Part(leaves, body, keeps, reports="router", terms=terms)
