"""Gated short convolution: the operator of an LFM2 ``conv`` layer.

``[B, C, X] = split3(h @ w_in)``; ``u = B * X``; a causal depthwise
convolution of ``taps`` taps per channel, ``v_t = sum_j w[:, j] *
u_{t - (taps - 1) + j}`` with zeros before position 0 (``w[:, -1]`` weighs
the position's own value, as a torch ``Conv1d`` with left padding does);
``y = (C * v) @ w_out``. No biases.

Between the two projections lies one elementwise pass (``conv_mix``): it
reads the in-projection's three thirds and writes one, float32 inside,
the activations' dtype in and out. It is written as ``taps`` shifted
multiply-adds in ``jax.numpy``: XLA fuses the gate, the shifts and the
second gate into one fusion forward and one backward (read on a v5e, PR
32: PERF.md 6), so there is no kernel. The shift is along the sequence
axis of a ``[batch, seq, channels]`` array: a sequence never sees the one
before it in the batch.

Named scopes (metadata only): ``short_conv`` holds ``conv_in`` (the norm
is the caller's, the in-projection), ``conv_mix`` (the pass) and
``conv_out`` (the out-projection).

``taps_silu`` is the other pass of this file, a Mamba-2 layer's
(``ops/ssm.causal_conv_silu``): the same taps, a bias and a silu, as a
Pallas kernel pair behind a ``custom_vjp``. At 32,768 positions XLA lays
``causal_taps``' shifted copies out in HBM, each a float32 ``[seq,
channels]`` (read on a v5e, PR 36 and 37: PERF.md 6); the kernels read the
activations where the in-projection left them, once forward and once
backward, and keep what is float32 in VMEM. ``causal_taps`` stays what
LFM2's pass runs, the form off the TPU and the kernels' reference in the
tests.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import Leaf, Part, kept, rms_norm


def causal_taps(u: jax.Array, w: jax.Array) -> jax.Array:
    """u [b, s, c] float32, w [c, taps] -> the causal depthwise convolution
    ``v_t = sum_j w[:, j] * u_{t - (taps - 1) + j}`` [b, s, c] float32, as
    ``taps`` shifted multiply-adds (``ops/ssm.py``'s taps are these too)."""
    s, taps = u.shape[1], w.shape[-1]
    wf = w.astype(jnp.float32)
    # u_{t - back}: ``back`` zeros before position 0, the tail cut off
    return sum(wf[:, taps - 1 - back]
               * (u if back == 0
                  else jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :s])
               for back in range(min(taps, s)))


def conv_mix(bcx: jax.Array, w: jax.Array) -> jax.Array:
    """bcx [b, s, 3c] (the in-projection's output: B, C, X thirds), w
    [c, taps] -> C * conv(B * X) [b, s, c] in ``bcx``'s dtype, float32
    inside."""
    b_, c_, x_ = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return (c_ * causal_taps(b_ * x_, w)).astype(bcx.dtype)


def gated_short_conv(h: jax.Array, w_in: jax.Array, w_conv: jax.Array,
                     w_out: jax.Array) -> jax.Array:
    """h [b, s, hidden] (normed), w_in [hidden, 3c], w_conv [c, taps],
    w_out [c, hidden] -> [b, s, hidden]; matmuls in ``h``'s dtype,
    accumulated in float32."""
    dt = h.dtype
    with jax.named_scope("short_conv"):
        with jax.named_scope("conv_in"):
            bcx = jnp.dot(h, w_in.astype(dt),
                          preferred_element_type=jnp.float32).astype(dt)
        with jax.named_scope("conv_mix"):
            y = conv_mix(bcx, w_conv)
        with jax.named_scope("conv_out"):
            return jnp.dot(y, w_out.astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)


def short_conv_part() -> Part:
    """The gated short convolution as a layer's mixer: ``x +
    gated_short_conv(RMSNorm(x))`` over ``cfg.hidden_size`` channels with
    ``cfg.conv_taps`` taps."""
    def leaves(cfg):
        h, taps = cfg.hidden_size, cfg.conv_taps
        return {"op_norm": Leaf((h,), "ones", ("embed",)),
                "w_in": Leaf((h, 3 * h), h, ("embed", "mlp")),
                "w_conv": Leaf((h, taps), taps, ("mlp", None)),
                "w_out": Leaf((h, h), h, ("mlp", "embed"))}

    def body(cfg, x, p, ctx):
        return x + gated_short_conv(
            rms_norm(x, p["op_norm"], cfg.rms_norm_eps), p["w_in"],
            p["w_conv"], p["w_out"]), {}

    def keeps(cfg, shape, tokens, mesh):
        # the in-projection's thirds, the pass's output and their
        # gradients; no rung of the ladder names anything in it
        return kept(width=2 * shape["w_in"][-1])

    return Part(leaves, body, keeps)


# ---- taps, bias and silu as one pass forward and one backward ----

# what a grid step of ``taps_silu`` takes: positions (on the lanes), and the
# most channels (fewer where a part of the channels is narrower); tests
# patch them
TAPS_BLOCK_ROWS = 4096
TAPS_BLOCK_CHANNELS = 128
# channels the kernels take of a block at a time, all its positions with
# them: one tile of a 16-bit dtype
_GROUP = 16
_HALO = 128     # positions a block reads of a neighbour: one tile's lanes


def taps_plan(batch: int, seq: int, channels: int, taps: int, itemsize: int,
              first: int = 0, sizes: Optional[Sequence[int]] = None
              ) -> Dict[str, Any]:
    """What ``taps_silu`` does with these shapes: a block's positions (no
    more than the sequence's, in whole tiles of ``halo_rows`` positions,
    which is also what a block reads of its neighbours) and channels (what
    divides ``first`` and every part), the grid's blocks, and the bytes
    the blocks' copies move: forward a block and the tile before it in, a
    block out; backward ``u`` with the tiles before and after and ``dy``
    with the tile after in, ``du`` and the float32 sums out."""
    length = min(TAPS_BLOCK_ROWS, -(-seq // _HALO) * _HALO)
    chans = math.gcd(TAPS_BLOCK_CHANNELS, first, *(sizes or (channels,)))
    blocks = batch * -(-seq // length) * (channels // chans)
    one, edge = length * chans * itemsize, _HALO * chans * itemsize
    return {"seq": seq, "channels": channels, "taps": taps,
            "block_rows": length, "block_channels": chans, "blocks": blocks,
            "halo_rows": _HALO,
            "bytes_moved_fwd": blocks * (2 * one + edge),
            "bytes_moved_bwd": blocks * (3 * one + 3 * edge)
            + (taps + 1) * channels * _HALO * 4}


def _shifted(prev, cur, taps):
    """prev [c, 128] (the positions before), cur [c, n] -> ``cur`` as it is
    and moved 1 .. taps - 1 positions back (t holds t - back), float32."""
    from jax.experimental.pallas import tpu as pltpu

    both = jnp.concatenate([prev, cur], axis=1).astype(jnp.float32)
    return [both[:, _HALO:]] + [pltpu.roll(both, back, 1)[:, _HALO:]
                                for back in range(1, taps)]


def _weighed(w, moved):
    """sum_j w[j] u_{t - (taps - 1) + j}, in ``causal_taps``' order; ``w[j]``
    [c, 1], a channel's weight."""
    taps = len(w)
    v = w[taps - 1] * moved[0]
    for back in range(1, taps):
        v = v + w[taps - 1 - back] * moved[back]
    return v


def _sigmoid(v):
    # one transcendental and three products and sums, where 1 / (1 +
    # exp(-v)) is two and a division: the kernels are bound by the VPU
    return 0.5 + 0.5 * jnp.tanh(0.5 * v)


def _owned(parts, refs, run):
    """``run(ref)`` on the one of ``refs`` whose part of the channel blocks
    (``parts``: its first block and the one after its last) this step of
    the grid is in."""
    import jax.experimental.pallas as pl

    if len(parts) == 1:
        return run(refs[0])
    j = pl.program_id(0)
    for (lo, hi), ref in zip(parts, refs):
        pl.when((j >= lo) & (j < hi))(functools.partial(run, ref))


def _groups(u_ref, w_ref, bias_ref, body):
    """``body(rows, w, bias)`` for every group of a block's channels, all
    the block's positions with them, as straight-line code (a loop over
    positions inside it cost a forward call five times its time, PR 37):
    ``rows`` the group's slice, ``w`` its taps and ``bias`` its bias, each
    [c, 1]."""
    import jax.experimental.pallas as pl

    some = math.gcd(u_ref.shape[1], _GROUP)

    def group(r, _):
        rows = pl.ds(pl.multiple_of(r * some, some), some)
        body(rows, [w_ref[rows, k:k + 1] for k in range(w_ref.shape[1])],
             bias_ref[rows, :])
        return _

    jax.lax.fori_loop(0, u_ref.shape[1] // some, group, 0)


def _taps_silu_fwd_kernel(before_ref, u_ref, w_ref, bias_ref, *out_refs,
                          parts):
    import jax.experimental.pallas as pl

    taps = w_ref.shape[1]
    first = pl.program_id(2) == 0

    def run(out_ref):
        def group(rows, w, bias):
            # zeros before position 0, not the end of the row before
            before = before_ref[0, rows, :]
            v = _weighed(w, _shifted(
                jnp.where(first, jnp.zeros_like(before), before),
                u_ref[0, rows, :], taps)) + bias
            out_ref[0, rows, :] = (v * _sigmoid(v)).astype(out_ref.dtype)

        _groups(u_ref, w_ref, bias_ref, group)

    _owned(parts, out_refs, run)


def _taps_silu_bwd_kernel(before_ref, u_ref, after_ref, *refs, parts, seq):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    count = len(parts)
    w_ref, bias_ref, du_ref, sums_ref, g_ref = refs[2 * count:]
    taps, length = w_ref.shape[1], u_ref.shape[2]
    f32 = jnp.float32
    t = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (t == 0))
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def live(a, at):
        # positions the sequence has: a last block may hold fewer, and the
        # tile after the last block none
        return jnp.where(at + jax.lax.broadcasted_iota(
            jnp.int32, a.shape, 1) < seq, a, jnp.zeros_like(a))

    def run(dy_ref, dy_after_ref):
        def group(rows, w, bias):
            def grad(prev, cur, dy):
                """(g = dy silu'(v) of ``cur``'s positions, ``cur`` moved
                as the taps take it)."""
                moved = _shifted(prev, cur, taps)
                v = _weighed(w, moved) + bias
                sig = _sigmoid(v)
                return dy.astype(f32) * (sig * (1.0 + v * (1.0 - sig))), moved

            before = before_ref[0, rows, :]
            cur, dy = u_ref[0, rows, :], dy_ref[0, rows, :]
            if seq % length:
                cur, dy = live(cur, t * length), live(dy, t * length)
            g, moved = grad(jnp.where(t == 0, jnp.zeros_like(before), before),
                            cur, dy)
            g_ref[rows, :length] = g
            # dw[:, j] takes g_t u_{t - (taps - 1) + j}, dbias g_t: a tile
            # of sums each, its lanes added up after the call
            for k, of in enumerate(moved[::-1] + [None]):
                all_ = g if of is None else g * of
                sums_ref[k, rows, :] += sum(
                    all_[:, at:at + _HALO] for at in range(0, length, _HALO))
            # g of the positions after the block, which du of its last takes
            g_ref[rows, length:], _ = grad(
                cur[:, length - _HALO:],
                live(after_ref[0, rows, :], (t + 1) * length),
                live(dy_after_ref[0, rows, :], (t + 1) * length))
            g = g_ref[rows, :]
            # du_t takes w[j] g_{t + (taps - 1) - j}
            du_ref[0, rows, :] = _weighed(w, [g[:, :length]] + [
                pltpu.roll(g, length + _HALO - fwd, 1)[:, :length]
                for fwd in range(1, taps)]).astype(du_ref.dtype)

        _groups(u_ref, w_ref, bias_ref, group)

    _owned(parts, list(zip(refs[:count], refs[count:2 * count])),
           lambda r: run(*r))


def _taps_specs(u, w, first, sizes):
    """u [b, wide, s] -> what both calls share: the grid (channel blocks
    outermost, so that a block of the sums is one run of steps), each
    part's channel blocks, the shapes of a block and of a neighbour's tile,
    the taps' and the bias' specs, and the index maps: ``whole(pos)`` of
    ``u`` and ``of_part(lo, hi, pos)`` of an array that holds one part's
    channels, ``pos`` (``own``, ``before``, ``after``) the block or tile
    along the positions that sequence block ``t`` takes."""
    import jax.experimental.pallas as pl

    b, _, s = u.shape
    c, taps = w.shape
    assert sum(sizes) == c and taps <= _HALO, (sizes, w.shape)
    plan = taps_plan(b, s, c, taps, u.dtype.itemsize, first, sizes)
    length, chans = plan["block_rows"], plan["block_channels"]
    per, tiles, nt = length // _HALO, -(-s // _HALO), -(-s // length)
    edges = [sum(sizes[:k]) // chans for k in range(len(sizes) + 1)]

    def whole(pos):
        return lambda j, i, t: (i, first // chans + j, pos(t))

    def of_part(lo, hi, pos):
        # its own block while the grid is in the part; before and after,
        # where it will start and where it ended: a block is copied when
        # its index moves, so nothing is, and an output block is not
        # written back before the kernel has filled it
        def index(j, i, t):
            inside, end = (j >= lo) & (j < hi), jnp.where(j < lo, 0, 1)
            return (jnp.where(inside, i, end * (b - 1)),
                    jnp.clip(j - lo, 0, hi - lo - 1),
                    jnp.where(inside, pos(t), end * pos(nt - 1)))
        return index

    return {"grid": (c // chans, b, nt),
            "parts": tuple(zip(edges[:-1], edges[1:])),
            "block": (1, chans, length), "tile": (1, chans, _HALO),
            "weights": [pl.BlockSpec((chans, taps), lambda j, i, t: (j, 0)),
                        pl.BlockSpec((chans, 1), lambda j, i, t: (j, 0))],
            "whole": whole, "of_part": of_part, "own": lambda t: t,
            "before": lambda t: jnp.maximum(t * per - 1, 0),
            "after": lambda t: jnp.minimum((t + 1) * per, tiles - 1)}


def _taps_silu_forward(u, w, bias, first, sizes, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, s = u.shape
    at = _taps_specs(u, w, first, sizes)
    return tuple(pl.pallas_call(
        functools.partial(_taps_silu_fwd_kernel, parts=at["parts"]),
        name="taps_silu_fwd",
        out_shape=[jax.ShapeDtypeStruct((b, n, s), u.dtype) for n in sizes],
        grid=at["grid"],
        in_specs=[pl.BlockSpec(at["tile"], at["whole"](at["before"])),
                  pl.BlockSpec(at["block"], at["whole"](at["own"]))]
        + at["weights"],
        out_specs=[pl.BlockSpec(at["block"],
                                at["of_part"](lo, hi, at["own"]))
                   for lo, hi in at["parts"]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(u, u, w.astype(jnp.float32), bias.astype(jnp.float32)[:, None]))


def _taps_silu_backward(u, w, bias, dys, first, sizes, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, wide, s = u.shape
    c, taps = w.shape
    at = _taps_specs(u, w, first, sizes)
    block, tile, parts = at["block"], at["tile"], at["parts"]
    f32 = jnp.float32
    du, sums = pl.pallas_call(
        functools.partial(_taps_silu_bwd_kernel, parts=parts, seq=s),
        name="taps_silu_bwd",
        out_shape=[jax.ShapeDtypeStruct((b, c, s), u.dtype),
                   jax.ShapeDtypeStruct((taps + 1, c, _HALO), f32)],
        grid=at["grid"],
        in_specs=[pl.BlockSpec(tile, at["whole"](at["before"])),
                  pl.BlockSpec(block, at["whole"](at["own"])),
                  pl.BlockSpec(tile, at["whole"](at["after"]))]
        + [pl.BlockSpec(block, at["of_part"](lo, hi, at["own"]))
           for lo, hi in parts]
        + [pl.BlockSpec(tile, at["of_part"](lo, hi, at["after"]))
           for lo, hi in parts]
        + at["weights"],
        out_specs=[pl.BlockSpec(block, lambda j, i, t: (i, j, t)),
                   pl.BlockSpec((taps + 1,) + tile[1:],
                                lambda j, i, t: (0, j, 0))],
        scratch_shapes=[pltpu.VMEM((block[1], block[2] + _HALO), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(u, u, u, *dys, *dys, w.astype(f32), bias.astype(f32)[:, None])
    if wide != c:           # no gradient to the channels beside the taps'
        du = jnp.pad(du, ((0, 0), (first, wide - first - c), (0, 0)))
    sums = sums.sum(-1)
    return du, sums[:taps].T.astype(w.dtype), sums[taps].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _taps_silu(u, w, bias, how):
    return _taps_silu_forward(u, w, bias, *how)


def _taps_silu_fwd(u, w, bias, how):
    # nothing float32 is kept: the backward builds the taps' sums again
    return _taps_silu_forward(u, w, bias, *how), (u, w, bias)


def _taps_silu_bwd(how, res, dys):
    return _taps_silu_backward(*res, dys, *how)


_taps_silu.defvjp(_taps_silu_fwd, _taps_silu_bwd)


def taps_silu(u: jax.Array, w: jax.Array, bias: jax.Array, *,
              first: int = 0, sizes: Optional[Sequence[int]] = None,
              interpret: bool = False) -> Tuple[jax.Array, ...]:
    """``silu(taps(x) + bias)`` of ``x = u[:, first : first + c]`` (u [b,
    wide, s], channels before positions as a torch ``Conv1d`` has them; w
    [c, taps], bias [c]; the taps are ``causal_taps``') as one pass over
    HBM, cut into arrays of ``sizes`` channels (one of all ``c`` by
    default), each [b, size, s] in ``u``'s dtype. ``u``, ``w`` and ``bias``
    are float32 inside, the sum is in ``causal_taps``' order, the silu
    float32, one rounding at the end. The backward is one pass too: ``g =
    dy silu'(v)`` with ``v`` built again from ``u``, ``du_t = sum_j w[:, j]
    g_{t + (taps - 1) - j}`` (zero beside the taps' channels), ``dw[:, j] =
    sum_t g_t u_{t - (taps - 1) + j}`` and ``dbias = sum_t g_t``
    accumulated in float32.

    Positions are on the lanes: that is how XLA lays a Mamba-2 mixer's
    activations out on a v5e (the in-projection writes, and the scan
    reads, position-minor arrays: PR 37, PERF.md 6), so a caller's
    ``swapaxes`` before and after are layouts and not copies, where a
    channel-minor call costs a transpose of every operand. The grid is
    (channel block, batch row, sequence block). The ``taps - 1`` positions
    before a block come from a second ``BlockSpec`` on ``u``, the tile of
    128 that ends where the block starts (zeros in a row's first block);
    the backward also reads the tile after the block, of ``u`` and of
    ``dy``. Nothing is sliced before the call nor joined after it:
    ``first`` is an offset of the index maps, and the parts are outputs
    (and cotangents) of their own. ``taps_plan`` says what the blocks are:
    a block's channels divide ``first`` and every size, and Mosaic takes
    them in whole tiles of 16."""
    return _taps_silu(u, w, bias, (first, tuple(sizes or (w.shape[0],)),
                                   interpret))
