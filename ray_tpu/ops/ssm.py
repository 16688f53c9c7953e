"""Mamba-2's mixer and the chunked (SSD) form of its selective scan.

The recurrence, a head at a time (``x_t [P]``, ``B_t, C_t [N]``, ``dt_t >
0`` and ``A < 0`` scalars, state ``S [P, N]`` float32, zero before the
sequence):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;    y_t = S_t C_t

``ssd_scan`` computes it in chunks of ``chunk`` positions and never token
by token. With ``cs`` the running sum of ``dt A`` inside a chunk: a
position's output is what its own chunk gives, ``sum_{k<=q} (C_q . B_k)
exp(cs_q - cs_k) dt_k x_k`` (one ``[chunk, chunk]`` decay-weighted ``C
B^T`` product against ``x``, on the MXU), plus what the state carried into
the chunk gives, ``exp(cs_q) C_q . S_in``; the state a chunk hands on is
``exp(cs_end) S_in + sum_k exp(cs_end - cs_k) dt_k x_k (x) B_k``. The
result does not depend on ``chunk``.

Two forms share this arithmetic and no code beyond the padding; which
runs is read from the call and never set (``_kernel_takes``; ``scan_plan``
says what a call will do, and a traced call writes it once as the kept
span ``rtpu.ssm.scan_plan``):

- ``xla_walk``, on the CPU, under a mesh (a Mosaic call is whole to the
  partitioner) and for a chunk that is not whole lane tiles. The decay
  matrices ``exp(cs_q - cs_k)`` are ``heads x chunk x chunk`` float32 a
  chunk, 2.1 GB for all the chunks of one 32,768-position sequence at 64
  heads, and the backward wants them again. So the chunks are walked: a
  ``lax.scan`` whose step takes several chunks at once (as many as put
  ``WALK_BYTES`` of decay matrices in HBM), carries the float32 state,
  and is under ``jax.checkpoint``, so that what the backward keeps of a
  step is the state it started from and the step's decay matrices are
  built again. The controls of ``benchmark/tests/scan_limits.py`` plant
  their faults in ``_walk_step`` and this module's ``jnp``.
- ``pallas``, on a TPU backend without a mesh (``scan_kernels``): two
  Mosaic calls behind a ``custom_vjp``, ``ssd_scan_fwd`` and
  ``ssd_scan_bwd``, on operands that lie positions last, as the taps'
  kernels leave them (``mamba2_mixer``'s swaps to ``ssd_scan``'s own
  order and back cancel in the compiled step). The grid is (batch row,
  ``KERNEL_CHUNKS`` chunks along the sequence, ``KERNEL_HEADS`` heads),
  the heads innermost. A chunk's decays, ``B C^T`` (once for the block's
  heads) and their product live in VMEM and nothing ``[chunk, chunk]`` is
  written to HBM; the float32 states of all heads are carried in VMEM from
  step to step. The forward writes the states before every grid step when
  a gradient is asked for (``states_kept``); the backward takes the steps
  last first, builds a step's states again from those in VMEM, walks its
  chunks last first carrying the states' cotangent, and adds the heads'
  ``dB`` and ``dC`` up in VMEM. Its seams for the controls are
  ``_kernel_state`` and ``_kernel_sums`` / ``_kernel_decays`` (through
  this module's ``jnp``), looked up while the kernels trace.

Decays, running sums and the state are float32; the MXU's operands are the
activations' dtype with float32 accumulation (``dt`` is folded into ``x``
once a position, not into every pair). A sequence that is not whole
chunks is padded with ``dt = 0``, which moves neither state nor output.

Named scopes (metadata only): ``ssm`` holds ``ssm_in`` (the in-projection;
the norm before it is the caller's), ``ssm_conv`` (the causal depthwise
taps, their bias and the silu: ``causal_conv_silu``, on a TPU the kernel
pair ``ops/conv.taps_silu``), ``ssm_scan`` (softplus, the scan, the skip
``D x``), ``ssm_norm`` (the gate and the RMSNorm over a group's channels:
all of them where the norm has one group) and ``ssm_out`` (the
out-projection).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.conv import causal_taps, taps_plan, taps_silu
from ray_tpu.ops.layers import Leaf, Part, checkpoint_name, kept, rms_norm
from ray_tpu.util import tracing

# decay matrices one step of XLA's walk may put in HBM (float32, before the
# product that consumes them): 8 chunks of 256 at 64 heads
WALK_BYTES = 128 << 20
# the kernels: heads a grid step takes (what a chunk's chain of products
# waits for overlaps across them, and the products against a group's B and C
# take all of them at once) and chunks a grid step takes (the backward keeps
# the state before each step and builds the ones between again). Read on
# the chip at the cell's shapes, forward / gradient ms a layer, the calls
# alone: 16, 16 1.87 / 7.05; 8, 32 1.94 / 6.89; 8, 16 2.11 / 7.86; 16, 8
# 2.23 / 9.03; 8, 8 2.49 / 9.82; 4, 16 2.50 / 8.69; 2, 32 3.50 / 11.37;
# XLA's walk 5.00 / 13.86 (PERF.md 6, PR 41)
KERNEL_HEADS = 16
KERNEL_CHUNKS = 16
# positions a register holds along its lanes: the kernels take a chunk that
# is whole tiles of them (tests patch it for a tiny chunk in the interpreter)
KERNEL_LANES = 128


def _kernel_takes(chunk: int, mesh) -> bool:
    """Whether a call runs as the kernels: on a TPU backend (anything but
    the CPU), without a ``mesh`` (a Mosaic call is whole to the
    partitioner, which would gather its operands: XLA's walk shards as the
    arrays do), and with a chunk (a sequence shorter than one is its own)
    that is whole tiles of ``KERNEL_LANES`` positions."""
    return (mesh is None and jax.default_backend() != "cpu"
            and chunk % KERNEL_LANES == 0)


def scan_plan(batch: int, seq: int, heads: int, head_dim: int, state: int,
              groups: int, chunk: int, mesh=None) -> Dict[str, Any]:
    """What ``ssd_scan`` does with these shapes, and in which ``form``.
    Both forms: the chunk it uses (no longer than the sequence), the
    chunks, the ``steps`` (of the walk, or of the kernels' grid along the
    sequence), ``chunks_a_call`` (what one step takes), ``states_kept``
    (the float32 states a backward starts from, one a step), the float32
    bytes the form puts in HBM and, of those, the decay matrices'
    (``decay_bytes_in_hbm``) beside what all chunks' at once would be.
    ``xla_walk``: ``walk`` (= ``chunks_a_call``, the largest divisor of the
    chunks within ``WALK_BYTES``) and one step's decay matrices.
    ``pallas``: ``heads_a_block`` (the largest divisor of a group's heads
    within ``KERNEL_HEADS``), ``KERNEL_CHUNKS`` chunks a step (all of a
    shorter sequence), and the bytes of what a gradient's two calls hold
    in float32: the kept states and the last, five rows a head (``dt``,
    the running sums, the gradients of both and of the skip) and the
    groups' ``dB`` and ``dC``: nothing ``[chunk, chunk]``."""
    chunk = min(chunk, seq)
    chunks = -(-seq // chunk)
    one = batch * heads * chunk * chunk * 4
    plan = {"seq": seq, "chunk": chunk, "chunks": chunks, "heads": heads,
            "head_dim": head_dim, "state": state, "groups": groups,
            "decay_bytes_all_chunks": chunks * one}
    if _kernel_takes(chunk, mesh):
        call = min(KERNEL_CHUNKS, chunks)
        steps = -(-chunks // call)
        a_state = batch * heads * head_dim * state * 4
        return dict(plan, form="pallas", walk=None, steps=steps,
                    heads_a_block=max(
                        h for h in range(1, KERNEL_HEADS + 1)
                        if (heads // groups) % h == 0),
                    chunks_a_call=call, states_kept=steps,
                    decay_bytes_in_hbm=0,
                    float32_bytes_in_hbm=(steps + 1) * a_state
                    + batch * (5 * heads + 2 * groups * state)
                    * steps * call * chunk * 4)
    walk = max(w for w in range(1, chunks + 1)
               if chunks % w == 0 and (w == 1 or w * one <= WALK_BYTES))
    return dict(plan, form="xla_walk", walk=walk, steps=chunks // walk,
                heads_a_block=None, chunks_a_call=walk,
                states_kept=chunks // walk, decay_bytes_in_hbm=walk * one,
                float32_bytes_in_hbm=walk * one)


def _walk_step(S, xs, A, dtype):
    """``walk`` chunks: S [b, G, R, P, N] float32 (heads as groups x heads
    a group), xs = (x [b, W, Q, G, R, P], dt [b, W, Q, G, R] float32, B and
    C [b, W, Q, G, N]) -> (the state after them, y [b, W, Q, G, R, P])."""
    x, dt, B, C = xs
    W, Q = x.shape[1], x.shape[2]
    f32 = jnp.float32
    cs = jnp.cumsum(dt * A, axis=2)                      # [b, W, Q, G, R]
    # inside a chunk: (C_q . B_k) exp(cs_q - cs_k) dt_k, keys k <= q
    cb = jnp.einsum("bwqgn,bwkgn->bwgqk", C, B, preferred_element_type=f32)
    by_head = jnp.moveaxis(cs, 2, -1)                    # [b, W, G, R, Q]
    keep = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(                           # [b, W, G, R, Q, Q]
        keep, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    m = (cb[:, :, :, None] * decay
         * jnp.moveaxis(dt, 2, -1)[..., None, :]).astype(dtype)
    y = jnp.einsum("bwgrqk,bwkgrp->bwqgrp", m, x, preferred_element_type=f32)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt            # [b, W, Q, G, R]
    add = jnp.einsum("bwkgrp,bwkgn->bwgrpn",
                     (x.astype(f32) * to_end[..., None]).astype(dtype), B,
                     preferred_element_type=f32)         # [b, W, G, R, P, N]
    whole = jnp.exp(cs[:, :, -1])                        # [b, W, G, R]
    carried = []                                         # the state into each
    for w in range(W):
        carried.append(S)
        S = whole[:, w, ..., None, None] * S + add[:, w]
    into = jnp.stack(carried, axis=1)                    # [b, W, G, R, P, N]
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bwqgn,bwgrpn->bwqgrp", C, into.astype(dtype),
        preferred_element_type=f32)
    return S, y.astype(dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int = 256, mesh=None
             ) -> Tuple[jax.Array, jax.Array]:
    """x [b, s, H, P], dt [b, s, H] float32 (after its softplus), A [H]
    float32 (negative), B and C [b, s, G, N] (G groups of H / G heads share
    a B and a C) -> (y [b, s, H, P] in ``x``'s dtype, the state after the
    last position [b, H, P, N] float32). ``mesh``: the one the caller's
    arrays are sharded over, if any. Which form runs is read from the call
    (``_kernel_takes``), and the kept span ``rtpu.ssm.scan_plan`` says
    which."""
    b, s, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    plan = scan_plan(b, s, H, P, N, G, chunk, mesh)
    with tracing.span("rtpu.ssm.scan_plan", keep=True, **plan):
        pass
    if plan["form"] == "pallas":
        def last(a):
            return jnp.swapaxes(a.reshape(b, s, -1), 1, 2)

        # looked up at trace time: a test hands it the interpreter
        y, S = scan_kernels(last(x), last(dt), A, last(B), last(C), plan)
        return jnp.swapaxes(y, 1, 2).reshape(x.shape), S
    R = H // G
    Q, W, steps = plan["chunk"], plan["walk"], plan["steps"]
    pad = plan["chunks"] * Q - s
    dtype = x.dtype

    def stepped(a, *tail):
        # [b, s, ...] -> [steps, b, W, Q, ...]
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((b, steps, W, Q) + tail), 1, 0)

    xs = (stepped(x, G, R, P), stepped(dt.astype(jnp.float32), G, R),
          stepped(B, G, N), stepped(C, G, N))
    A = A.astype(jnp.float32).reshape(G, R)

    # ``_walk_step`` is looked up at trace time: scan_limits.py plants its
    # faults there (a state that is not carried, decays in bfloat16)
    step = jax.checkpoint(lambda S, xs_: _walk_step(S, xs_, A, dtype))
    S, y = jax.lax.scan(step, jnp.zeros((b, G, R, P, N), jnp.float32), xs)
    y = jnp.moveaxis(y, 0, 1).reshape(b, steps * W * Q, H, P)
    return y[:, :s], S.reshape(b, H, P, N)


# ---- the scan as Pallas (Mosaic) kernels. Every operand lies as the taps'
# kernels leave it, positions on the lanes: a grid step takes ``hb`` heads
# and ``n`` chunks of one batch row, x and y [1, hb P, n Q] (a head's P
# channels are P sublanes), the group's B and C [1, N, n Q], ``dt`` and the
# running sums along the lanes ([1, 1, hb, n Q]); a chunk's sums are turned
# down the sublanes (a pair's other end) for all of a block's heads at
# once, one [hb, Q] transpose, and nothing [Q, Q] ever is. A chunk's pair
# matrix is built keys down, queries along (``[k, q]``, zero under the
# diagonal), and every product is the MXU's own form: ``x [P, k] @ M [k,
# q]``, ``S [P, N] @ C^T [N, q]``, ``x [P, k] B^T[N, k]^T``. The grid is
# (batch row, step along the sequence, block of heads), the heads
# innermost: all heads' float32 states [H P, N] lie in the block of the
# last-state output, which stays in VMEM while the grid walks a row's
# sequence, and the backward's dB and dC, which every head of a group adds
# to, in their output blocks while it walks a step's heads.


def _nt(a, b):
    """a [m, d], b [n, d] -> a b^T [m, n], float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a [d, m], b [d, n] -> a^T b [m, n], float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _at(shape):
    """(row, column) of every entry of a 2-D ``shape``."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _kernel_sums(da):
    """da [..., Q] float32 (``dt A``, a chunk along the last axis) -> its
    running sums along a chunk (the kernels' ``cs``; looked up at trace
    time, as ``_kernel_decays`` is). A product with a triangle of ones at
    the highest precision (float32's 24 bits in three bfloat16 parts, the
    ones exact: a float32 sum in another order): on a TPU ``cumsum`` along
    256 lanes is a ``reduce-window`` of 1.5 ms a call, three calls a layer
    and step (PERF.md 6, PR 41)."""
    at = jnp.arange(da.shape[-1])
    return jnp.matmul(da, (at[:, None] <= at[None, :]).astype(da.dtype),
                      precision=jax.lax.Precision.HIGHEST)


def _kernel_decays(cs_col, cs_row):
    """Every decay a chunk's kernels use, formed here and nowhere else:
    cs_col [Q, 1] and cs_row [1, Q], the chunk's running sums down the
    sublanes and along the lanes -> ``pair`` [Q, Q] (``exp(cs_q - cs_k)`` at
    [k, q] for k <= q, zero under the diagonal), ``grown`` (``exp(cs)``)
    and ``to_end`` (``exp(cs_end - cs)``), both [1, Q], and ``whole``
    (``exp(cs_end)`` [1, 1]), float32."""
    Q = cs_row.shape[1]
    row, col = _at((Q, Q))
    # (the last sum by a masked sum: Mosaic does not spread a [1, 1] that
    # it sliced from the last lane over lanes and sublanes at once)
    end = jnp.where(_at((1, Q))[1] == Q - 1, cs_row, 0.0
                    ).sum(1, keepdims=True)
    return {"pair": jnp.exp(jnp.where(row <= col, cs_row - cs_col, -jnp.inf)),
            "grown": jnp.exp(cs_row), "to_end": jnp.exp(end - cs_row),
            "whole": jnp.exp(end)}


def _kernel_state(S):
    """The state a chunk starts from, as the kernels carried it to there
    (S [hb P, N] float32, a block's heads; looked up at trace time)."""
    return S


def _lanes_of(j, Q):
    """Chunk ``j``'s positions of a grid step's block (``j`` a loop's
    index)."""
    import jax.experimental.pallas as pl

    return pl.ds(pl.multiple_of(j * Q, Q), Q)


def _chunk_local(j, at, x_ref, dt_ref, rows_ref, skip_ref):
    """What the kernels build of chunk ``j`` of each of a grid step's heads
    from its own positions alone: the decays (``_kernel_decays``), ``dt``
    [1, Q], ``x`` in float32 (``xf`` [P, Q]) and the MXU's operands ``xs``
    (``dt x``: what the pair matrix multiplies) and ``xe`` (``dt x`` decayed
    to the chunk's end: what the state takes up) in the activations'
    dtype, and the head's ``skip`` (``D``) [1, Q]."""
    heads = rows_ref.shape[2]
    P = x_ref.shape[1] // heads
    along = rows_ref[0, 0, :, at]                       # [hb, Q]
    down = along.T                                      # [Q, hb]
    local = []
    for h in range(heads):
        dt = dt_ref[0, 0, h:h + 1, at]
        d = _kernel_decays(down[:, h:h + 1], along[h:h + 1])
        xf = x_ref[0, h * P:(h + 1) * P, at].astype(jnp.float32)
        local.append(dict(
            d, dt=dt, xf=xf, skip=skip_ref[0, h:h + 1, :],
            xs=(xf * dt).astype(x_ref.dtype),
            xe=(xf * (d["to_end"] * dt)).astype(x_ref.dtype)))
    return local


def _handed_on(S, local, add):
    """The block's states after a chunk: S [hb P, N] before it, ``add``
    what the chunk's positions add (``xe B``), each head's rows decayed by
    its own ``whole``."""
    P = S.shape[0] // len(local)
    return jnp.concatenate([
        c["whole"] * S[h * P:(h + 1) * P] + add[h * P:(h + 1) * P]
        for h, c in enumerate(local)], axis=0)


def _scan_fwd_kernel(x_ref, dt_ref, rows_ref, skip_ref, B_ref, C_ref, y_ref,
                     last_ref, *kept_ref, chunks):
    """A grid step of the forward: its chunks one after another (a loop,
    its body traced once: what overlaps is a chunk's heads), the block's
    states in their rows of ``last_ref`` from step to step."""
    import jax.experimental.pallas as pl

    heads, Q = rows_ref.shape[2], rows_ref.shape[3] // chunks
    rows, dtype = x_ref.shape[1], x_ref.dtype
    P = rows // heads
    mine = pl.ds(pl.multiple_of(pl.program_id(2) * rows, rows), rows)

    @pl.when(pl.program_id(1) == 0)
    def _():
        last_ref[0, mine, :] = jnp.zeros((rows, last_ref.shape[2]),
                                         jnp.float32)

    for ref in kept_ref:                    # the states this step starts from
        ref[0, 0] = last_ref[0, mine, :]

    def chunk(j, carry):
        at = _lanes_of(j, Q)
        Bt, Ct = B_ref[0, :, at], C_ref[0, :, at]
        bc = _tn(Bt, Ct)                                # B C^T [k, q]
        S = _kernel_state(last_ref[0, mine, :])
        on_state = _nn(S.astype(dtype), Ct)             # [hb P, q]
        local = _chunk_local(j, at, x_ref, dt_ref, rows_ref, skip_ref)
        for h, c in enumerate(local):
            own = slice(h * P, (h + 1) * P)
            y = _nn(c["xs"], (bc * c["pair"]).astype(dtype))
            y_ref[0, own, at] = (y + c["grown"] * on_state[own]
                                 + c["skip"] * c["xf"]).astype(y_ref.dtype)
        last_ref[0, mine, :] = _handed_on(S, local, _nt(
            jnp.concatenate([c["xe"] for c in local], axis=0), Bt))
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def _scan_bwd_kernel(x_ref, dt_ref, rows_ref, skip_ref, B_ref, C_ref,
                     kept_ref, dy_ref, dlast_ref, dx_ref, ddt_ref, drows_ref,
                     dskip_ref, dB_ref, dC_ref, state_ref, dstate_ref, *,
                     chunks, blocks_a_group):
    """A grid step of the backward, the steps taken last first: the states
    its chunks started from built again from the state the step started
    from (``kept_ref``) into scratch, then the chunks last first,
    ``dstate_ref`` (all heads') carrying the states' cotangent from step
    to step. ``dB_ref`` and ``dC_ref`` stay where they are while the grid
    takes the ``blocks_a_group`` blocks of heads that share a B and a C."""
    import jax.experimental.pallas as pl

    n, heads = chunks, rows_ref.shape[2]
    Q = rows_ref.shape[3] // n
    rows, dtype = x_ref.shape[1], x_ref.dtype
    P = rows // heads
    f32 = jnp.float32
    refs = (x_ref, dt_ref, rows_ref, skip_ref)
    mine = pl.ds(pl.multiple_of(pl.program_id(2) * rows, rows), rows)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[mine, :] = dlast_ref[0, mine, :]

    @pl.when(pl.program_id(2) % blocks_a_group == 0)
    def _():
        dB_ref[...] = jnp.zeros_like(dB_ref)
        dC_ref[...] = jnp.zeros_like(dC_ref)

    state_ref[0] = _kernel_state(kept_ref[0, 0])

    def again(j, carry):
        at = _lanes_of(j, Q)
        local = _chunk_local(j, at, *refs)
        state_ref[j + 1] = _kernel_state(_handed_on(
            state_ref[j], local, _nt(
                jnp.concatenate([c["xe"] for c in local], axis=0),
                B_ref[0, :, at])))
        return carry

    jax.lax.fori_loop(0, n - 1, again, 0)
    _, head = _at((Q, heads))
    _, position = _at((1, Q))

    def back(i, carry):
        j = n - 1 - i
        at = _lanes_of(j, Q)
        Bt, Ct = B_ref[0, :, at], C_ref[0, :, at]
        bc = _tn(Bt, Ct)
        S, dS = state_ref[j], dstate_ref[mine, :]
        Sb, dSb = S.astype(dtype), dS.astype(dtype)
        # y = xs M + grown (S C^T);  S' = whole S + xe B^T
        on_state = _nn(Sb, Ct)                          # [hb P, q]
        dxe_all = _nn(dSb, Bt)                          # [hb P, k]
        local = _chunk_local(j, at, *refs)
        dbc, dyg, decayed, down, along = 0.0, [], [], 0.0, []
        for h, c in enumerate(local):
            own = slice(h * P, (h + 1) * P)
            dy = dy_ref[0, own, at]
            dyf, xf = dy.astype(f32), c["xf"]
            m = bc * c["pair"]
            dxs = _nt(dy, m.astype(dtype))              # [P, k]
            dm = _tn(c["xs"], dy)                       # [k, q]
            dbc = dbc + dm * c["pair"]
            moved = dm * m                              # d pair * pair
            dyg.append((dyf * c["grown"]).astype(dtype))
            # xe = x (to_end dt): to dt, and through to_end to the sums
            dxe, weight = dxe_all[own], c["to_end"] * c["dt"]
            to_weight = (dxe * xf).sum(0, keepdims=True)
            dend = ((to_weight * weight).sum(1, keepdims=True)
                    + c["whole"] * jnp.sum(S[own] * dS[own], keepdims=True))
            dx_ref[0, own, at] = (dxs * c["dt"] + dxe * weight
                                  + c["skip"] * dyf).astype(dx_ref.dtype)
            dskip_ref[0, 0, h:h + 1, at] = (dyf * xf).sum(0, keepdims=True)
            ddt_ref[0, 0, h:h + 1, at] = ((dxs * xf).sum(0, keepdims=True)
                                          + to_weight * c["to_end"])
            # the sums: a pair's query along the lanes, its key down the
            # sublanes (every head's turned along the lanes at once, below)
            along.append(
                moved.sum(0, keepdims=True) - to_weight * weight
                + (dyf * on_state[own]).sum(0, keepdims=True) * c["grown"]
                + jnp.where(position == Q - 1, dend, 0.0))
            down = jnp.where(head == h, moved.sum(1, keepdims=True), down)
            decayed.append(c["whole"] * dS[own])
        drows_ref[0, 0, :, at] = jnp.concatenate(along, axis=0) - down.T
        dyg = jnp.concatenate(dyg, axis=0)
        dbc = dbc.astype(dtype)
        dstate_ref[mine, :] = (jnp.concatenate(decayed, axis=0)
                               + _nt(dyg, Ct))
        dC_ref[0, :, at] += _tn(Sb, dyg) + _nn(Bt, dbc)
        dB_ref[0, :, at] += _tn(dSb, jnp.concatenate(
            [c["xe"] for c in local], axis=0)) + _nt(Ct, dbc)
        return carry

    jax.lax.fori_loop(0, n, back, 0)


def _scan_specs(x, rows, B, groups, chunks):
    """What both calls share: the grid (batch row, step along the
    sequence, block of heads) and the operands' block shapes and places
    (``place(step)``: a step's place along the sequence, which the backward
    counts from the end)."""
    import jax.experimental.pallas as pl

    b, d, _ = x.shape
    blocks, hb, S = rows.shape[1:]
    steps = S // chunks[0] // chunks[1]
    N = B.shape[1] // groups
    L, r = chunks[0] * chunks[1], d // blocks
    a_group = blocks // groups

    def specs(place):
        return {
            "x": pl.BlockSpec((1, r, L), lambda i, t, h: (i, h, place(t))),
            "rows": pl.BlockSpec((1, 1, hb, L),
                                 lambda i, t, h: (i, h, 0, place(t))),
            "skip": pl.BlockSpec((1, hb, chunks[1]),
                                 lambda i, t, h: (h, 0, 0)),
            "BC": pl.BlockSpec((1, N, L),
                               lambda i, t, h: (i, h // a_group, place(t))),
            "states": pl.BlockSpec((1, d, N), lambda i, t, h: (i, 0, 0)),
            "kept": pl.BlockSpec((1, 1, r, N),
                                 lambda i, t, h: (i, place(t), h, 0))}

    return {"grid": (b, steps, blocks), "chunks": chunks[0], "steps": steps,
            "blocks_a_group": a_group, "rows_a_block": r, "state": N,
            "specs": specs}


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=100 << 20)


def _scan_forward(x, dt, rows, skip, B, C, groups, chunks, keep, interpret):
    """x [b, H P, S], dt and rows [b, H / hb, hb, S] float32, skip [H / hb,
    hb, Q] float32 (a head's ``D`` along the lanes), B and C [b, G N, S],
    ``chunks``: (the chunks a grid step takes, a chunk's positions)
    -> (y [b, H P, S], the last states [b, H P, N] float32, and with
    ``keep`` the states before every step [b, steps, H P, N])."""
    import jax.experimental.pallas as pl

    b, d, _ = x.shape
    at = _scan_specs(x, rows, B, groups, chunks)
    to = at["specs"](lambda t: t)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_scan_fwd_kernel, chunks=at["chunks"]),
        name="ssd_scan_fwd",
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, d, at["state"]), f32)]
        + [jax.ShapeDtypeStruct((b, at["steps"], d, at["state"]), f32)] * keep,
        grid=at["grid"],
        in_specs=[to["x"], to["rows"], to["rows"], to["skip"], to["BC"],
                  to["BC"]],
        out_specs=[to["x"], to["states"]] + [to["kept"]] * keep,
        compiler_params=_compiler_params(), interpret=interpret,
    )(x, dt, rows, skip, B, C)


def _scan_backward(x, dt, rows, skip, B, C, kept, dy, dlast, groups, chunks,
                   interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = x.shape[1]
    at = _scan_specs(x, rows, B, groups, chunks)
    steps = at["steps"]
    to = at["specs"](lambda t: steps - 1 - t)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunks=at["chunks"],
                          blocks_a_group=at["blocks_a_group"]),
        name="ssd_scan_bwd",
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct(B.shape, f32),
                   jax.ShapeDtypeStruct(B.shape, f32)],
        grid=at["grid"],
        in_specs=[to["x"], to["rows"], to["rows"], to["skip"], to["BC"],
                  to["BC"], to["kept"], to["x"], to["states"]],
        out_specs=[to["x"], to["rows"], to["rows"], to["rows"], to["BC"],
                   to["BC"]],
        scratch_shapes=[
            pltpu.VMEM((at["chunks"], at["rows_a_block"], at["state"]), f32),
            pltpu.VMEM((d, at["state"]), f32)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(x, dt, rows, skip, B, C, kept, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_calls(x, dt, rows, skip, B, C, groups, chunks, interpret):
    return tuple(_scan_forward(x, dt, rows, skip, B, C, groups, chunks, 0,
                               interpret))


def _scan_calls_fwd(x, dt, rows, skip, B, C, groups, chunks, interpret):
    y, last, kept = _scan_forward(x, dt, rows, skip, B, C, groups, chunks, 1,
                                  interpret)
    return (y, last), (x, dt, rows, skip, B, C, kept)


def _scan_calls_bwd(groups, chunks, interpret, res, cts):
    dx, ddt, drows, dskip, dB, dC = _scan_backward(*res, *cts, groups, chunks,
                                                   interpret)
    # a head's dD, position by position: summed over the batch and the
    # chunks, a chunk's positions where ``skip``'s lie
    blocks, hb = dskip.shape[1:3]
    dskip = dskip.reshape(-1, blocks, hb, dskip.shape[3] // chunks[1],
                          chunks[1]).sum((0, 3))
    return (dx, ddt, drows, dskip, dB.astype(res[4].dtype),
            dC.astype(res[5].dtype))


_scan_calls.defvjp(_scan_calls_fwd, _scan_calls_bwd)


def scan_kernels(x, dt, A, B, C, plan, skip=None, interpret: bool = False):
    """``ssd_scan`` as two Mosaic calls, ``ssd_scan_fwd`` and
    ``ssd_scan_bwd`` behind a ``custom_vjp`` (``plan``: ``scan_plan``'s, of
    the form ``pallas``), on operands that lie as the taps' kernels leave
    them, positions last: x [b, H P, s], dt [b, H, s], B and C [b, G N, s]
    -> (y [b, H P, s] in ``x``'s dtype, the last states [b, H, P, N]
    float32). ``skip``: a mixer's ``D`` [H], whose ``D x`` the kernels add
    to ``y`` while ``x`` is in VMEM (none: zeros). Around the calls, in
    XLA: the padding to whole grid steps (``dt = 0``) and the running sums
    of ``dt A`` along each chunk. The forward carries the states in VMEM
    and, when a gradient is asked for, writes the states before every grid
    step (``states_kept``); the backward takes the steps last first, builds
    a step's states again from those and carries the states' cotangent."""
    b, _, s = x.shape
    H, P, G = plan["heads"], plan["head_dim"], plan["groups"]
    Q, n, steps = plan["chunk"], plan["chunks_a_call"], plan["steps"]
    hb = plan["heads_a_block"]
    S = steps * n * Q
    f32 = jnp.float32

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, S - s))) if S > s else a

    dt = padded(dt.astype(f32))
    sums = _kernel_sums((dt * A.astype(f32)[:, None]
                         ).reshape(b, H, steps, n, Q))
    skip = jnp.zeros((H,), f32) if skip is None else skip.astype(f32)
    y, last = _scan_calls(
        padded(x), dt.reshape(b, H // hb, hb, S),
        sums.reshape(b, H // hb, hb, S),
        jnp.broadcast_to(skip.reshape(H // hb, hb, 1), (H // hb, hb, Q)),
        padded(B), padded(C), G, (n, Q), interpret)
    return y[..., :s], last.reshape(b, H, P, -1)


def causal_conv_silu(u: jax.Array, w: jax.Array, bias: jax.Array,
                     first: int = 0, sizes: Optional[Sequence[int]] = None,
                     mesh=None, span: str = "rtpu.ssm.conv_plan"
                     ) -> Tuple[jax.Array, ...]:
    """u [b, wide, s] (channels before positions, as a torch ``Conv1d``
    takes them), w [c, taps], bias [c] -> silu(conv(x) + bias) of ``x =
    u[:, first : first + c]`` in ``u``'s dtype, float32 inside, cut into
    arrays of ``sizes`` channels (one of all ``c`` by default), each [b,
    size, s]: ``ops/conv.causal_taps`` (zeros before position 0; ``w[:,
    -1]`` weighs the position's own value, as a ``Conv1d`` with left
    padding does), the bias, the silu.

    On a TPU backend it is ``ops/conv.taps_silu``, one pass over HBM
    forward and one backward that read ``u`` where it lies and write the
    parts; elsewhere, for channels that are not whole tiles of 16, and
    under a ``mesh`` (a Mosaic call is whole to the partitioner, which
    would gather its operands: XLA's form shards as the arrays do) it is
    XLA's form of the same sums. A traced call writes which as the kept
    span ``rtpu.ssm.conv_plan`` (``span``: a delta-rule layer's taps,
    ``ops/delta.py``, write ``rtpu.gdn.conv_plan``): ``ops/conv.taps_plan``'s
    blocks and the bytes their copies move beside ``form`` (``pallas``), or
    ``form`` ``xla_taps`` and no blocks."""
    c, taps = w.shape
    sizes = tuple(sizes or (c,))
    plan = taps_plan(u.shape[0], u.shape[2], c, taps, u.dtype.itemsize,
                     first, sizes)
    kernel = (mesh is None and jax.default_backend() != "cpu"
              and plan["block_channels"] % 16 == 0)
    if not kernel:
        plan = {k: v if k in ("seq", "channels", "taps") else None
                for k, v in plan.items()}
    with tracing.span(span, keep=True,
                      form="pallas" if kernel else "xla_taps", **plan):
        pass
    if kernel:
        return taps_silu(u, w, bias, first=first, sizes=sizes)
    x = jnp.swapaxes(u[:, first:first + c], 1, 2).astype(jnp.float32)
    y = jax.nn.silu(causal_taps(x, w) + bias.astype(jnp.float32))
    return tuple(jnp.split(jnp.swapaxes(y.astype(u.dtype), 1, 2),
                           np.cumsum(sizes)[:-1], axis=1))


def mamba2_mixer(h: jax.Array, p: Dict[str, jax.Array], *, heads: int,
                 head_dim: int, state: int, groups: int = 1,
                 chunk: int = 256, eps: float = 1e-5, mesh=None,
                 norm_groups: int = 1) -> Tuple[jax.Array, jax.Array]:
    """h [b, s, hidden] (normed) -> (the mixer's output [b, s, hidden],
    the state after the last position [b, H, P, N] float32, which no
    gradient passes). ``mesh``: the one the caller's arrays are sharded
    over, if any (``causal_conv_silu`` keeps XLA's form under one).
    ``p``: ``m_in [hidden, 2 d + 2 G N + H]`` (z, then x B C, then dt; ``d =
    H P``), ``m_conv [d + 2 G N, taps]`` and ``m_conv_bias``, ``dt_bias``,
    ``A_log`` and ``D`` ``[H]``, ``m_norm [d]``, ``m_out [d, hidden]``.
    No projection has a bias; ``dt`` is not clamped (``time_step_limit``
    (0, inf)). The gated norm is over a group's ``d / norm_groups``
    channels, each group normed on its own after the gate (``nemotron_h``:
    as many groups as B and C have); one group (Granite) is all ``d``.
    The in-projection's output carries the checkpoint name ``ssm_in``,
    the one value of the mixer a layer's remat level can keep."""
    b, s, _ = h.shape
    dt_ = h.dtype
    d, gn = heads * head_dim, groups * state
    f32 = jnp.float32
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm_in"):
            # named for the ladder's first rung (models/llama.py
            # REMAT_LADDER; inert elsewhere): kept, the backward runs the
            # product three times a layer (forward, dX, dW) and not four
            zxbcdt = checkpoint_name(
                jnp.dot(h, p["m_in"].astype(dt_),
                        preferred_element_type=f32).astype(dt_), "ssm_in")
            z, dt = zxbcdt[..., :d], zxbcdt[..., 2 * d + 2 * gn:]
            # positions last, as the in-projection's output lies on a TPU
            by_channel = jnp.swapaxes(zxbcdt, 1, 2)
        with jax.named_scope("ssm_conv"):
            # x B C read where the in-projection left them
            x, B, C = causal_conv_silu(by_channel, p["m_conv"],
                                       p["m_conv_bias"], first=d,
                                       sizes=(d, gn, gn), mesh=mesh)
        with jax.named_scope("ssm_scan"):
            plan = scan_plan(b, s, heads, head_dim, state, groups, chunk, mesh)
            A = -jnp.exp(p["A_log"].astype(f32))
            if plan["form"] == "pallas":
                # x B C and dt taken where they lie, positions last, and
                # the skip inside the kernels: no pass of XLA's over [b, s,
                # d] stands between the taps and the norm. ``scan_kernels``
                # is looked up at trace time: a test hands it the
                # interpreter
                with tracing.span("rtpu.ssm.scan_plan", keep=True, **plan):
                    pass
                y, S = scan_kernels(
                    x, jax.nn.softplus(
                        by_channel[:, 2 * d + 2 * gn:].astype(f32)
                        + p["dt_bias"].astype(f32)[:, None]),
                    A, B, C, plan, skip=p["D"])
                y = jnp.swapaxes(y, 1, 2).astype(f32)
            else:
                x, B, C = (jnp.swapaxes(a, 1, 2) for a in (x, B, C))
                x = x.reshape(b, s, heads, head_dim)
                y, S = ssd_scan(
                    x, jax.nn.softplus(dt.astype(f32)
                                       + p["dt_bias"].astype(f32)), A,
                    B.reshape(b, s, groups, state),
                    C.reshape(b, s, groups, state), chunk=chunk, mesh=mesh)
                y = (y.astype(f32) + p["D"].astype(f32)[:, None]
                     * x.astype(f32)).reshape(b, s, d)
            S = jax.lax.stop_gradient(S)
        with jax.named_scope("ssm_norm"):
            y = y * jax.nn.silu(z.astype(f32))
            if norm_groups > 1:
                y = y.reshape(b, s, norm_groups, d // norm_groups)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                  + eps)
            y = (y.reshape(b, s, d) * p["m_norm"].astype(f32)).astype(dt_)
        with jax.named_scope("ssm_out"):
            out = jnp.dot(y, p["m_out"].astype(dt_),
                          preferred_element_type=f32).astype(dt_)
    return out, S


def mamba2_part(resid: Optional[str] = None,
                counter: str = "ssm_state_abs_max",
                norm_groups: Optional[str] = None) -> Part:
    """Mamba-2's mixer as a layer's mixer: ``x + r * mamba2_mixer(
    RMSNorm(x))`` at the config's ``ssm_heads``, ``ssm_head_dim``,
    ``ssm_state``, ``ssm_groups``, ``ssm_conv_taps`` and ``ssm_chunk``
    (``resid`` names the field ``r`` where it is not 1; ``norm_groups``
    the field that says in how many groups of channels the gated norm
    runs, all channels as one without). A layer reports
    its state after the last position under "ssm_state", and the loss's
    terms the largest ``|S|`` of any layer under ``counter``. The
    initialisation is Mamba-2's published one: ``A`` uniform in 1-16 as its
    log, ``dt`` through the inverse softplus, ``D`` and the norms 1, the
    taps' bias 0."""
    def leaves(cfg):
        h, H, taps = cfg.hidden_size, cfg.ssm_heads, cfg.ssm_conv_taps
        d = H * cfg.ssm_head_dim
        conv = d + 2 * cfg.ssm_groups * cfg.ssm_state
        return {"op_norm": Leaf((h,), "ones", ("embed",)),
                "m_in": Leaf((h, d + conv + H), h, ("embed", "mlp")),
                "m_conv": Leaf((conv, taps), taps, ("mlp", None)),
                "m_conv_bias": Leaf((conv,), "zeros", ("mlp",)),
                "dt_bias": Leaf((H,), "dt", (None,)),
                "A_log": Leaf((H,), (1.0, 16.0), (None,)),
                "D": Leaf((H,), "ones", (None,)),
                "m_norm": Leaf((d,), "ones", ("mlp",)),
                "m_out": Leaf((d, h), d, ("mlp", "embed"))}

    def body(cfg, x, p, ctx):
        out, S = mamba2_mixer(
            rms_norm(x, p["op_norm"], cfg.rms_norm_eps), p,
            heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, groups=cfg.ssm_groups, chunk=cfg.ssm_chunk,
            eps=cfg.rms_norm_eps, mesh=ctx.mesh,
            norm_groups=getattr(cfg, norm_groups) if norm_groups else 1)
        if resid is not None:
            out = out * jnp.asarray(getattr(cfg, resid), cfg.dtype)
        return x + out, {"ssm_state": S}

    def keeps(cfg, shape, tokens, mesh):
        # The first rung's: the in-projection's output (z, x B C, dt:
        # ``mamba2_mixer`` names it ``ssm_in``), one array of ``m_in``'s
        # width a token in the activations' dtype, the same in both of the
        # scan's forms. Nothing else of a scan layer is named: the taps'
        # and the scan's second forwards read the kept array.
        # The working set, which the rung leaves as calibrated: the
        # in-projection's output and the taps' output with their
        # gradients, the gated output; beside them what the scan's form
        # puts in HBM (``scan_plan``). XLA's walk: one step's
        # decay matrices, their product with C B^T in float32 and the
        # activations' dtype and the gradients of those, and the state
        # before every step. Held to the compiled step at 16,384, 24,576
        # and 32,768 tokens of a 9 : 1 stack at full remat: 2.5, 3.3 and
        # 2.7% over what the compiler allots, 3.3% under at 8,192 (PERF.md
        # 6, PR 36). The kernels: the kept states and the running sums
        # alone, and neither the in-projection's output nor the gated
        # output is held a second time (the walk's float32 copies of x
        # went with it, and the skip is the kernels'): 5.2% over at 32,768
        # tokens, and still over a v5e's budget; a closer reckoning would
        # hand the attention layer a rung (PERF.md 7, PR 41)
        heads, d = shape["A_log"][-1], shape["m_out"][0]
        groups = cfg.ssm_groups
        state = (shape["m_conv"][0] - d) // (2 * groups)
        plan = scan_plan(1, tokens, heads, d // heads, state, groups,
                         cfg.ssm_chunk, mesh)
        first = tokens * jnp.dtype(cfg.dtype).itemsize * shape["m_in"][-1]
        if plan["form"] == "pallas":
            return kept(first=first,
                        width=shape["m_in"][-1] + 2 * shape["m_conv"][0],
                        rows=plan["float32_bytes_in_hbm"])
        return kept(
            first=first,
            width=2 * shape["m_in"][-1] + 2 * shape["m_conv"][0] + d,
            rows=4 * plan["decay_bytes_in_hbm"]
            + plan["steps"] * heads * (d // heads) * state * 4)

    def terms(cfg, states):
        return None, {counter: jnp.abs(states).max()}

    return Part(leaves, body, keeps, reports="ssm_state", terms=terms)
