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

The form is XLA's, not a kernel (``FORM``): the decay matrices ``exp(cs_q -
cs_k)`` are ``heads x chunk x chunk`` float32 a chunk, 2.1 GB for all the
chunks of one 32,768-position sequence at 64 heads, and the backward wants
them again. So the chunks are walked: a ``lax.scan`` whose step takes
several chunks at once (as many as put ``WALK_BYTES`` of decay matrices
in HBM), carries the float32 state, and is under ``jax.checkpoint``, so
that what the backward keeps of a step is the state it started from and
the step's decay matrices are built again. ``scan_plan`` says what a call
will do, and a traced call writes it once as the kept span
``rtpu.ssm.scan_plan``.

Decays, running sums and the state are float32; the MXU's operands are the
activations' dtype with float32 accumulation. A sequence that is not whole
chunks is padded with ``dt = 0``, which moves neither state nor output.

Named scopes (metadata only): ``ssm`` holds ``ssm_in`` (the in-projection;
the norm before it is the caller's), ``ssm_conv`` (the causal depthwise
taps, their bias and the silu: ``causal_conv_silu``, on a TPU the kernel
pair ``ops/conv.taps_silu``), ``ssm_scan`` (softplus, the scan, the skip
``D x``), ``ssm_norm`` (the gate and the RMSNorm over all channels) and
``ssm_out`` (the out-projection).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.conv import causal_taps, taps_plan, taps_silu
from ray_tpu.util import tracing

FORM = "xla_walk"
# decay matrices one step of the walk may put in HBM (float32, before the
# product that consumes them): 8 chunks of 256 at 64 heads
WALK_BYTES = 128 << 20


def scan_plan(batch: int, seq: int, heads: int, head_dim: int, state: int,
              groups: int, chunk: int) -> Dict[str, Any]:
    """What ``ssd_scan`` does with these shapes: the chunk it uses (no
    longer than the sequence), the chunks, how many a step of the walk
    takes (``walk``: the largest divisor of the chunks within
    ``WALK_BYTES``), the steps, and the float32 bytes of decay matrices a
    step puts in HBM beside what all chunks at once would."""
    chunk = min(chunk, seq)
    chunks = -(-seq // chunk)
    one = batch * heads * chunk * chunk * 4
    walk = max(w for w in range(1, chunks + 1)
               if chunks % w == 0 and (w == 1 or w * one <= WALK_BYTES))
    return {"seq": seq, "chunk": chunk, "chunks": chunks, "walk": walk,
            "steps": chunks // walk, "heads": heads, "head_dim": head_dim,
            "state": state, "groups": groups, "form": FORM,
            "decay_bytes_in_hbm": walk * one,
            "decay_bytes_all_chunks": chunks * one}


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
             C: jax.Array, chunk: int = 256
             ) -> Tuple[jax.Array, jax.Array]:
    """x [b, s, H, P], dt [b, s, H] float32 (after its softplus), A [H]
    float32 (negative), B and C [b, s, G, N] (G groups of H / G heads share
    a B and a C) -> (y [b, s, H, P] in ``x``'s dtype, the state after the
    last position [b, H, P, N] float32)."""
    b, s, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = H // G
    plan = scan_plan(b, s, H, P, N, G, chunk)
    with tracing.span("rtpu.ssm.scan_plan", keep=True, **plan):
        pass
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
                 chunk: int = 256, eps: float = 1e-5, mesh=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """h [b, s, hidden] (normed) -> (the mixer's output [b, s, hidden],
    the state after the last position [b, H, P, N] float32, which no
    gradient passes). ``mesh``: the one the caller's arrays are sharded
    over, if any (``causal_conv_silu`` keeps XLA's form under one).
    ``p``: ``m_in [hidden, 2 d + 2 G N + H]`` (z, then x B C, then dt; ``d =
    H P``), ``m_conv [d + 2 G N, taps]`` and ``m_conv_bias``, ``dt_bias``,
    ``A_log`` and ``D`` ``[H]``, ``m_norm [d]``, ``m_out [d, hidden]``.
    No projection has a bias; ``dt`` is not clamped (``time_step_limit``
    (0, inf))."""
    b, s, _ = h.shape
    dt_ = h.dtype
    d, gn = heads * head_dim, groups * state
    f32 = jnp.float32
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm_in"):
            zxbcdt = jnp.dot(h, p["m_in"].astype(dt_),
                             preferred_element_type=f32).astype(dt_)
            z, dt = zxbcdt[..., :d], zxbcdt[..., 2 * d + 2 * gn:]
            # positions last, as the in-projection's output lies on a TPU
            by_channel = jnp.swapaxes(zxbcdt, 1, 2)
        with jax.named_scope("ssm_conv"):
            # x B C read where the in-projection left them
            x, B, C = causal_conv_silu(by_channel, p["m_conv"],
                                       p["m_conv_bias"], first=d,
                                       sizes=(d, gn, gn), mesh=mesh)
        with jax.named_scope("ssm_scan"):
            x, B, C = (jnp.swapaxes(a, 1, 2) for a in (x, B, C))
            x = x.reshape(b, s, heads, head_dim)
            y, S = ssd_scan(
                x, jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32)),
                -jnp.exp(p["A_log"].astype(f32)),
                B.reshape(b, s, groups, state), C.reshape(b, s, groups, state),
                chunk=chunk)
            y = (y.astype(f32) + p["D"].astype(f32)[:, None] * x.astype(f32)
                 ).reshape(b, s, d)
            S = jax.lax.stop_gradient(S)
        with jax.named_scope("ssm_norm"):
            y = y * jax.nn.silu(z.astype(f32))
            y = (y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                   + eps)
                 * p["m_norm"].astype(f32)).astype(dt_)
        with jax.named_scope("ssm_out"):
            out = jnp.dot(y, p["m_out"].astype(dt_),
                          preferred_element_type=f32).astype(dt_)
    return out, S
