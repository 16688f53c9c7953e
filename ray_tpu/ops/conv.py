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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
