"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
(WY / UT) form, and the mixer of a ``linear_attention`` layer of
Olmo-Hybrid.

The recurrence, a head at a time (``q_t, k_t [K]`` with ``|k_t| = 1``,
``v_t [V]``, ``g_t <= 0`` and ``beta_t`` in (0, 2) scalars, state ``S [V,
K]`` float32, zero before the sequence):

    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

Every position multiplies the state by a rank-one factor whose eigenvalue
along ``k_t`` is ``1 - beta_t``, of either sign: a ``beta`` that lost its
factor two, or a decay that lost its float32, is another model.

``gated_delta_rule`` computes it in chunks of ``chunk`` positions and
never token by token. Within a chunk, with ``G`` the running sum of ``g``,
``K``, ``V``, ``Q`` the chunk's rows and ``S_in`` the state before it:

    A = tril(diag(beta) (K K^T * exp(G_i - G_j)), -1);   T = (I + A)^-1
    W = T diag(beta) (K * exp(G));                       U = T diag(beta) V
    V' = U - W S_in^T
    O = (Q * exp(G)) S_in^T + tril(Q K^T * exp(G_i - G_j)) V'
    S_out = exp(G_end) S_in + V'^T (K * exp(G_end - G))

The result does not depend on ``chunk``. ``T`` is the inverse of a unit
lower-triangular ``[chunk, chunk]`` matrix a head: blocks of
``INVERSE_BASE`` rows by forward substitution (row ``i`` is ``e_i - A_i
T``, exact whatever the keys are, where a sum of powers of ``A`` cancels
catastrophically on repeated keys), joined two at a time by ``[[T11, 0],
[-T22 A21 T11, T22]]`` in float32 at the highest matmul precision.

The form is XLA's, not a kernel (``FORM``), walked as ``ops/ssm.ssd_scan``
is: a ``lax.scan`` whose step takes several chunks at once (as many as put
``WALK_BYTES`` of float32 pair matrices and carried states in HBM), builds
``A``, ``T``, ``W``, ``U`` for all of them in one batch, hands the state
from chunk to chunk in an inner ``lax.scan`` (two small matmuls a chunk),
and then builds ``O`` for all of them; the step is under
``jax.checkpoint``, so what the backward keeps of it is the state it
started from. ``rule_plan`` says what a call will do, and a traced call
writes it once as the kept span ``rtpu.gdn.rule_plan``.

Decays, running sums, ``beta``, ``A``, ``T`` and the carried state are
float32; the MXU's operands (``K``, ``V``, ``Q``, their decayed copies,
``T``, ``W``, ``V'`` and the state where it is multiplied) are the
activations' dtype with float32 accumulation. A sequence that is not whole
chunks is padded with ``g = 0``, ``beta = 0`` and zero rows, which move
neither state nor output.

Named scopes (metadata only): ``gdn`` holds ``gdn_in`` (the
in-projection), ``gdn_conv`` (the causal depthwise taps and the silu over
q, k and v: ``ops/ssm.causal_conv_silu``, on a TPU the kernel pair
``ops/conv.taps_silu`` with a zero bias), ``gdn_rule`` (the L2 norms,
``g`` and ``beta``, the rule), ``gdn_norm`` (the RMSNorm of each head and
the gate) and ``gdn_out`` (the out-projection).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import gated_rms_norm, l2_norm
from ray_tpu.ops.ssm import causal_conv_silu
from ray_tpu.util import tracing

FORM = "xla_walk"
# float32 a step of the walk may put in HBM: each chunk's pair matrices
# (the decays, A, T and Q K^T: four [chunk, chunk] a head) and the state
# carried into it. 8 chunks of 64 at 30 heads: the rule alone, forward and
# gradient, read 25 + 127 ms a layer so and 35 + 147 at four times the
# bytes (PERF.md 6, PR 39)
WALK_BYTES = 40 << 20
# rows of T found by forward substitution before blocks are joined
INVERSE_BASE = 16


def rule_plan(batch: int, seq: int, heads: int, key_dim: int,
              value_dim: int, chunk: int) -> Dict[str, Any]:
    """What ``gated_delta_rule`` does with these shapes: the chunk it uses
    (no longer than the sequence), the chunks, how many a step of the walk
    takes (``walk``: the largest divisor of the chunks within
    ``WALK_BYTES``), the steps, and the float32 bytes a step puts in HBM
    (pair matrices and carried states) beside what all chunks at once
    would."""
    chunk = min(chunk, seq)
    chunks = -(-seq // chunk)
    one = batch * heads * 4 * (4 * chunk * chunk + value_dim * key_dim)
    walk = max(w for w in range(1, chunks + 1)
               if chunks % w == 0 and (w == 1 or w * one <= WALK_BYTES))
    return {"seq": seq, "chunk": chunk, "chunks": chunks, "walk": walk,
            "steps": chunks // walk, "heads": heads, "key_dim": key_dim,
            "value_dim": value_dim, "form": FORM,
            "float32_bytes_in_hbm": walk * one,
            "float32_bytes_all_chunks": chunks * one}


def _unit_lower_inverse(A: jax.Array) -> jax.Array:
    """A [..., n, n] float32, zero on and above the diagonal -> ``(I +
    A)^-1`` (the module's docstring)."""
    n = A.shape[-1]
    if n > INVERSE_BASE and n % 2 == 0:
        half = n // 2
        blocks = A.reshape(A.shape[:-2] + (2, half, 2, half))
        # both diagonal blocks in one batch
        T11, T22 = _unit_lower_inverse(jnp.stack(
            [blocks[..., 0, :, 0, :], blocks[..., 1, :, 1, :]]))

        def mm(a, b):
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

        T21 = -mm(mm(T22, blocks[..., 1, :, 0, :]), T11)
        return jnp.concatenate([
            jnp.concatenate([T11, jnp.zeros_like(T11)], -1),
            jnp.concatenate([T21, T22], -1)], -2)
    def row(i, T):
        # rows from i on are still the identity's, and A_i is zero there
        A_i = jax.lax.dynamic_index_in_dim(A, i, A.ndim - 2, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            T, (i == jnp.arange(n)) - (A_i[..., None] * T).sum(-2), i,
            A.ndim - 2)

    # a loop and not n - 1 copies of its body: the program of a step holds
    # the rule six times over (PERF.md 6, PR 39)
    return jax.lax.fori_loop(
        1, n, row, jnp.broadcast_to(jnp.eye(n, dtype=A.dtype), A.shape))


def _walk_step(S, xs, dtype):
    """``walk`` chunks: S [b, H, V, K] float32, xs = (q and k [b, W, C, H,
    K], v [b, W, C, H, V], g and beta [b, W, C, H] float32) -> (the state
    after them, o [b, W, C, H, V])."""
    q, k, v, g, beta = xs
    C = q.shape[2]
    f32 = jnp.float32
    G = jnp.cumsum(g, axis=2)                            # [b, W, C, H]
    by_head = jnp.moveaxis(G, 2, -1)                     # [b, W, H, C]
    at = jnp.arange(C)
    decay = jnp.exp(jnp.where(                           # [b, W, H, C, C]
        at[:, None] >= at[None, :],
        by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    kk = jnp.einsum("bwihd,bwjhd->bwhij", k, k, preferred_element_type=f32)
    A = jnp.where(at[:, None] > at[None, :],
                  jnp.moveaxis(beta, 2, -1)[..., None] * kk * decay, 0.0)
    T = _unit_lower_inverse(A).astype(dtype)
    grown = jnp.exp(G)
    kf, vf = k.astype(f32), v.astype(f32)
    Wm = jnp.einsum("bwhij,bwjhd->bwihd", T,
                    (kf * (beta * grown)[..., None]).astype(dtype),
                    preferred_element_type=f32).astype(dtype)
    U = jnp.einsum("bwhij,bwjhd->bwihd", T,
                   (vf * beta[..., None]).astype(dtype),
                   preferred_element_type=f32)
    # K decayed to the chunk's end, and the whole chunk's decay
    k_end = (kf * jnp.exp(G[:, :, -1:] - G)[..., None]).astype(dtype)
    whole = jnp.exp(G[:, :, -1])                         # [b, W, H]

    def chunk_step(S, c):
        W_c, U_c, k_c, whole_c = c
        new = U_c - jnp.einsum("bchk,bhvk->bchv", W_c, S.astype(dtype),
                               preferred_element_type=f32)
        after = whole_c[..., None, None] * S + jnp.einsum(
            "bchv,bchk->bhvk", new.astype(dtype), k_c,
            preferred_element_type=f32)
        return after, (S, new.astype(dtype))

    S, (into, new) = jax.lax.scan(chunk_step, S, tuple(
        jnp.moveaxis(a, 1, 0) for a in (Wm, U, k_end, whole)))
    into, new = jnp.moveaxis(into, 0, 1), jnp.moveaxis(new, 0, 1)
    qk = jnp.einsum("bwihd,bwjhd->bwhij", q, k, preferred_element_type=f32)
    o = jnp.einsum("bwchk,bwhvk->bwchv",
                   (q.astype(f32) * grown[..., None]).astype(dtype),
                   into.astype(dtype), preferred_element_type=f32)
    o = o + jnp.einsum("bwhij,bwjhv->bwihv", (qk * decay).astype(dtype), new,
                       preferred_element_type=f32)
    return S, o.astype(dtype)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = 64
                     ) -> Tuple[jax.Array, jax.Array]:
    """q and k [b, s, H, K] (k of unit length, q scaled as the caller
    wants its outputs), v [b, s, H, V], g [b, s, H] float32 (the log of the
    decay, not positive), beta [b, s, H] float32 -> (o [b, s, H, V] in
    ``v``'s dtype, the state after the last position [b, H, V, K]
    float32)."""
    b, s, H, K = q.shape
    V = v.shape[-1]
    plan = rule_plan(b, s, H, K, V, chunk)
    with tracing.span("rtpu.gdn.rule_plan", keep=True, **plan):
        pass
    C, W, steps = plan["chunk"], plan["walk"], plan["steps"]
    pad = plan["chunks"] * C - s
    dtype = v.dtype

    def stepped(a):
        # [b, s, ...] -> [steps, b, W, C, ...]
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((b, steps, W, C) + a.shape[2:]), 1, 0)

    xs = (stepped(q), stepped(k), stepped(v), stepped(g.astype(jnp.float32)),
          stepped(beta.astype(jnp.float32)))
    # ``_walk_step`` is looked up at trace time: delta_limits.py plants its
    # faults there (a state that is not carried, decays in bfloat16)
    step = jax.checkpoint(lambda S, xs_: _walk_step(S, xs_, dtype))
    S, o = jax.lax.scan(step, jnp.zeros((b, H, V, K), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 1).reshape(b, steps * W * C, H, V)
    return o[:, :s], S


def _gates(a, b_, p):
    """The in-projection's a and b [b, s, H] -> (g, the log of the decay:
    ``-exp(A_log) softplus(a + dt_bias)``, and ``beta = 2 sigmoid(b)``, the
    two of ``linear_allow_neg_eigval``), float32."""
    f32 = jnp.float32
    return (-jnp.exp(p["g_A_log"].astype(f32)) * jax.nn.softplus(
        a.astype(f32) + p["g_dt_bias"].astype(f32)),
            2.0 * jax.nn.sigmoid(b_.astype(f32)))


def gated_delta_mixer(h: jax.Array, p: Dict[str, jax.Array], *, heads: int,
                      key_dim: int, value_dim: int, chunk: int = 64,
                      eps: float = 1e-6, mesh=None
                      ) -> Tuple[jax.Array, jax.Array]:
    """h [b, s, hidden] -> (the mixer's output [b, s, hidden], the state
    after the last position [b, H, V, K] float32, which no gradient
    passes). ``p``: ``g_in [hidden, 2 H V + 2 H K + 2 H]`` (the gate z,
    then q k v, then a and b), ``g_conv [2 H K + H V, taps]`` (q's, k's and
    v's own taps, no bias), ``g_dt_bias`` and ``g_A_log`` ``[H]``,
    ``g_norm [V]``, ``g_out [H V, hidden]``. No projection has a bias.
    ``mesh``: the one the caller's arrays are sharded over, if any
    (``causal_conv_silu`` keeps XLA's form under one)."""
    b, s, _ = h.shape
    dt_ = h.dtype
    hk, hv = heads * key_dim, heads * value_dim
    f32 = jnp.float32
    with jax.named_scope("gdn"):
        with jax.named_scope("gdn_in"):
            zqkvab = jnp.dot(h, p["g_in"].astype(dt_),
                             preferred_element_type=f32).astype(dt_)
            z = zqkvab[..., :hv]
            a, b_ = jnp.split(zqkvab[..., 2 * (hv + hk):], 2, axis=-1)
            # positions last, as the in-projection's output lies on a TPU
            by_channel = jnp.swapaxes(zqkvab, 1, 2)
        with jax.named_scope("gdn_conv"):
            # q k v read where the in-projection left them
            q, k, v = causal_conv_silu(
                by_channel, p["g_conv"], jnp.zeros((2 * hk + hv,), f32),
                first=hv, sizes=(hk, hk, hv), mesh=mesh,
                span="rtpu.gdn.conv_plan")
        with jax.named_scope("gdn_rule"):
            q, k, v = (jnp.swapaxes(x, 1, 2).reshape(b, s, heads, -1)
                       for x in (q, k, v))
            # ``_gates`` and ``l2_norm`` are looked up at trace time too
            g, beta = _gates(a, b_, p)
            o, S = gated_delta_rule(l2_norm(q, scale=key_dim ** -0.5),
                                    l2_norm(k), v, g, beta, chunk=chunk)
            S = jax.lax.stop_gradient(S)
        with jax.named_scope("gdn_norm"):
            y = gated_rms_norm(o, z.reshape(b, s, heads, value_dim),
                               p["g_norm"], eps).reshape(b, s, hv)
        with jax.named_scope("gdn_out"):
            out = jnp.dot(y, p["g_out"].astype(dt_),
                          preferred_element_type=f32).astype(dt_)
    return out, S
