"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) and the rule whose
decay is a vector over the key's channels (Kimi Delta Attention, KDA,
arXiv:2510.26692) in their chunked (WY / UT) form, and the mixers of a
``linear_attention`` layer of Olmo-Hybrid and Qwen3-Next and of a KDA layer.

Two recurrences, a head at a time (``q_t, k_t [K]`` with ``|k_t| = 1``,
``v_t [V]``, ``beta_t`` in (0, 2), state ``S [V, K]`` float32, zero before
the sequence), told apart by the rank of ``g``: a decay a head, ``g_t <= 0``
a scalar,

    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

and a decay a key channel, ``g_t [K]``, each entry in (``lower_bound``, 0),

    S_t = S_{t-1} Diag(exp g_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

Every position multiplies the state by a rank-one factor whose eigenvalue
along ``k_t`` is ``1 - beta_t``, of either sign: a ``beta`` that lost its
factor two, or a decay that lost its float32, is another model.

``gated_delta_rule`` computes either in chunks of ``chunk`` positions and
never token by token. Within a chunk, with ``G`` the running sum of ``g``,
``K``, ``V``, ``Q`` the chunk's rows and ``S_in`` the state before it, a
decay a head:

    A = tril(diag(beta) (K K^T * exp(G_i - G_j)), -1);   T = (I + A)^-1
    W = T diag(beta) (K * exp(G));                       U = T diag(beta) V
    V' = U - W S_in^T
    O = (Q * exp(G)) S_in^T + tril(Q K^T * exp(G_i - G_j)) V'
    S_out = exp(G_end) S_in + V'^T (K * exp(G_end - G))

A decay a channel moves inside the two products over ``K``, with ``Gam =
exp(G)`` ``[C, K]``:

    A = tril(diag(beta) ((K * Gam) (K / Gam)^T), -1);    T = (I + A)^-1
    W = T diag(beta) (K * Gam);                          U = T diag(beta) V
    V' = U - W S_in^T
    O = (Q * Gam) S_in^T + tril((Q * Gam) (K / Gam)^T) V'
    S_out = S_in Diag(Gam_end) + V'^T (K * Gam_end / Gam)

``1 / Gam`` is never formed over a chunk (at -5 a position 64 rows reach
``exp(320)``). **The sub-block rule** (``_decayed_pairs``): a chunk is
sub-blocks of ``INVERSE_BASE`` rows (``_sub_block``) that take their decays
from the sub-block's first row ``r``: rows ``i`` of it carry ``exp(G_i -
G_r)`` (at most 1) and every row ``j`` up to the sub-block's end ``exp(G_r
- G_j)``: at most 1 before the sub-block, at most ``exp(5 x 15)`` inside it
for a ``g`` bounded by -5, within float32 and bfloat16; later rows are
masked before the exponential. So a pair in different sub-blocks multiplies
two factors that are at most 1.

The result does not depend on ``chunk``. ``T`` is the inverse of a unit
lower-triangular ``[chunk, chunk]`` matrix a head: blocks of
``INVERSE_BASE`` rows by forward substitution (row ``i`` is ``e_i - A_i
T``, exact whatever the keys are, where a sum of powers of ``A`` cancels
catastrophically on repeated keys), joined two at a time by ``[[T11, 0],
[-T22 A21 T11, T22]]`` in float32 at the highest matmul precision.

Two forms share this arithmetic and no code beyond ``_gates``, the norms'
``eps`` and the padding; which runs is read from the call and never set
(``_kernel_takes``; ``rule_plan`` says what a call will do, its ``decay``
with it, and a traced call writes it once as the kept span
``rtpu.gdn.rule_plan``, a KDA layer's as ``rtpu.kda.rule_plan``). Where
fewer key heads serve the value heads (Qwen3-Next: 16 under 32) value head
``i`` reads key head ``i // (heads / key heads)`` in both:

- ``xla_walk``, on the CPU, under a mesh (a Mosaic call is whole to the
  partitioner), for a chunk that is not whole tiles and for a decay a
  channel (no Mosaic form is built for it yet): walked as
  ``ops/ssm.ssd_scan`` is, a ``lax.scan`` whose step takes several chunks
  at once (as many as put ``WALK_BYTES`` of float32 pair matrices, decayed
  rows and carried states in HBM), builds ``A``, ``T``, ``W``, ``U`` for
  all of them in one batch, hands the state from chunk to chunk in an inner
  ``lax.scan`` (two small matmuls a chunk), and then builds ``O`` for all
  of them; the step is under ``jax.checkpoint``, so what the backward
  keeps of it is the state it started from. One step serves both decays:
  they differ in ``_decayed_pairs`` and in what a decay broadcasts over.
  q and k come [b, s, key heads, K], normed by ``l2_norm``, and are copied
  to the value heads (``_join_heads``). The controls of
  ``benchmark/tests/delta_limits.py`` plant their faults in ``_walk_step``,
  ``_unit_lower_inverse`` and this module's ``jnp``,
  ``delta_moe_limits.py``'s wrong head map in ``_join_heads``,
  ``kda_moe_limits.py``'s in ``_channel_gates``, ``_head_gated`` and
  ``l2_norm``.
- ``pallas``, on a TPU backend without a mesh, a decay a head
  (``rule_kernels``): two
  Mosaic calls behind a ``custom_vjp``, ``delta_rule_fwd`` and
  ``delta_rule_bwd``, on a grid of (batch row, a block of key heads with
  their value heads, ``KERNEL_HEADS`` of those at most, ``KERNEL_CHUNKS``
  chunks), the sequence the last and sequential axis. The operands are
  read and written where their producers and consumers hold them,
  positions last ([b, channels, s], as the taps' kernels leave q, k and
  v), q and k at the key heads, and XLA relays nothing for the calls: a
  grid step turns its blocks in VMEM, ``KERNEL_LANES`` positions at a
  time, takes q's and k's L2 norms there (``_kernel_norm``; the backward
  its ``jax.vjp``), forms a key head's ``K K^T`` and ``Q K^T`` once for
  its value heads (``_key_head`` is the map), and the backward adds a key
  head's ``dq`` and ``dk`` over its value heads in VMEM and writes them
  once. A chunk's decays, ``A``, ``T``, ``W``, ``U``, ``V'`` and ``Q K^T``
  live in VMEM and nothing ``[chunk, chunk]`` is written to HBM; the
  float32 state is carried in VMEM from step to step. ``T``'s diagonal
  tiles of ``KERNEL_BASE`` rows come by substitution in straight-line
  code, the heads of a block side by side on the lanes, and are joined as
  above. The forward writes the state before every grid step when a
  gradient is asked for (``states_kept``); the backward takes the steps
  last first, builds a step's chunks again from that state in VMEM, and
  walks them last first carrying the state's cotangent, ``dA = -T^T dT
  T^T`` under the diagonal. Its seams for the controls are
  ``_kernel_state``, ``_kernel_inverse``, ``_kernel_sums`` /
  ``_kernel_decays`` (through this module's ``jnp``), ``_kernel_norm`` and
  ``_key_head``, looked up while the kernels trace. Head widths of 96 and
  192 (Olmo-Hybrid) are whole sublane tiles and not whole registers of
  lanes: that is why the layout is positions last and not [b, s, heads x
  dim], and one layout serves both cells.

Decays, running sums, ``beta``, ``A``, ``T``, the norms and the carried
state are float32; the MXU's operands (``K``, ``V``, ``Q``, their decayed
copies, ``T``, ``W``, ``V'`` and the state where it is multiplied) are the
activations' dtype with float32 accumulation. A sequence that is not whole
chunks (the kernels: whole grid steps) is padded with ``g = 0``, ``beta =
0`` and zero rows, which move neither state nor output.

Named scopes (metadata only): ``gdn`` holds ``gdn_in`` (the
in-projection), ``gdn_conv`` (the causal depthwise taps and the silu over
q, k and v: ``ops/ssm.causal_conv_silu``, on a TPU the kernel pair
``ops/conv.taps_silu`` with a zero bias), ``gdn_rule`` (``rule_part``:
``g`` and ``beta``, the running sums, the rule in either form; the walk's
swaps, L2 norms and copies to the value heads with it, which the kernels
do in VMEM), ``gdn_norm`` (the RMSNorm of each head and the gate) and
``gdn_out`` (the out-projection); ``kda`` holds ``kda_in``, ``kda_conv``,
``kda_gate`` (the bounded gate and ``beta``), ``kda_rule``, ``kda_norm`` and
``kda_out`` (``kda_mixer``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import (Leaf, Part, gated_rms_norm, kept, l2_norm,
                                norm_start, rms_norm)
from ray_tpu.ops.ssm import causal_conv_silu
from ray_tpu.util import tracing

# float32 a step of XLA's walk may put in HBM: each chunk's pair matrices
# (the decays, A, T and Q K^T: four [chunk, chunk] a head) and the state
# carried into it. 8 chunks of 64 at 30 heads: the rule alone, forward and
# gradient, read 25 + 127 ms a layer so and 35 + 147 at four times the
# bytes (PERF.md 6, PR 39)
WALK_BYTES = 40 << 20
# rows of T found by forward substitution before blocks are joined
INVERSE_BASE = 16
# the kernels: value heads a grid step takes at most (what a chunk's chain
# waits for overlaps across them; their diagonal tiles lie side by side on
# the lanes while T's first rows are substituted, 2 heads of 4 tiles of 16 a
# register's 128; a step takes whole key heads: ``_heads_a_block``), chunks a
# grid step takes (the backward keeps the state before each step and builds
# the ones between again), and the rows of T substituted before blocks are
# joined. Read on the chip, the mixer's ``gdn_rule`` part whole, forward /
# gradient ms a layer (PERF.md 6, PR 53): Olmo-Hybrid's 30 heads of 96 / 192
# at 15 heads a step 10.6 / 37.7, 10 10.7 / 38.3, 6 11.6 / 40.8; Qwen3-Next's
# 32 on 16 of 128 / 128 at 16 10.6 / 33.3, 8 11.6 / 35.8, 4 14.2 / 41.9. (PR
# 40, head-major operands, 30 heads: 10, 8, 16 13.7 / 40.3; 6, 8, 32 14.9 /
# 43.2; 2, 8, 16 19.3 / 52.4; 6, 8, 64 22.1 / 57.1)
KERNEL_HEADS = 16
KERNEL_CHUNKS = 8
KERNEL_BASE = 16
# the positions of a grid step's block that the kernels turn at once (the
# operands lie positions last: a register's lanes), which a block's
# positions are whole numbers of
KERNEL_LANES = 128
# the ``eps`` of q's and k's L2 norms, in XLA (``l2_norm``) and in the kernels
QK_NORM_EPS = 1e-6


def _kernel_takes(chunk: int, mesh, key_dim: int, value_dim: int,
                  decay: str = "head") -> bool:
    """Whether a call runs as the kernels: on a TPU backend (anything but
    the CPU), without a ``mesh`` (a Mosaic call is whole to the
    partitioner, which would gather its operands: XLA's walk shards as the
    arrays do), with one decay a head (a decay a channel is the walk's),
    with a chunk (a sequence shorter than one is its own) that is whole
    tiles of ``KERNEL_BASE`` rows, a power of two of them, and with heads
    of whole sublane tiles (a block's heads lie one under another)."""
    tiles = chunk // KERNEL_BASE
    return (mesh is None and jax.default_backend() != "cpu"
            and chunk == tiles * KERNEL_BASE and tiles & (tiles - 1) == 0
            and key_dim % 8 == 0 and value_dim % 8 == 0 and decay == "head")


def _heads_a_block(heads: int, key_heads: Optional[int] = None) -> int:
    """The value heads a grid step takes: whole key heads with the ``heads
    / key_heads`` value heads of each, as many key heads as divide theirs
    and keep the value heads within ``KERNEL_HEADS`` (one key head's at the
    least)."""
    key_heads = key_heads or heads
    ratio = heads // key_heads
    return ratio * max(k for k in range(1, key_heads + 1) if key_heads % k == 0
                       and (k == 1 or k * ratio <= KERNEL_HEADS))


def _sub_block(chunk: int) -> int:
    """The rows of a chunk whose pair decays share one reference row (the
    module's docstring's sub-block rule): the largest divisor of the chunk
    within ``INVERSE_BASE``."""
    return max(r for r in range(1, min(INVERSE_BASE, chunk) + 1)
               if chunk % r == 0)


def _decayed_rows(decay: str, chunk: int) -> int:
    """The float32 ``[rows, K]`` a head and chunk that a decay a channel
    adds to a walk's step (``rule_plan``'s ``one``): the running sums, k's
    and q's rows decayed from their sub-block's reference row, and the keys
    decayed to each sub-block's reference row."""
    if decay != "channel":
        return 0
    return (3 + chunk // _sub_block(chunk)) * chunk


def rule_plan(batch: int, seq: int, heads: int, key_dim: int,
              value_dim: int, chunk: int, mesh=None,
              key_heads: Optional[int] = None, decay: str = "head"
              ) -> Dict[str, Any]:
    """What the rule does with these shapes, and in which ``form``.
    ``heads`` are the value heads, the rule's own; ``key_heads`` those of q
    and k where fewer serve them, and ``joined`` how a value head comes by
    its key head: "repeat" (XLA's walk: q and k copied to the value heads,
    ``_join_heads``), "index_map" (the kernels: a grid step takes a block of
    key heads and the value heads of each, q and k are read and ``dq`` and
    ``dk`` written once at the key heads, ``_key_head``), None where they
    are as many. ``decay``: "head" (``g [b, s, H]``) or "channel" (``g [b,
    s, H, K]``, which adds ``sub_block``, the rows that share a reference
    row).
    Both forms: the chunk it uses (no longer than the sequence), the chunks,
    the ``steps`` (of the walk, or of the kernels' grid along the sequence),
    ``chunks_a_call`` (what one step takes), ``states_kept`` (the float32
    states a backward starts from, one a step) and the float32 bytes the form
    puts in HBM beside what all chunks' pair matrices at once would.
    ``xla_walk``: ``walk`` (= ``chunks_a_call``, the largest divisor of the
    chunks within ``WALK_BYTES``) and the bytes of one step's pair matrices,
    decayed rows and carried states. ``pallas``: ``heads_a_block``
    (``_heads_a_block``),
    ``KERNEL_CHUNKS`` chunks a step (all of a shorter sequence; whole
    ``KERNEL_LANES`` positions), ``operands`` ("positions_last": q, k, v, o
    and their gradients are read and written [b, channels, s], as the taps
    leave them) and the bytes of the kept states, the last state and the
    running sums (in their two layouts) and ``beta``: nothing ``[chunk,
    chunk]``."""
    chunk, key_heads = min(chunk, seq), key_heads or heads
    chunks = -(-seq // chunk)
    one = batch * heads * 4 * (4 * chunk * chunk + value_dim * key_dim
                               + _decayed_rows(decay, chunk) * key_dim)
    kernels = _kernel_takes(chunk, mesh, key_dim, value_dim, decay)
    plan = {"seq": seq, "chunk": chunk, "chunks": chunks, "heads": heads,
            "key_heads": key_heads, "decay": decay,
            "joined": None if key_heads == heads else (
                "index_map" if kernels else "repeat"),
            "key_dim": key_dim, "value_dim": value_dim,
            "float32_bytes_all_chunks": chunks * one}
    if decay == "channel":
        plan["sub_block"] = _sub_block(chunk)
    if kernels:
        whole = max(1, KERNEL_LANES // chunk)
        call = -(-min(KERNEL_CHUNKS, chunks) // whole) * whole
        steps = -(-chunks // call)
        state = batch * heads * value_dim * key_dim * 4
        return dict(plan, form="pallas", walk=None, steps=steps,
                    heads_a_block=_heads_a_block(heads, key_heads),
                    chunks_a_call=call, states_kept=steps,
                    operands="positions_last",
                    float32_bytes_in_hbm=(steps + 1) * state
                    + 3 * batch * heads * steps * call * chunk * 4)
    walk = max(w for w in range(1, chunks + 1)
               if chunks % w == 0 and (w == 1 or w * one <= WALK_BYTES))
    return dict(plan, form="xla_walk", walk=walk, steps=chunks // walk,
                heads_a_block=None, chunks_a_call=walk,
                states_kept=chunks // walk, operands=None,
                float32_bytes_in_hbm=walk * one)


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


def _decayed_pairs(q, k, G, beta, dtype):
    """``walk`` chunks' ``diag(beta) K K^T`` and ``Q K^T`` with their pair
    decays: q and k [b, W, C, H, K], G the running sums, [b, W, C, H] or a
    key channel [b, W, C, H, K], and beta [b, W, C, H] float32 -> two [b,
    W, H, C, C] float32, row ``i`` and column ``j`` the product decayed
    from ``j`` to ``i``, zero where ``j > i``. A decay a head multiplies
    the product; a decay a channel lies inside it, by the sub-block rule of
    the module's docstring."""
    b, W, C, H, K = q.shape
    f32 = jnp.float32
    at = jnp.arange(C)
    causal = at[:, None] >= at[None, :]
    by_row = jnp.moveaxis(beta, 2, -1)[..., None]        # [b, W, H, C, 1]
    if G.ndim == 4:
        by_head = jnp.moveaxis(G, 2, -1)                 # [b, W, H, C]
        decay = jnp.exp(jnp.where(                       # [b, W, H, C, C]
            causal, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        kk, qk = (jnp.einsum("bwihd,bwjhd->bwhij", x, k,
                             preferred_element_type=f32) for x in (k, q))
        return by_row * kk * decay, qk * decay
    R = _sub_block(C)
    n = C // R
    kf = k.astype(f32)
    ref = G.reshape(b, W, n, R, H, K)[:, :, :, 0]        # [b, W, n, H, K]
    from_ref = jnp.exp(G.reshape(b, W, n, R, H, K) - ref[:, :, :, None])
    rows = jnp.concatenate(                              # k's rows, then q's
        [(x.reshape(b, W, n, R, H, K) * from_ref).astype(dtype)
         for x in (kf, q.astype(f32))], axis=3)          # [b, W, n, 2 R, H, K]
    upto = at[None, :] < (jnp.arange(n)[:, None] + 1) * R          # [n, C]
    to_ref = jnp.exp(jnp.where(                          # [b, W, n, C, H, K]
        upto[None, None, :, :, None, None],
        ref[:, :, :, None] - G[:, :, None], -jnp.inf))
    pairs = jnp.einsum("bwnrhd,bwnjhd->bwhnrj", rows,
                       (kf[:, :, None] * to_ref).astype(dtype),
                       preferred_element_type=f32)       # [b, W, H, n, 2R, C]
    kk, qk = (x.reshape(b, W, H, C, C)
              for x in (pairs[:, :, :, :, :R], pairs[:, :, :, :, R:]))
    return jnp.where(causal, by_row * kk, 0.0), jnp.where(causal, qk, 0.0)


def _walk_step(S, xs, dtype):
    """``walk`` chunks: S [b, H, V, K] float32, xs = (q and k [b, W, C, H,
    K], v [b, W, C, H, V], g [b, W, C, H] or, a decay a key channel, [b, W,
    C, H, K], and beta [b, W, C, H] float32) -> (the state after them, o
    [b, W, C, H, V])."""
    q, k, v, g, beta = xs
    C = q.shape[2]
    f32 = jnp.float32
    G = jnp.cumsum(g, axis=2)                            # [b, W, C, H(, K)]
    kk, qk = _decayed_pairs(q, k, G, beta, dtype)
    at = jnp.arange(C)
    A = jnp.where(at[:, None] > at[None, :], kk, 0.0)
    T = _unit_lower_inverse(A).astype(dtype)

    def wide(x):
        # over the key's channels: a decay a head is one number for all
        return x if g.ndim == 5 else x[..., None]

    grown = wide(jnp.exp(G))
    kf, vf = k.astype(f32), v.astype(f32)
    Wm = jnp.einsum("bwhij,bwjhd->bwihd", T,
                    (kf * (grown * beta[..., None])).astype(dtype),
                    preferred_element_type=f32).astype(dtype)
    U = jnp.einsum("bwhij,bwjhd->bwihd", T,
                   (vf * beta[..., None]).astype(dtype),
                   preferred_element_type=f32)
    # K decayed to the chunk's end, and the whole chunk's decay
    k_end = (kf * wide(jnp.exp(G[:, :, -1:] - G))).astype(dtype)
    whole = wide(jnp.exp(G[:, :, -1]))[:, :, :, None]    # [b, W, H, 1, K | 1]

    def chunk_step(S, c):
        W_c, U_c, k_c, whole_c = c
        new = U_c - jnp.einsum("bchk,bhvk->bchv", W_c, S.astype(dtype),
                               preferred_element_type=f32)
        after = whole_c * S + jnp.einsum(
            "bchv,bchk->bhvk", new.astype(dtype), k_c,
            preferred_element_type=f32)
        return after, (S, new.astype(dtype))

    S, (into, new) = jax.lax.scan(chunk_step, S, tuple(
        jnp.moveaxis(a, 1, 0) for a in (Wm, U, k_end, whole)))
    into, new = jnp.moveaxis(into, 0, 1), jnp.moveaxis(new, 0, 1)
    o = jnp.einsum("bwchk,bwhvk->bwchv",
                   (q.astype(f32) * grown).astype(dtype),
                   into.astype(dtype), preferred_element_type=f32)
    o = o + jnp.einsum("bwhij,bwjhv->bwihv", qk.astype(dtype), new,
                       preferred_element_type=f32)
    return S, o.astype(dtype)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = 64, mesh=None,
                     span: str = "rtpu.gdn.rule_plan", **said
                     ) -> Tuple[jax.Array, jax.Array]:
    """q and k [b, s, key heads, K] (k of unit length, q scaled as the
    caller wants its outputs), v [b, s, H, V] (``H`` a multiple of the key
    heads: value head ``i`` reads key head ``i // (H / key heads)``), g
    float32, the log of the decay, not positive: [b, s, H], or a decay a key
    channel [b, s, H, K] (every head then has q and k of its own, and ``g``
    is bounded below: a sub-block's ``exp(-R g)`` must stay inside float32),
    beta [b, s, H] float32 -> (o [b, s, H, V] in ``v``'s dtype, the state
    after the last position [b, H, V, K] float32). ``mesh``: the one the
    caller's arrays are sharded over, if any. Which form runs is read from
    the call (``_kernel_takes``), and the kept span ``span`` says which,
    with what the caller ``said``. The kernels take their operands positions
    last (``rule_kernels``; the mixer hands them its own so): this order is
    swapped around them. The walk reads q and k copied to the value heads
    (``_join_heads``)."""
    b, s, key_heads, K = q.shape
    H, V = v.shape[2:]
    decay = "channel" if g.ndim == 4 else "head"
    if decay == "channel" and not q.shape == k.shape == g.shape[:2] + (H, K):
        raise ValueError(
            f"a decay a channel wants q, k and g of one shape and v at their "
            f"heads: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}")
    plan = rule_plan(b, s, H, K, V, chunk, mesh, key_heads, decay)
    with tracing.span(span, keep=True, **plan, **said):
        pass
    if plan["form"] == "pallas":
        def last(a):
            return jnp.swapaxes(a.reshape(b, s, -1), 1, 2)

        # looked up at trace time: a test hands it the interpreter
        o, S = rule_kernels(last(q), last(k), last(v), g, beta, plan)
        return jnp.swapaxes(o, 1, 2).reshape(b, s, H, V), S
    if key_heads != H:
        # looked up at trace time too: a test plants a wrong map
        q, k = (_join_heads(x, H) for x in (q, k))
    C, W, steps = plan["chunk"], plan["walk"], plan["steps"]
    pad = plan["chunks"] * C - s
    dtype = v.dtype

    def stepped(a):
        # [b, s, ...] -> [steps, b, W, C, ...]
        return jnp.moveaxis(_padded(a, pad).reshape(
            (b, steps, W, C) + a.shape[2:]), 1, 0)

    xs = (stepped(q), stepped(k), stepped(v), stepped(g.astype(jnp.float32)),
          stepped(beta.astype(jnp.float32)))
    # ``_walk_step`` is looked up at trace time: delta_limits.py plants its
    # faults there (a state that is not carried, decays in bfloat16)
    step = jax.checkpoint(lambda S, xs_: _walk_step(S, xs_, dtype))
    S, o = jax.lax.scan(step, jnp.zeros((b, H, V, K), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 1).reshape(b, steps * W * C, H, V)
    return o[:, :s], S


def _padded(a, pad):
    """a [b, s, ...] with ``pad`` more positions of zeros: ``g = 0``,
    ``beta = 0`` and zero rows move neither state nor output."""
    if not pad:
        return a
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


# ---- the rule as Pallas (Mosaic) kernels. The operands lie in HBM where
# their producers left them, positions last: q and k [b, key heads x K, S],
# v and o [b, heads x V, S]. A grid step takes ``kb`` key heads, the ``hb =
# kb x ratio`` value heads that read them and ``n`` chunks of one batch row:
# blocks [1, kb K, n C] and [1, hb V, n C], which the body turns in VMEM,
# ``KERNEL_LANES`` positions at a time, into scratch [kb, n C, K] and [hb, n
# C, V], positions on the sublanes (q and k L2-normed on the way, where the
# caller asks); the chunks' walk reads and writes that scratch, and what it
# wrote is turned back into the output blocks. A chunk's running sums and
# beta come down the sublanes (``cols`` [C, 2 n]: what scales a row), the
# sums along the lanes too (``rows`` [n, C]: a pair's other end), so that no
# [C, C] is ever transposed. The state is carried transposed, ``P = S^T
# [K, V]``, in the block of the last-state output, which stays in VMEM
# while the grid walks a head's sequence.


def _nt(a, b, **kw):
    """a [m, d], b [n, d] -> a b^T [m, n], float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32, **kw)


def _tn(a, b, **kw):
    """a [d, m], b [d, n] -> a^T b [m, n], float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32, **kw)


def _nn(a, b, **kw):
    return jnp.dot(a, b, preferred_element_type=jnp.float32, **kw)


def _at(shape):
    """(row, column) of every entry of a 2-D ``shape``."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _kernel_sums(g):
    """g [..., C, H] float32 -> its running sums down a chunk (the
    kernels' ``G``; looked up at trace time, as ``_kernel_decays`` is)."""
    return jnp.cumsum(g, axis=-2)


def _kernel_decays(Gc, Gr):
    """Every decay a chunk's kernels use, formed here and nowhere else:
    Gc [C, 1] and Gr [1, C], the chunk's running sums down the sublanes
    and along the lanes -> ``pair`` [C, C] (``exp(G_i - G_j)`` on and
    under the diagonal, zero above), ``grown`` (``exp(G)``) and ``to_end``
    (``exp(G_end - G)``), both [C, 1], and ``whole`` (``exp(G_end)`` [1,
    1]), float32."""
    C = Gc.shape[0]
    row, col = _at((C, C))
    # (the last sum by a masked sum: Mosaic does not spread a [1, 1] that
    # it sliced from the last sublane over lanes and sublanes at once)
    end = jnp.where(row[:, :1] == C - 1, Gc, 0.0).sum(0, keepdims=True)
    return {"pair": jnp.exp(jnp.where(row >= col, Gc - Gr, -jnp.inf)),
            "grown": jnp.exp(Gc), "to_end": jnp.exp(end - Gc),
            "whole": jnp.exp(end)}


def _kernel_state(P):
    """The state a chunk starts from, as the kernels carried it to there
    (P [K, V] float32; looked up at trace time)."""
    return P


def _kernel_inverse(A: List[jax.Array]) -> List[jax.Array]:
    """``(I + A)^-1`` of several chunks at once, inside a kernel: each
    ``A`` [C, C] float32 and zero on and above the diagonal; as many
    chunks at a time as fill a register's 128 lanes (``_inverse_group``)."""
    per = max(1, 128 // A[0].shape[0])
    return [T for first in range(0, len(A), per)
            for T in _inverse_group(A[first:first + per])]


def _inverse_group(A: List[jax.Array]) -> List[jax.Array]:
    """The diagonal tiles of ``KERNEL_BASE`` rows by substitution, every
    tile of every chunk side by side on the lanes ([base, chunks * C]: an
    update is whole registers) and every update straight-line code: with
    ``t`` a tile's inverse, begun as the identity, step ``j`` takes ``a[i,
    j] t[j, :]`` from every row ``i`` under ``j`` (row ``j`` is final by
    then), for all tiles at once: row ``j`` spread down the sublanes,
    column ``j`` of each tile's ``a`` spread over the tile's lanes by one
    gather along the lanes. Nothing is summed across lanes, and what a
    step waits for is a multiply and a subtraction. Then the joins, as the
    XLA form's, the chunks block-diagonal in one matrix: with ``Td`` the
    inverses of the diagonal blocks of ``m`` rows and ``M`` the entries of
    ``A`` that join two of them, ``Td - (Td M) Td`` holds the diagonal
    blocks of ``2 m``, float32 at the highest precision. Exact whatever
    the keys are."""
    C, n = A[0].shape[0], len(A)
    base, wide = min(KERNEL_BASE, C), len(A) * C
    f32 = jnp.float32
    if n > 1:
        zero = jnp.zeros((C, C), f32)
        both = jnp.concatenate([jnp.concatenate(
            [a if i == at else zero for i in range(n)], axis=1)
            for at, a in enumerate(A)], axis=0)
    else:
        both = A[0]
    row, col = _at((wide, wide))
    _, lane = _at((base, wide))
    packed = both[:base]
    for t in range(1, wide // base):
        packed = jnp.where(lane // base == t,
                           both[t * base:(t + 1) * base], packed)
    lanes = -(-wide // 128) * 128           # a gather takes whole registers
    if lanes != wide:
        packed = jnp.concatenate(
            [packed, jnp.zeros((base, lanes - wide), f32)], axis=1)
    at_row, at_col = _at((base, lanes))
    first = at_col - at_col % base          # a tile's first lane
    P = (at_row == at_col % base).astype(f32)
    for j in range(base - 1):
        P = P - (jnp.take_along_axis(packed, first + j, axis=1)
                 * jnp.broadcast_to(P[j:j + 1], P.shape))
    T = jnp.where(row // base == col // base,
                  jnp.concatenate([P[:, :wide]] * (wide // base), axis=0)
                  if wide > base else P[:, :wide], 0.0)
    m = base
    while m < C:
        join = jnp.where((row // (2 * m) == col // (2 * m))
                         & (row // m != col // m), both, 0.0)
        high = jax.lax.Precision.HIGHEST
        T = T - _nn(_nn(T, join, precision=high), T, precision=high)
        m *= 2
    return [T[i * C:(i + 1) * C, i * C:(i + 1) * C] for i in range(n)]


def _key_head(h: int, ratio: int) -> int:
    """The key head, of a grid step's own, that the step's value head ``h``
    reads: a step holds whole key heads with the ``ratio`` value heads of
    each, neighbours sharing one (``_join_heads``' map; the kernels' bodies
    look it up while they trace)."""
    return h // ratio


def _kernel_norm(x, eps, scale):
    """``l2_norm``'s arithmetic on a tile in VMEM: x [D, positions] float32,
    a position a lane -> ``scale x / sqrt(sum(x^2) + eps)`` down each lane
    (looked up at trace time; the backward takes its ``jax.vjp``)."""
    return x * (jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=0, keepdims=True) + eps) * scale)


def _norms_of(norm):
    """``rule_kernels``' ``norm`` (``eps``, q's ``scale``) -> (q's, k's)
    arguments of ``_kernel_norm``, k's scale 1; (None, None) for none."""
    return (norm, (norm[0], 1.0)) if norm else (None, None)


def _lane_groups(block_ref, body):
    """``body(at)`` for each ``KERNEL_LANES`` positions ``at`` of a block
    whose last axis is its positions (a loop, its body traced once)."""
    import jax.experimental.pallas as pl

    w = min(KERNEL_LANES, block_ref.shape[-1])

    def group(i, carry):
        body(pl.ds(pl.multiple_of(i * w, w), w))
        return carry

    jax.lax.fori_loop(0, block_ref.shape[-1] // w, group, 0)


def _turned_in(block_ref, rows_ref, norm=None):
    """A block [1, heads D, L], positions last, into scratch [heads, L, D],
    positions on the sublanes; ``norm`` (``eps``, ``scale``): each head's
    rows L2-normed on the way (float32, one rounding to the scratch's
    dtype, as ``l2_norm``)."""
    heads, _, D = rows_ref.shape

    def group(at):
        for h in range(heads):
            x = block_ref[0, h * D:(h + 1) * D, at]
            if norm is not None:
                x = _kernel_norm(x.astype(jnp.float32), *norm)
            rows_ref[h, at, :] = x.astype(rows_ref.dtype).T

    _lane_groups(block_ref, group)


def _turned_out(rows_ref, block_ref, normed=None):
    """Scratch [heads, L, D] into a block [1, heads D, L]. ``normed`` (the
    block of q or k that ``_turned_in`` normed, and its ``norm``): the rows
    are the normed rows' cotangent, and what leaves is the block's, through
    ``_kernel_norm``'s ``jax.vjp`` in float32."""
    heads, _, D = rows_ref.shape
    f32 = jnp.float32

    def group(at):
        for h in range(heads):
            own = slice(h * D, (h + 1) * D)
            d = rows_ref[h, at, :].T
            if normed is not None:
                x_ref, norm = normed
                _, back = jax.vjp(lambda x: _kernel_norm(x, *norm),
                                  x_ref[0, own, at].astype(f32))
                d, = back(d.astype(f32))
            block_ref[0, own, at] = d.astype(block_ref.dtype)

    _lane_groups(block_ref, group)


def _chunk_keys(q, k):
    """What the value heads of one key head share of a chunk: its rows q
    and k [C, K] (``q``, ``k``; float32 ``qf``, ``kf``) and ``K K^T`` and
    ``Q K^T`` [C, C] float32 (``kk``, ``qk``) from one product, k stacked
    over q: the MXU holds the right-hand side's tile while the rows stream,
    and 64 rows leave it waiting for the next tile."""
    f32 = jnp.float32
    C = k.shape[0]
    with_k = _nt(jnp.concatenate([k, q], axis=0), k)
    return dict(q=q, k=k, qf=q.astype(f32), kf=k.astype(f32),
                kk=with_k[:C], qk=with_k[C:])


def _chunk_local(keys, v, Gc, Gr, bc, dt):
    """What a chunk's kernels build for one value head from the chunk's own
    rows alone, the state aside: ``keys`` (``_chunk_keys`` of its key
    head), v [C, V], the head's running sums down the sublanes (``Gc`` [C,
    1]) and along the lanes (``Gr`` [1, C]) and its beta down the sublanes
    -> ``keys``' entries, ``bc``, the decays (``_kernel_decays``), ``kkd``
    (``K K^T`` times the pair decays) and ``A`` float32, ``vf``, the MXU's
    operands ``Kb`` (``beta exp(G) K``), ``Vb`` (``beta V``), ``Qg``
    (``exp(G) Q``), ``Ke`` (K decayed to the chunk's end) in ``dt``, and
    ``Mf`` (``Q K^T`` times the pair decays, float32)."""
    f32 = jnp.float32
    d = _kernel_decays(Gc, Gr)
    row, col = _at(d["pair"].shape)
    kkd = keys["kk"] * d["pair"]
    qf, kf, vf = keys["qf"], keys["kf"], v.astype(f32)
    return dict(
        d, **keys, bc=bc, kkd=kkd, vf=vf,
        A=jnp.where(row > col, bc * kkd, 0.0),
        Kb=(kf * (bc * d["grown"])).astype(dt), Vb=(vf * bc).astype(dt),
        Qg=(qf * d["grown"]).astype(dt), Ke=(kf * d["to_end"]).astype(dt),
        Mf=keys["qk"] * d["pair"])


def _rows_of(j, C):
    """Chunk ``j``'s rows of a grid step's scratch (``j`` a loop's
    index)."""
    import jax.experimental.pallas as pl

    return pl.ds(pl.multiple_of(j * C, C), C)


def _column(cols, j):
    """Column ``j`` of ``cols`` [C, 2 n] as [C, 1], ``j`` a loop's index (a
    lane cannot be sliced at one: the others are masked out of a sum)."""
    _, lane = _at(cols.shape)
    return jnp.where(lane == j, cols, 0.0).sum(1, keepdims=True)


def _readers(key_heads: int, heads: int):
    """[(key head, the value heads that read it)] of a grid step of
    ``key_heads`` key heads and ``heads`` value heads: ``_key_head``'s map,
    looked up at trace time."""
    reads = [_key_head(h, heads // key_heads) for h in range(heads)]
    return [(kh, [h for h in range(heads) if reads[h] == kh])
            for kh in range(key_heads)]


def _chunk_of(j, h, keys, vs_ref, cols_ref, rows_ref):
    """``_chunk_local`` of chunk ``j`` of a grid step's value head ``h``,
    ``keys`` its key head's ``_chunk_keys`` of that chunk."""
    import jax.experimental.pallas as pl

    C, n = rows_ref.shape[-1], rows_ref.shape[-2]
    cols = cols_ref[0, h, 0]
    return _chunk_local(
        keys, vs_ref[h, _rows_of(j, C), :], _column(cols, j),
        rows_ref[0, h, 0, pl.ds(j, 1), :], _column(cols, n + j),
        vs_ref.dtype)


def _chunks_of(j, qs_ref, ks_ref, vs_ref, cols_ref, rows_ref):
    """Chunk ``j`` of every value head of a grid step, in the heads' order,
    with every chunk's ``T``, ``W`` (in the activations' dtype) and ``U``;
    a key head's rows and products are formed once for its value heads."""
    dt, at = vs_ref.dtype, _rows_of(j, rows_ref.shape[-1])
    local = {}
    for kh, heads in _readers(qs_ref.shape[0], vs_ref.shape[0]):
        keys = _chunk_keys(qs_ref[kh, at, :], ks_ref[kh, at, :])
        for h in heads:
            local[h] = _chunk_of(j, h, keys, vs_ref, cols_ref, rows_ref)
    local = [local[h] for h in sorted(local)]
    # ``_kernel_inverse`` is looked up at trace time, as ``_kernel_state``
    # and ``_kernel_decays`` are: the controls' three seams
    for x, T in zip(local, _kernel_inverse([x["A"] for x in local])):
        Tb = T.astype(dt)
        x.update(T=T, W=_nn(Tb, x["Kb"]).astype(dt), U=_nn(Tb, x["Vb"]))
    return local


def _handed_on(x, P, dt):
    """(V' in ``dt``, the state after the chunk, the chunk's output
    float32): ``x`` the chunk's matrices with ``W`` and ``U``, P [K, V] the
    state it starts from. W over Qg against the state, M over Ke^T against
    V'."""
    C = x["W"].shape[0]
    on_state = _nn(jnp.concatenate([x["W"], x["Qg"]], axis=0), P.astype(dt))
    new = (x["U"] - on_state[:C]).astype(dt)
    on_new = _nn(jnp.concatenate([x["Mf"].astype(dt), x["Ke"].T], axis=0),
                 new)
    return new, x["whole"] * P + on_new[C:], on_state[C:] + on_new[:C]


def _rule_fwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, o_ref,
                     last_ref, *more, norm):
    """A grid step of the forward: its blocks turned into scratch (``more``:
    the kept state's block where a gradient is asked for, then the scratch
    of q, k, v and o), its chunks one after another (a loop, its body
    traced once: what overlaps is a chunk's heads), the state in
    ``last_ref``'s block from step to step, and o turned back."""
    import jax.experimental.pallas as pl

    *kept_ref, qs_ref, ks_ref, vs_ref, os_ref = more
    (n, C), dt = rows_ref.shape[-2:], v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        last_ref[...] = jnp.zeros_like(last_ref)

    for ref in kept_ref:                    # the state this step starts from
        ref[0, :, 0] = last_ref[0]
    q_norm, k_norm = _norms_of(norm)
    _turned_in(q_ref, qs_ref, q_norm)
    _turned_in(k_ref, ks_ref, k_norm)
    _turned_in(v_ref, vs_ref)

    def chunk(j, carry):
        for h, x in enumerate(_chunks_of(j, qs_ref, ks_ref, vs_ref, cols_ref,
                                         rows_ref)):
            _, last_ref[0, h], o = _handed_on(
                x, _kernel_state(last_ref[0, h]), dt)
            os_ref[h, _rows_of(j, C), :] = o.astype(os_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)
    _turned_out(os_ref, o_ref)


def _rule_bwd_kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, kept_ref,
                     do_ref, dlast_ref, dq_ref, dk_ref, dv_ref, dcols_ref,
                     drows_ref, state_ref, T_ref, W_ref, new_ref, dstate_ref,
                     qs_ref, ks_ref, vs_ref, dos_ref, dqs_ref, dks_ref,
                     dvs_ref, *, norm):
    """A grid step of the backward, the steps taken last first: its blocks
    turned into scratch as the forward's; the chunks' states, ``T``, ``W``
    and ``V'`` built again from the state the step started from
    (``kept_ref``) into scratch; then the chunks last first, ``dstate_ref``
    carrying the state's cotangent from step to step, a key head's ``dq``
    and ``dk`` summed over its value heads in float32 (what multiplies q
    and k summed before its product) into scratch; and the three gradients
    turned back, q's and k's through their norms."""
    import jax.experimental.pallas as pl

    (n, C), dt = rows_ref.shape[-2:], v_ref.dtype
    refs = (qs_ref, ks_ref, vs_ref, cols_ref, rows_ref)
    q_norm, k_norm = _norms_of(norm)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dlast_ref[0]

    state_ref[0] = kept_ref[0, :, 0]
    dcols_ref[...] = jnp.zeros_like(dcols_ref)
    _turned_in(q_ref, qs_ref, q_norm)
    _turned_in(k_ref, ks_ref, k_norm)
    _turned_in(v_ref, vs_ref)
    _turned_in(do_ref, dos_ref)

    def again(j, carry):
        for h, x in enumerate(_chunks_of(j, *refs)):
            P = _kernel_state(state_ref[j, h])
            state_ref[j, h], T_ref[j, h], W_ref[j, h] = P, x["T"], x["W"]
            new_ref[j, h], state_ref[j + 1, h], _ = _handed_on(x, P, dt)
        return carry

    jax.lax.fori_loop(0, n, again, 0)
    row, col = _at((C, C))
    _, lane = _at((C, 2 * n))

    def back(i, carry):
        j = n - 1 - i
        at = _rows_of(j, C)
        for kh, heads in _readers(qs_ref.shape[0], vs_ref.shape[0]):
            keys = _chunk_keys(qs_ref[kh, at, :], ks_ref[kh, at, :])
            q, k = keys["q"], keys["k"]
            # what the key head's value heads add to dq and dk: rows, and
            # what multiplies q and k (summed before the product)
            dq = dk = jnp.zeros(q.shape, jnp.float32)
            dqk = dkk = jnp.zeros((C, C), jnp.float32)
            for h in heads:
                x = _chunk_of(j, h, keys, *refs[2:])
                bc = x["bc"]
                P, T, W, new = (state_ref[j, h], T_ref[j, h], W_ref[j, h],
                                new_ref[j, h])
                Pb, Tb = P.astype(dt), T.astype(dt)
                dO, dP = dos_ref[h, at, :], dstate_ref[h]
                dPb = dP.astype(dt)
                # O = Qg P + M V',  P' = whole P + Ke^T V',  V' = U - W P
                dnew = _tn(x["Mf"].astype(dt), dO) + _nn(x["Ke"], dPb)
                dnewb = dnew.astype(dt)
                dMf = jnp.where(row >= col, _nt(dO, new), 0.0)
                dQg, dKe = _nt(dO, Pb), _nt(new, dPb)
                dW = (-_nt(dnewb, Pb)).astype(dt)
                dwhole = jnp.sum(P * dP, keepdims=True)
                dstate_ref[h] = (x["whole"] * dP + _tn(x["Qg"], dO)
                                 - _tn(W, dnewb))
                # W = T Kb, U = T Vb; dA = -T^T dT T^T under the diagonal
                dT = _nt(dW, x["Kb"]) + _nt(dnewb, x["Vb"])
                dKb, dVb = _tn(Tb, dW), _tn(Tb, dnewb)
                dA = jnp.where(row > col, -_tn(
                    Tb, _nt(dT.astype(dt), Tb).astype(dt)), 0.0)
                # A = beta (K K^T pair), M = Q K^T pair
                dkk = dkk + dA * bc * x["pair"]
                dqk = dqk + dMf * x["pair"]
                moved = dA * x["A"] + dMf * x["Mf"]      # d pair * pair
                along_k = (dKb * x["kf"]).sum(1, keepdims=True)
                dto_end = ((dKe * x["kf"]).sum(1, keepdims=True)
                           * x["to_end"])
                dq = dq + dQg * x["grown"]
                dk = dk + dKb * (bc * x["grown"]) + dKe * x["to_end"]
                dvs_ref[h, at, :] = (dVb * bc).astype(dvs_ref.dtype)
                dbeta = ((dA * x["kkd"]).sum(1, keepdims=True)
                         + along_k * x["grown"]
                         + (dVb * x["vf"]).sum(1, keepdims=True))
                # G: through exp(G), exp(G_end - G), exp(G_end), the pairs
                dGc = (((dQg * x["qf"]).sum(1, keepdims=True) + along_k * bc)
                       * x["grown"] - dto_end + moved.sum(1, keepdims=True))
                dGc = dGc + jnp.where(
                    row[:, :1] == C - 1,
                    dto_end.sum(0, keepdims=True) + dwhole * x["whole"], 0.0)
                dcols_ref[0, h, 0] = jnp.where(lane == j, dGc, jnp.where(
                    lane == n + j, dbeta, dcols_ref[0, h, 0]))
                drows_ref[0, h, 0, pl.ds(j, 1), :] = -moved.sum(
                    0, keepdims=True)
            dqk, dkk = dqk.astype(dt), dkk.astype(dt)
            dqs_ref[kh, at, :] = dq + _nn(dqk, k)
            dks_ref[kh, at, :] = (dk + _tn(dqk, q) + _nn(dkk, k)
                                  + _tn(dkk, k))
        return carry

    jax.lax.fori_loop(0, n, back, 0)
    _turned_out(dqs_ref, dq_ref, norm and (q_ref, q_norm))
    _turned_out(dks_ref, dk_ref, norm and (k_ref, k_norm))
    _turned_out(dvs_ref, dv_ref)


def _rule_specs(q, v, cols, dims):
    """What both calls share: the grid (batch row, block of key heads, step
    along the sequence), the operands' block shapes (``place(t)``: a step's
    place along the sequence, which the backward counts from the end) and
    the scratch a step turns its blocks into. ``dims``: (K, V)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, V = dims
    b, H, steps, C, n2 = cols.shape
    key_heads = q.shape[1] // K
    hb = _heads_a_block(H, key_heads)
    kb = hb * key_heads // H
    L = C * n2 // 2

    def specs(place):
        def seq(i, h, t):
            return (i, h, place(t))

        def step(i, h, t):
            return (i, h, place(t), 0, 0)

        return {"qk": pl.BlockSpec((1, kb * K, L), seq),
                "v": pl.BlockSpec((1, hb * V, L), seq),
                "cols": pl.BlockSpec((1, hb, 1, C, n2), step),
                "rows": pl.BlockSpec((1, hb, 1, n2 // 2, C), step),
                "state": pl.BlockSpec((1, hb, K, V),
                                      lambda i, h, t: (i, h, 0, 0)),
                "kept": pl.BlockSpec((1, hb, 1, K, V), step)}

    return {"grid": (b, H // hb, steps), "hb": hb, "chunks": n2 // 2,
            "specs": specs,
            "qk_rows": lambda dtype: pltpu.VMEM((kb, L, K), dtype),
            "v_rows": lambda dtype: pltpu.VMEM((hb, L, V), dtype)}


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=100 << 20)


def _rule_forward(q, k, v, cols, rows, dims, norm, keep, interpret):
    """q, k [b, key heads K, S], v [b, H V, S], cols [b, H, steps, C, 2 n],
    rows [b, H, steps, n, C] -> (o [b, H V, S], the last state transposed
    [b, H, K, V] float32, and with ``keep`` the state before every step [b,
    H, steps, K, V])."""
    import jax.experimental.pallas as pl

    K, V = dims
    b, H, steps = cols.shape[:3]
    at = _rule_specs(q, v, cols, dims)
    to = at["specs"](lambda t: t)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_rule_fwd_kernel, norm=norm),
        name="delta_rule_fwd",
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, H, K, V), f32)]
        + [jax.ShapeDtypeStruct((b, H, steps, K, V), f32)] * keep,
        grid=at["grid"],
        in_specs=[to["qk"], to["qk"], to["v"], to["cols"], to["rows"]],
        out_specs=[to["v"], to["state"]] + [to["kept"]] * keep,
        scratch_shapes=[at["qk_rows"](q.dtype), at["qk_rows"](q.dtype),
                        at["v_rows"](v.dtype), at["v_rows"](v.dtype)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(q, k, v, cols, rows)


def _rule_backward(q, k, v, cols, rows, kept, do, dlast, dims, norm,
                   interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, V = dims
    steps, C = cols.shape[2:4]
    at = _rule_specs(q, v, cols, dims)
    to = at["specs"](lambda t: steps - 1 - t)
    n, hb = at["chunks"], at["hb"]
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_rule_bwd_kernel, norm=norm),
        name="delta_rule_bwd",
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(cols.shape, f32),
                   jax.ShapeDtypeStruct(rows.shape, f32)],
        grid=at["grid"],
        in_specs=[to["qk"], to["qk"], to["v"], to["cols"], to["rows"],
                  to["kept"], to["v"], to["state"]],
        out_specs=[to["qk"], to["qk"], to["v"], to["cols"], to["rows"]],
        scratch_shapes=[pltpu.VMEM((n + 1, hb, K, V), f32),
                        pltpu.VMEM((n, hb, C, C), f32),
                        pltpu.VMEM((n, hb, C, K), v.dtype),
                        pltpu.VMEM((n, hb, C, V), v.dtype),
                        pltpu.VMEM((hb, K, V), f32),
                        at["qk_rows"](q.dtype), at["qk_rows"](q.dtype),
                        at["v_rows"](v.dtype), at["v_rows"](v.dtype),
                        at["qk_rows"](f32), at["qk_rows"](f32),
                        at["v_rows"](v.dtype)],
        compiler_params=_compiler_params(), interpret=interpret,
    )(q, k, v, cols, rows, kept, do, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule_calls(q, k, v, cols, rows, dims, norm, interpret):
    return tuple(_rule_forward(q, k, v, cols, rows, dims, norm, 0,
                               interpret))


def _rule_calls_fwd(q, k, v, cols, rows, dims, norm, interpret):
    o, last, kept = _rule_forward(q, k, v, cols, rows, dims, norm, 1,
                                  interpret)
    return (o, last), (q, k, v, cols, rows, kept)


def _rule_calls_bwd(dims, norm, interpret, res, cts):
    return tuple(_rule_backward(*res, *cts, dims, norm, interpret))


_rule_calls.defvjp(_rule_calls_fwd, _rule_calls_bwd)


def rule_kernels(q, k, v, g, beta, plan, norm=None,
                 interpret: bool = False):
    """The rule as two Mosaic calls, ``delta_rule_fwd`` and
    ``delta_rule_bwd`` behind a ``custom_vjp`` (``plan``: ``rule_plan``'s,
    of the form ``pallas``), on operands that lie as the taps' kernels
    leave them, positions last: q and k [b, key heads x K, s], v [b, H V,
    s], g and beta [b, s, H] float32 -> (o [b, H V, s] in ``v``'s dtype,
    the state after the last position [b, H, V, K] float32). ``norm``
    (``eps``, ``scale``): q and k come as the taps left them and the calls
    take their L2 norms in VMEM, forward and backward (q's times ``scale``);
    none: k is of unit length and q scaled already. Around the calls, in
    XLA: the padding to whole grid steps, the running sums of ``g`` down
    each chunk, and those and ``beta`` in the two layouts the kernels read
    (``_sums_laid``); nothing of q, k, v, o or their gradients. The
    forward carries the state in VMEM and, when a gradient is asked for,
    writes the state before every grid step (``states_kept``); the backward
    takes the steps last first, builds a step's chunks again from that
    state and carries the state's cotangent."""
    s = q.shape[-1]
    pad = plan["steps"] * plan["chunks_a_call"] * plan["chunk"] - s

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad))) if pad else a

    o, last = _rule_calls(padded(q), padded(k), padded(v),
                          *_sums_laid(g, beta, plan),
                          (plan["key_dim"], plan["value_dim"]), norm,
                          interpret)
    return o[..., :s], jnp.swapaxes(last, -1, -2)


def _sums_laid(g, beta, plan):
    """g and beta [b, s, H] -> what the calls read of them, float32 and
    padded to whole grid steps: ``cols`` [b, H, steps, C, 2 n] (a chunk's
    running sums of ``g``, then its beta, down the sublanes) and ``rows`` [b,
    H, steps, n, C] (the sums along the lanes)."""
    b, s, H = g.shape
    C, n, steps = plan["chunk"], plan["chunks_a_call"], plan["steps"]
    g, beta = (_padded(a.astype(jnp.float32), steps * n * C - s
                       ).reshape(b, steps, n, C, H) for a in (g, beta))
    G = _kernel_sums(g)
    return (jnp.transpose(jnp.concatenate([G, beta], axis=2),
                          (0, 4, 1, 3, 2)),
            jnp.transpose(G, (0, 4, 1, 2, 3)))


def _gates(a, b_, p, beta_scale: float = 2.0):
    """The in-projection's a and b [b, s, H] -> (g, the log of the decay:
    ``-exp(A_log) softplus(a + dt_bias)``, and ``beta = beta_scale
    sigmoid(b)``: 2, the two of ``linear_allow_neg_eigval``, or 1),
    float32."""
    f32 = jnp.float32
    return (-jnp.exp(p["g_A_log"].astype(f32)) * jax.nn.softplus(
        a.astype(f32) + p["g_dt_bias"].astype(f32)),
            beta_scale * jax.nn.sigmoid(b_.astype(f32)))


def _join_heads(x: jax.Array, heads: int) -> jax.Array:
    """x [b, s, key heads, K] -> [b, s, heads, K]: value head ``i`` reads
    key head ``i // (heads / key heads)``, neighbours sharing one."""
    return jnp.repeat(x, heads // x.shape[2], axis=2)


def rule_part(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array,
              b_: jax.Array, p: Dict[str, jax.Array], *, heads: int,
              key_heads: int, key_dim: int, value_dim: int, chunk: int = 64,
              mesh=None, beta_scale: float = 2.0
              ) -> Tuple[jax.Array, jax.Array]:
    """What the mixer runs under ``gdn_rule``: q and k [b, key heads x K, s]
    and v [b, H V, s] as the taps leave them, positions last, the
    in-projection's a and b [b, s, H] -> (o [b, s, H, V], the state after
    the last position). The kernels take q, k and v where they lie, q and k
    at their own heads, and norm them in VMEM; XLA's walk gets them swapped,
    normed (``l2_norm``) and, inside ``gated_delta_rule``, copied to the
    value heads. ``_gates``, ``rule_kernels`` and ``l2_norm`` are looked up
    at trace time."""
    b, _, s = v.shape
    g, beta = _gates(a, b_, p, beta_scale)
    plan = rule_plan(b, s, heads, key_dim, value_dim, chunk, mesh, key_heads)
    scale = key_dim ** -0.5
    if plan["form"] == "pallas":
        with tracing.span("rtpu.gdn.rule_plan", keep=True, **plan):
            pass
        o, S = rule_kernels(q, k, v, g, beta, plan,
                            norm=(QK_NORM_EPS, scale))
        return jnp.swapaxes(o, 1, 2).reshape(b, s, heads, value_dim), S
    q, k, v = (jnp.swapaxes(x, 1, 2).reshape(b, s, n, -1)
               for x, n in ((q, key_heads), (k, key_heads), (v, heads)))
    return gated_delta_rule(
        l2_norm(q, QK_NORM_EPS, scale), l2_norm(k, QK_NORM_EPS), v, g, beta,
        chunk=chunk, mesh=mesh)


def gated_delta_mixer(h: jax.Array, p: Dict[str, jax.Array], *, heads: int,
                      key_dim: int, value_dim: int, chunk: int = 64,
                      eps: float = 1e-6, mesh=None,
                      key_heads: Optional[int] = None,
                      beta_scale: float = 2.0
                      ) -> Tuple[jax.Array, jax.Array]:
    """h [b, s, hidden] -> (the mixer's output [b, s, hidden], the state
    after the last position [b, H, V, K] float32, which no gradient
    passes). ``p``: ``g_in [hidden, 2 H V + 2 H K + 2 H]`` (the gate z,
    then q k v, then a and b), ``g_conv [2 H K + H V, taps]`` (q's, k's and
    v's own taps, no bias), ``g_dt_bias`` and ``g_A_log`` ``[H]``,
    ``g_norm [V]``, ``g_out [H V, hidden]``. No projection has a bias.
    ``mesh``: the one the caller's arrays are sharded over, if any
    (``causal_conv_silu`` keeps XLA's form under one). ``key_heads``: q
    and k have that many heads (a divisor of ``heads``, the value heads';
    ``H K`` above is then theirs) and value head ``i`` reads key head ``i
    // (heads / key_heads)`` (Qwen3-Next: 16 under 32); q and k are normed
    at their own heads, and the rule reads them there as its kernels or
    copied to the value heads as XLA's walk (``rule_part``).
    ``beta_scale``: ``_gates``'."""
    b, s, _ = h.shape
    dt_ = h.dtype
    key_heads = key_heads or heads
    hk, hv = key_heads * key_dim, heads * value_dim
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
            # q k v taken where the taps left them: on the kernel path no
            # pass of XLA's over them stands between the taps and the
            # gated norm
            o, S = rule_part(q, k, v, a, b_, p, heads=heads,
                             key_heads=key_heads, key_dim=key_dim,
                             value_dim=value_dim, chunk=chunk, mesh=mesh,
                             beta_scale=beta_scale)
            S = jax.lax.stop_gradient(S)
        with jax.named_scope("gdn_norm"):
            y = gated_rms_norm(o, z.reshape(b, s, heads, value_dim),
                               p["g_norm"], eps).reshape(b, s, hv)
        with jax.named_scope("gdn_out"):
            out = jnp.dot(y, p["g_out"].astype(dt_),
                          preferred_element_type=f32).astype(dt_)
    return out, S


def gated_delta_part(counter: str = "gdn_state_abs_max", norm: str = "post",
                     key_heads: Optional[str] = None,
                     beta_scale: float = 2.0) -> Part:
    """The gated delta rule's mixer as a layer's mixer, in OLMo 2's order
    (``norm="post"``): ``x + RMSNorm(gated_delta_mixer(x))``, or llama's
    (``"pre"``: ``x + gated_delta_mixer(RMSNorm(x))``, the norm as the config's
    are, ``cfg.zero_centred_norm``), at the config's ``linear_heads``,
    ``linear_key_dim``, ``linear_value_dim``, ``linear_conv_taps`` and
    ``rule_chunk``. ``key_heads`` names the config's field where q and k have
    fewer heads than ``linear_heads``, the value heads'
    (``gated_delta_mixer``); ``beta_scale``: ``beta`` is that times
    ``sigmoid(b)``. A layer reports its state after the last position under
    "gdn_state", and the loss's terms the largest ``|S|`` of any layer under
    ``counter``. The initialisation is the delta-net's published one: ``A``
    uniform in 0-16 as its log, ``dt`` through the inverse softplus, norms
    1."""
    def leaves(cfg):
        h, H, taps = cfg.hidden_size, cfg.linear_heads, cfg.linear_conv_taps
        hv = H * cfg.linear_value_dim
        conv = 2 * q_heads(cfg) * cfg.linear_key_dim + hv
        first = ({"op_norm": Leaf((h,), norm_start(cfg), ("embed",))}
                 if norm == "pre" else {})
        last = ({"op_post_norm": Leaf((h,), "ones", ("embed",))}
                if norm == "post" else {})
        return {**first,
                "g_in": Leaf((h, hv + conv + 2 * H), h, ("embed", "mlp")),
                "g_conv": Leaf((conv, taps), taps, ("mlp", None)),
                "g_dt_bias": Leaf((H,), "dt", (None,)),
                "g_A_log": Leaf((H,), (0.0, 16.0), (None,)),
                "g_norm": Leaf((cfg.linear_value_dim,), "ones", (None,)),
                "g_out": Leaf((hv, h), hv, ("mlp", "embed")), **last}

    def q_heads(cfg):
        return getattr(cfg, key_heads) if key_heads else cfg.linear_heads

    def body(cfg, x, p, ctx):
        if norm == "pre":
            with jax.named_scope("gdn_pre_norm"):
                u = rms_norm(x, p["op_norm"], cfg.rms_norm_eps,
                             cfg.zero_centred_norm)
        else:
            u = x
        out, S = gated_delta_mixer(
            u, p, heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
            value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk,
            eps=cfg.rms_norm_eps, mesh=ctx.mesh, key_heads=q_heads(cfg),
            beta_scale=beta_scale)
        if norm == "post":
            out = rms_norm(out, p["op_post_norm"], cfg.rms_norm_eps)
        return x + out, {"gdn_state": S}

    def keeps(cfg, shape, tokens, mesh):
        # the in-projection's output (z, q k v, a b) and the taps' output
        # (their gradients lie where the SwiGLU's arrays did); beside them
        # what the rule's form puts in HBM (``rule_plan``). XLA's walk: one
        # step's pair matrices and carried states with their gradients, W,
        # U, V' and the decayed copies of q and k in both dtypes, the
        # state before every step, and q's and k's copies at the value
        # heads where fewer key heads serve them; held to the compiled step
        # at 32,768 tokens of a 3 : 1 stack at full remat: 1.4% over what
        # the compiler allots (PR 39). The kernels: the kept states and the
        # running sums alone; the calls read the taps' output where it lies,
        # q and k at their own heads, and nothing of their size is written
        # beside it: 1.9% over at 32,768 tokens (PERF.md 6, PR 53; PR 40's
        # head-major copies stood where the taps' output is counted)
        heads, hv = shape["g_A_log"][-1], shape["g_out"][0]
        key_dim = cfg.linear_key_dim
        plan = rule_plan(1, tokens, heads, key_dim, hv // heads,
                         cfg.rule_chunk, mesh, q_heads(cfg))
        if plan["form"] == "pallas":
            return kept(width=shape["g_in"][-1],
                        rows=plan["float32_bytes_in_hbm"])
        return kept(width=shape["g_in"][-1] + shape["g_conv"][0]
                    + 2 * (heads - q_heads(cfg)) * key_dim,
                    rows=4 * plan["float32_bytes_in_hbm"]
                    + plan["steps"] * hv * key_dim * 4)

    def terms(cfg, states):
        return None, {counter: jnp.abs(states).max()}

    return Part(leaves, body, keeps, reports="gdn_state", terms=terms)


# ---- Kimi Delta Attention (arXiv:2510.26692): the mixer of a layer whose
# rule has a decay a key channel (the module's docstring's second recurrence)


def _channel_gates(f, b_, p, lower_bound: float):
    """The in-projection's f [b, s, H, K] and b [b, s, H] -> (g, the log of
    the decay a channel: ``lower_bound sigmoid(exp(A_log[h]) (f +
    dt_bias))``, each entry in (``lower_bound``, 0): the lower-bounded gate
    that keeps a sub-block's ``exp(-G)`` inside float32; ``beta =
    sigmoid(b)``), float32. Looked up at trace time: the controls of
    ``benchmark/tests/kda_moe_limits.py`` plant their gates here."""
    f32 = jnp.float32
    H, K = f.shape[-2:]
    rate = jnp.exp(p["k_A_log"].astype(f32))[:, None]
    return (lower_bound * jax.nn.sigmoid(rate * (
        f.astype(f32) + p["k_dt_bias"].astype(f32).reshape(H, K))),
            jax.nn.sigmoid(b_.astype(f32)))


def _head_gated(o, gate, weight, eps):
    """o [b, s, H, V], gate [b, s, H] -> ``RMSNorm(o; weight) *
    sigmoid(gate)``, a head normed alone over its V and gated by one
    number, float32 inside. Looked up at trace time (a control leaves the
    gate off)."""
    of = o.astype(jnp.float32)
    normed = of * jax.lax.rsqrt(
        jnp.mean(jnp.square(of), axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
            ).astype(o.dtype)


def kda_mixer(h: jax.Array, p: Dict[str, jax.Array], *, heads: int,
              key_dim: int, value_dim: int, chunk: int = 64,
              eps: float = 1e-6, lower_bound: float = -5.0, mesh=None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Kimi Delta Attention's mixer: h [b, s, hidden] (normed) -> (its
    output [b, s, hidden], {"state": the state after the last position [b,
    H, V, K] float32, "log_decay_min": the smallest ``g``}, which no
    gradient passes). ``p``: ``k_in [hidden, 2 H K + H V + H K + 2 H]``
    (q k v, then the decay's f, then beta's b and the output gate's, one
    number a head each), ``k_conv [2 H K + H V, taps]`` (q's, k's and v's
    own taps, no bias), ``k_A_log [H]``, ``k_dt_bias [H K]``, ``k_norm
    [V]``, ``k_out [H V, hidden]``. Every head has q, k and v of its own;
    q and k are L2-normed after the taps' silu, v is not; ``beta =
    sigmoid(b)``; the output is ``RMSNorm_head(o) sigmoid(gate_h)`` through
    ``k_out``. Scopes: ``kda`` holds ``kda_in``, ``kda_conv``, ``kda_gate``
    (the bounded gate and beta), ``kda_rule`` (the swaps, the L2 norms and
    the walk, its running sums with it), ``kda_norm`` and ``kda_out``."""
    b, s, _ = h.shape
    dt_ = h.dtype
    hk, hv = heads * key_dim, heads * value_dim
    conv = 2 * hk + hv
    f32 = jnp.float32
    with jax.named_scope("kda"):
        with jax.named_scope("kda_in"):
            proj = jnp.dot(h, p["k_in"].astype(dt_),
                           preferred_element_type=f32).astype(dt_)
            # positions last, as the in-projection's output lies on a TPU
            by_channel = jnp.swapaxes(proj, 1, 2)
        with jax.named_scope("kda_conv"):
            q, k, v = causal_conv_silu(
                by_channel, p["k_conv"], jnp.zeros((conv,), f32),
                sizes=(hk, hk, hv), mesh=mesh, span="rtpu.kda.conv_plan")
        with jax.named_scope("kda_gate"):
            b_, gate = jnp.split(proj[..., conv + hk:], 2, axis=-1)
            g, beta = _channel_gates(
                proj[..., conv:conv + hk].reshape(b, s, heads, key_dim), b_,
                p, lower_bound)
        with jax.named_scope("kda_rule"):
            q, k, v = (jnp.swapaxes(x, 1, 2).reshape(b, s, heads, -1)
                       for x in (q, k, v))
            o, S = gated_delta_rule(
                l2_norm(q, QK_NORM_EPS, key_dim ** -0.5),
                l2_norm(k, QK_NORM_EPS), v, g, beta, chunk=chunk, mesh=mesh,
                span="rtpu.kda.rule_plan", lower_bound=lower_bound)
            said = jax.lax.stop_gradient(
                {"state": S, "log_decay_min": g.min()})
        with jax.named_scope("kda_norm"):
            y = _head_gated(o, gate, p["k_norm"], eps).reshape(b, s, hv)
        with jax.named_scope("kda_out"):
            out = jnp.dot(y, p["k_out"].astype(dt_),
                          preferred_element_type=f32).astype(dt_)
    return out, said


def kda_part() -> Part:
    """Kimi Delta Attention as a layer's mixer in llama's order, ``x +
    kda_mixer(RMSNorm(x))``, at the config's ``linear_heads``,
    ``linear_key_dim``, ``linear_value_dim``, ``linear_conv_taps``,
    ``rule_chunk`` and ``kda_lower_bound``. A layer reports under "kda" its
    state after the last position and its smallest ``g``; the loss's terms
    hold the counters ``kda_state_abs_max`` and ``kda_log_decay_min`` (which
    must stay above the bound). ``A`` starts uniform in 1-16 as its log,
    ``dt_bias`` through the inverse softplus, norms 1."""
    def leaves(cfg):
        h, H, taps = cfg.hidden_size, cfg.linear_heads, cfg.linear_conv_taps
        hk, hv = H * cfg.linear_key_dim, H * cfg.linear_value_dim
        conv = 2 * hk + hv
        return {"op_norm": Leaf((h,), norm_start(cfg), ("embed",)),
                "k_in": Leaf((h, conv + hk + 2 * H), h, ("embed", "mlp")),
                "k_conv": Leaf((conv, taps), taps, ("mlp", None)),
                "k_dt_bias": Leaf((hk,), "dt", (None,)),
                "k_A_log": Leaf((H,), (1.0, 16.0), (None,)),
                "k_norm": Leaf((cfg.linear_value_dim,), "ones", (None,)),
                "k_out": Leaf((hv, h), hv, ("mlp", "embed"))}

    def body(cfg, x, p, ctx):
        with jax.named_scope("kda_pre_norm"):
            u = rms_norm(x, p["op_norm"], cfg.rms_norm_eps,
                         cfg.zero_centred_norm)
        out, said = kda_mixer(
            u, p, heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
            value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk,
            eps=cfg.rms_norm_eps, lower_bound=cfg.kda_lower_bound,
            mesh=ctx.mesh)
        return x + out, {"kda": said}

    def keeps(cfg, shape, tokens, mesh):
        # as ``gated_delta_part``'s walk: the in-projection's and the taps'
        # outputs, the float32 gate and its gradient (four activations'
        # widths a key channel), q and k normed, and what a step of the
        # walk puts in HBM with the state before every step
        heads, hv = shape["k_A_log"][-1], shape["k_out"][0]
        hk = shape["k_dt_bias"][-1]
        plan = rule_plan(1, tokens, heads, hk // heads, hv // heads,
                         cfg.rule_chunk, mesh, heads, decay="channel")
        return kept(width=shape["k_in"][-1] + shape["k_conv"][0] + 6 * hk,
                    rows=4 * plan["float32_bytes_in_hbm"]
                    + plan["steps"] * hv * hk // heads * 4)

    def terms(cfg, said):
        return None, {"kda_state_abs_max": jnp.abs(said["state"]).max(),
                      "kda_log_decay_min": said["log_decay_min"].min()}

    return Part(leaves, body, keeps, reports="kda", terms=terms)
