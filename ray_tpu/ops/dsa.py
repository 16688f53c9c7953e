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

``sparse_attention`` walks blocks of ``block`` queries under XLA
(``lax.map`` over a ``jax.checkpoint``ed block): a block's index scores
``[block, S]`` float32, its choice (``choose``: an exact radix select of
the ``topk``-th largest score, 32 compare-and-count passes, no sort) and
its masked softmax over all heads live for that block alone, so the ``[T,
T]`` scores never exist whole and neither does a gather of the chosen
latents. The blocks are walked in ``tiers`` of equal length, a tier's
blocks against the keys up to the tier's end: four tiers skip three
eighths of the pairs the causal mask drops. The walk costs the dense
causal attention's FLOPs whatever the choice keeps (the mask zeroes what
is not chosen); a kernel that visits the chosen keys alone is a later
change and is read by the same yardstick (needed work = the chosen pairs).

Named scopes: ``dsa_scores`` (the index's scores), ``dsa_select`` (the
choice), ``flash_sparse`` (scores, masked softmax and PV of the attention
over the choice), ``dsa_loss`` (the index's term). One kept span as the op
is traced, ``rtpu.dsa.shapes``. Training only.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.util import tracing

_NEG = -1e30


def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array) -> jax.Array:
    """q_i [n, J, d], k_i [S, d], w [n, J] float32 -> ``I [n, S]`` float32:
    ``sum_j w[., j] ReLU(q_i[., j] . k_i)``, the products accumulated in
    float32. No mask: a caller drops the pairs its queries do not see."""
    x = jnp.einsum("njd,sd->njs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(x) * w.astype(jnp.float32)[:, :, None]).sum(1)


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
            index = index_scores(qi_b, ki_t, w_b)             # [block, S']
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
