"""The walk of ``ops/dsa.py`` as it stood before it had a rule of its own
(PR 58): a ``lax.map`` over ``jax.checkpoint``ed blocks of XLA's forms
(``plain_scores``, ``choose``, ``plain_attend`` / ``plain_attend_grouped``,
``kl_target``), differentiated by jax, every block's forward run again in
its backward. The tests' yardstick for the walk's outputs and gradients,
and a reader of the Mosaic calls a jaxpr holds."""

import collections

import jax
import jax.numpy as jnp

from ray_tpu.ops import dsa


def _one_sequence(q, k_n, v, k_r, q_i, k_i, w, *, scale, topk, block, tiers):
    s = q.shape[0]
    per_tier = s // block // tiers
    ends = [(g + 1) * per_tier * block for g in range(tiers)]

    def by_block(x):
        return x.reshape((tiers, per_tier, block) + x.shape[1:])

    def one_block(end, args):
        q_b, qi_b, w_b, first = args
        index = dsa.plain_scores(qi_b, k_i[:end], w_b)
        chosen = dsa.choose(jax.lax.stop_gradient(index), first, topk)
        if k_r is None:
            out, p = dsa.plain_attend_grouped(q_b, k_n[:end], v[:end],
                                              chosen, scale)
        else:
            out, p = dsa.plain_attend(q_b, k_n[:end], v[:end], k_r[:end],
                                      chosen, scale)
        target = dsa.kl_target(p)
        log_q = jax.nn.log_softmax(jnp.where(chosen, index, -1e30), -1)
        kl = jnp.where(
            target > 0,
            target * (jnp.log(jnp.where(target > 0, target, 1.0)) - log_q),
            0.0).sum()
        return (out, kl, chosen.sum(dtype=jnp.int32), jnp.packbits(
            jnp.pad(chosen, ((0, 0), (0, s - end))), axis=-1))

    firsts = (jnp.arange(s // block, dtype=jnp.int32) * block
              ).reshape(tiers, per_tier)
    parts = [jax.lax.map(
        jax.checkpoint(lambda a, end=end: one_block(end, a)),
        (by_block(q)[g], by_block(q_i)[g], by_block(w)[g], firsts[g]))
        for g, end in enumerate(ends)]
    out, kl, pairs, choice = (jnp.concatenate(xs) for xs in zip(*parts))
    return (out.reshape((s,) + out.shape[2:]), kl.sum(), pairs.sum(),
            choice.reshape(s, -1))


def checkpointed_walk(q, k_n, v, k_r, q_i, k_i, w, **how):
    """``dsa.sparse_attention(.., keep_choice=True)``'s arguments and
    outputs, ``k_r`` None under grouped keys."""
    args = tuple(x for x in (q, k_n, v, k_r, q_i, k_i, w) if x is not None)

    def rows(*a):
        if k_r is None:
            a = a[:3] + (None,) + a[3:]
        return _one_sequence(*a, **how)

    return jax.vmap(rows)(*args)


def mosaic_calls(jaxpr) -> collections.Counter:
    """How often each ``pallas_call`` stands in a jaxpr, by the call's
    name, whatever it is nested in."""
    seen = collections.Counter()

    def walk(j):
        for eqn in getattr(j, "jaxpr", j).eqns:
            if eqn.primitive.name == "pallas_call":
                seen[eqn.params["name"]] += 1
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                        value,):
                    if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                        walk(sub)

    walk(jaxpr)
    return seen


def clear_call_caches():
    """Forget the traces of the walk's six cached builders, so that a test
    counts the traces of its own shapes."""
    for name in ("_scores_forward", "_scores_backward", "_attend_forward",
                 "_attend_backward", "_grouped_forward",
                 "_grouped_backward"):
        getattr(dsa, name).clear_cache()
