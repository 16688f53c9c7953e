"""Plain reference of DeepSeek-V2 (deepseek-ai/DeepSeek-V2): float32,
``jax.numpy`` only, matmuls at ``highest`` precision, no kernel, no sort,
no grouped matmul, attention as a masked softmax over every key in blocks
of queries, the keys built whole (the shared rotated part copied to every
head).

The equations, from the model's ``config.json`` (what its keys do not
settle is listed under ``assumed`` in ``benchmark/configs/
deepseek-v2-c1.json``). ``u = RMSNorm(x)``, eps 1e-6, pre-norm, a final
norm before the untied head; ``H`` heads held of ``num_heads``, ``d_n`` =
128, ``d_r`` = 64, ``d_v`` = 128.

- Attention: ``c_q = RMSNorm(u W_qa)`` [1536]; ``q = c_q W_qb`` [H, 192],
  each head ``q_n [128] | q_r [64]``. ``[c_kv | k_r] = u W_kva`` [512 | 64];
  ``c_kv = RMSNorm(c_kv)``; ``[k_n | v] = c_kv W_kvb`` [H, 128 | 128]. Rope
  on ``q_r`` and on the one ``k_r`` of a position: the 64 dims
  de-interleaved (``x[0::2] | x[1::2]``), then rotated as two halves,
  theta 10,000 under yarn (factor 40 from 4,096, beta 32 / 1; mscale =
  mscale_all_dim, so the tables' factor is 1). ``A_h = softmax(([q_n | q_r]
  . [k_n | k_r]) s + mask) v``, ``s = 192 ** -0.5 * m ** 2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; ``y = x + concat_h(A_h) W_o``.
- MLP: a dense layer ``y + SwiGLU(RMSNorm(y))``; a routed one, with ``u =
  RMSNorm(y)``: ``s = softmax(u W_r)`` over all experts in float32; the
  experts as ``n_group`` groups of neighbours, a group's score its largest,
  the ``topk_group`` best groups kept, ``T`` the ``top_k`` largest scores
  inside them, ``w_e = scale * s_e`` (not renormalised); ``out = y +
  SwiGLU_shared(u) + sum over e in T that are held of w_e SwiGLU_e(u)``: a
  loop over the held experts, each over every token under a mask.
- Loss = cross entropy + ``router_aux_coef`` x, summed over the routed
  layers, the mean over the sequences of ``sum_e f_e P_e``: ``f_e`` the
  times the sequence chose ``e`` x E / (K x length), ``P_e`` its mean score.

``forced_topk`` ([routed layers, tokens, K] expert ids) replaces the
reference's own choice of experts by the program's, the gate weights
still the reference's own scores (``olmoe_ref.py`` says why).

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields; the norm, the SwiGLU, yarn's
frequencies and the walk over a batch's rows are ``laguna_ref.py``'s, by
import. ``grad_weights`` asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` with respect to ``first_layers(params)``: the
embedding, the last norm, the head and the first layer of each kind
(layer 0, dense, and layer 1, routed), as ``laguna_ref.py`` does and under
the same ``jax.checkpoint``s.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

from benchmark.references.laguna_ref import (_rms_norm, _rows,  # noqa: F401
                                              _swiglu, _yarn_inv_freq,
                                              first_layers)

Q_BLOCK = 256


def _sizes(cfg) -> Dict[str, Any]:
    return {"heads": cfg.num_heads,
            "d_n": cfg.qk_nope_head_dim, "d_r": cfg.qk_rope_head_dim,
            "d_v": cfg.v_head_dim, "kv_rank": cfg.kv_lora_rank,
            "eps": cfg.rms_norm_eps, "theta": cfg.rope_theta,
            "yarn": tuple(cfg.rope_scaling), "pattern": tuple(cfg.pattern),
            "top_k": cfg.top_k, "scale": cfg.routed_scale,
            "groups": (cfg.n_group, cfg.topk_group),
            "held": tuple(cfg.experts_held or (0, cfg.num_experts)),
            "aux_coef": cfg.router_aux_coef}


def softmax_scale(sz) -> float:
    yarn = dict(sz["yarn"])
    m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
    return (sz["d_n"] + sz["d_r"]) ** -0.5 * m * m


def _rope(x, inv_freq):
    """x [T, heads, d_r], positions 0..T-1: de-interleaved, then rotated
    as two halves."""
    import jax.numpy as jnp

    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_heads(x, p, sz):
    """x [T, h], one layer's attention weights -> the heads' outputs
    [T, H, d_v] before ``W_o``."""
    import jax
    import jax.numpy as jnp

    T, H = x.shape[0], sz["heads"]
    dn, dr, dv, rank = sz["d_n"], sz["d_r"], sz["d_v"], sz["kv_rank"]
    u = _rms_norm(x, p["attn_norm"], sz["eps"])
    q = (_rms_norm(u @ p["wq_a"], p["q_a_norm"], sz["eps"])
         @ p["wq_b"]).reshape(T, H, dn + dr)
    ckv = u @ p["wkv_a"]
    kv = (_rms_norm(ckv[:, :rank], p["kv_a_norm"], sz["eps"])
          @ p["wkv_b"]).reshape(T, H, dn + dv)
    inv = _yarn_inv_freq(dr, sz["theta"], dict(sz["yarn"]))
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv)], -1)
    k_r = _rope(ckv[:, None, rank:], inv)                    # [T, 1, d_r]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], -1)
    v = kv[..., dn:]
    scale = softmax_scale(sz)

    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                              # [qb, H, 192], [qb, 1]
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        sc = jnp.where((j <= i)[None], sc, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, dn + dr),
        jnp.arange(T).reshape(T // qb, qb, 1)))
    return outs.reshape(T, H, dv)


def group_limited_top_k(scores, sz):
    """scores [T, E] -> the chosen experts [T, K]: the ``top_k`` largest
    inside the ``topk_group`` groups whose best score is largest."""
    import jax
    import jax.numpy as jnp

    n_group, keep = sz["groups"]
    by_group = scores.reshape(scores.shape[0], n_group, -1)
    _, best = jax.lax.top_k(by_group.max(-1), keep)          # [T, keep]
    kept = (best[:, :, None] == jnp.arange(n_group)[None, None]).any(1)
    inside = jnp.where(kept[:, :, None], by_group, 0.0)
    return jax.lax.top_k(inside.reshape(scores.shape), sz["top_k"])[1]


def routed_mlp(u, p, sz, forced=None):
    """u [T, h] float32, the normed input of a routed layer -> (shared
    experts + the held experts' part [T, h], router logits [T, E], chosen
    experts [T, K]). ``p``'s expert weights are the held experts'."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logits = u @ p["router"].astype(f32)
    scores = jax.nn.softmax(logits, axis=-1)
    chosen = group_limited_top_k(scores, sz) if forced is None else forced
    gates = sz["scale"] * jnp.take_along_axis(scores, chosen, axis=-1)
    first, count = sz["held"]

    @jax.checkpoint
    def weighted(u, gate, w_gate, w_up, w_down):
        return gate[:, None] * _swiglu(
            u, w_gate.astype(f32), w_up.astype(f32), w_down.astype(f32))

    def one_expert(acc, ew):
        idx, w_gate, w_up, w_down = ew
        gate = jnp.where(chosen == idx, gates, 0.0).sum(-1)        # [T]
        return acc + weighted(u, gate, w_gate, w_up, w_down), None

    out = _swiglu(u, p["s_gate"].astype(f32), p["s_up"].astype(f32),
                  p["s_down"].astype(f32))
    out, _ = jax.lax.scan(
        one_expert, out,
        (first + jnp.arange(count), p["e_gate"], p["e_up"], p["e_down"]))
    return out, logits, chosen


def _layer(x, p, forced, *, sz, kind: str):
    """x [T, h] float32; p: one layer's weights (any float dtype). ->
    (x, router logits [T, E] and chosen experts [T, K], or None twice)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    big = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down", "router")
    small = {k: v.astype(f32) for k, v in p.items() if k not in big}
    heads = attention_heads(x, small, sz)
    x = x + heads.reshape(x.shape[0], -1) @ small["wo"]
    u = _rms_norm(x, small["mlp_norm"], sz["eps"])
    if not kind.endswith("_moe"):
        return x + _swiglu(u, small["w_gate"], small["w_up"],
                           small["w_down"]), None, None
    out, logits, chosen = routed_mlp(u, p, sz, forced)
    return x + out, logits, chosen


def _run(sz, params, tokens, forced, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm
    [T, h], router logits [Lr, T, E], chosen experts [Lr, T, K]).
    ``first`` (``first_layers(params)``) stands in for the weights it
    holds: what a gradient is taken with respect to."""
    import jax
    import jax.numpy as jnp

    x = (first or params)["embed"][tokens].astype(jnp.float32)
    taken = dict.fromkeys(params["layers"], 0)
    logits, chosen = [], []
    for kind in sz["pattern"]:
        at = taken[kind]
        taken[kind] += 1
        x, lg, ch = jax.checkpoint(partial(_layer, sz=sz, kind=kind))(
            x, first["layers"][kind] if first and at == 0
            else {k: v[at] for k, v in params["layers"][kind].items()},
            forced=None if forced is None or not kind.endswith("_moe")
            else forced[len(logits)])
        if lg is not None:
            logits.append(lg)
            chosen.append(ch)
    return x, jnp.stack(logits), jnp.stack(chosen)


def _head(x, params, sz):
    import jax.numpy as jnp

    x = _rms_norm(x, params["final_norm"].astype(jnp.float32), sz["eps"])
    return x @ params["lm_head"].astype(jnp.float32)


def _nll(sz, params, row, forced, first=None):
    """row [S + 1] -> (next-token loss [S], router logits, chosen)."""
    import jax
    import jax.numpy as jnp

    x, logits, chosen = _run(sz, params, row[:-1], forced, first)
    lg = _head(x, first or params, sz)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, row[1:, None], -1)[:, 0]
    return nll, logits, chosen


def _balance(logits, chosen, B: int):
    """The sequence-level balancing term of router logits [Lr, B * S, E]
    and chosen experts [Lr, B * S, K]: the layers' sum of the sequences'
    mean of ``sum_e f_e P_e``."""
    import jax

    Lr, _, E = logits.shape
    counts = jax.nn.one_hot(chosen.reshape(Lr, B, -1), E).sum(2)  # [Lr,B,E]
    share = counts * E / counts.sum(-1, keepdims=True)
    prob = jax.nn.softmax(logits, -1).reshape(Lr, B, -1, E).mean(2)
    return (share * prob).sum(-1).mean(-1).sum()


def _terms(sz, nll, logits, chosen):
    """The loss and its two terms from per-position losses [B, S], router
    logits [Lr, B * S, E] and chosen experts [Lr, B * S, K]."""
    balance = _balance(logits, chosen, nll.shape[0])
    ce = nll.mean()
    return {"cross_entropy": ce, "load_balance": balance,
            "loss": ce + sz["aux_coef"] * balance}


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape (``laguna_ref.py``)."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, f, w, aux):
        nll, logits, chosen = _nll(sz, p, row, f, first)
        return ((w * nll).sum() + aux * _balance(logits, chosen, 1),
                (nll, logits, chosen))

    def nll_and_grad(p, row, f, w, aux):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, w, aux)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, grad_weights=None,
              router_term: bool = False) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy arrays ``nll [B, S]``, ``router_logits
    [Lr, B * S, E]``, ``chosen [Lr, B * S, K]``, and the loss ``terms``
    (floats) computed from them; with ``grad_weights [B, S]`` also
    ``grads``, the gradient of ``sum(grad_weights * nll)`` with respect
    to ``first_layers(params)``; with ``router_term`` of that sum plus
    ``router_aux_coef`` x the balancing term (a sequence's term is its own
    row's, so the rows' gradients still add up), which with weights of
    ``1 / (B S)`` is the train step's loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = _sizes(cfg)
    fn = _jitted_nll(sz, grad_weights is not None)
    if forced_topk is not None:
        forced_topk = jnp.asarray(forced_topk, jnp.int32)
    tokens = jnp.asarray(tokens, jnp.int32)
    grads = []           # the sum of the rows' gradients so far

    def one_row(b, row, f):
        if grad_weights is None:
            return fn(params, row, f)
        *out, g = fn(params, row, f,
                     jnp.asarray(grad_weights[b], jnp.float32),
                     sz["aux_coef"] / tokens.shape[0] * router_term)
        grads[:] = [g if not grads
                    else jax.tree_util.tree_map(jnp.add, grads[0], g)]
        return out

    with jax.default_matmul_precision("highest"):
        nll, logits, chosen = _rows(one_row, tokens, forced_topk)
        terms = _terms(sz, nll, logits, chosen)
    out = {"nll": np.asarray(nll), "router_logits": np.asarray(logits),
           "chosen": np.asarray(chosen),
           "terms": {k: float(v) for k, v in terms.items()}}
    if grad_weights is not None:
        out["grads"] = grads[0]
    return out


def logits(cfg, params, tokens):
    """tokens [B, S] -> logits [B, S, V] float32 (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head(_run(sz, params, jnp.asarray(row, jnp.int32), None)[0],
                  params, sz) for row in tokens])


def loss(cfg, params, tokens, forced_topk: Optional[Any] = None):
    """The whole loss as one differentiable function of ``params`` (CPU
    sizes). The gradient flows through the gate weights and the router's
    scores, not through the choice of experts."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        nll, lg, chosen = _rows(
            lambda b, row, f: _nll(sz, params, row, f),
            jnp.asarray(tokens, jnp.int32), forced_topk)
        return _terms(sz, nll, lg, chosen)["loss"]


def attention_layer(cfg, p, x):
    """One layer's attention on x [T, h] (CPU sizes): what the heads
    ``cfg`` holds add to the residual stream, ``concat_h(A_h) W_o``."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
        x = jnp.asarray(x, jnp.float32)
        return attention_heads(x, p, sz).reshape(x.shape[0], -1) @ p["wo"]


def routed_layer(cfg, p, u, shared: bool = True):
    """One routed layer's MLP on its normed input u [T, h] (CPU sizes):
    the shared experts (``shared``) and the part of the experts ``cfg``
    holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        u = jnp.asarray(u, jnp.float32)
        out = routed_mlp(u, p, _sizes(cfg))[0]
        if not shared:
            out = out - _swiglu(u, *(p[k].astype(jnp.float32)
                                     for k in ("s_gate", "s_up", "s_down")))
        return out
