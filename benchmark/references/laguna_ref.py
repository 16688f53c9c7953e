"""Plain reference of Laguna (poolside/Laguna-S-2.1): float32,
``jax.numpy`` only, matmuls at ``highest`` precision, no kernel, no sort,
no grouped matmul, attention as a masked softmax over every key in
blocks of queries.

The equations, from the model's ``config.json`` (what its keys do not
settle is listed under ``assumed`` in ``benchmark/configs/
laguna-s-2.1-c1.json``). ``u = RMSNorm(x)``, eps 1e-6, pre-norm, a final
norm before the untied head.

- Attention, layer ``l`` with ``H_l`` query heads (48 in a full layer, 72
  in a sliding one), 8 kv heads, d = 128, no bias: ``q = u W_q``, ``k = u
  W_k``, ``v = u W_v``, ``g = sigmoid(u W_g)`` (one number a head). Rope
  on q and k, half-split rotation: a sliding layer rotates all 128 dims
  with theta 10,000; a full layer rotates the first 64 with theta 500,000
  and yarn (factor 128 from 8,192 positions, beta 32 / 1, cos and sin
  times the attention factor) and passes the other 64. ``A_h = softmax(q_h
  k_{h // (H_l / 8)}^T / sqrt(128) + mask) v``, mask ``j <= i`` (full) or
  ``i - window < j <= i`` (sliding). ``y = x + concat_h(g_h A_h) W_o``.
- MLP: a dense layer ``y + SwiGLU(RMSNorm(y))``; a routed one, with ``u =
  RMSNorm(y)``: ``s = softmax(u W_r)`` over all experts in float32, ``T``
  the ``top_k`` largest, ``w_e = scale * s_e / sum_T s``, ``out = y +
  SwiGLU_shared(u) + sum over e in T that are held of w_e SwiGLU_e(u)``.
  Every held expert runs over every token and a mask keeps the chosen
  ones: a loop over the experts. An expert that is not held adds nothing.
- Loss = cross entropy + ``router_aux_coef`` x transformers'
  ``load_balancing_loss_func`` over all experts and all routed layers.

``forced_topk`` ([routed layers, tokens, K] expert ids) replaces the
reference's own choice of experts by the program's, the gate weights
still the reference's own probabilities (``olmoe_ref.py`` says why).

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields: ``params["layers"][kind][name]``
stacked over the layers of a kind (``full_dense``, ``sliding_moe``,
``full_moe`` ...), ``cfg.pattern`` the kind of each layer. Weights are
upcast one layer, and inside it one expert, at a time.

``grad_weights`` ([B, S] float32) asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` as well, with respect to the embedding, the
last norm, the head and the first layer of each kind (``first_layers``:
every kind of backward pass runs, through every later layer, at a third
of the memory all layers' gradients would take), one row at a time: the
same functions differentiated by ``jax.grad``, float32 inside, each leaf
rounded once to its parameter's dtype where the upcast is transposed and
the rows' gradients added there. So that 8,192 positions fit, a layer, a
block of queries and an expert are each under ``jax.checkpoint`` (their
forward is computed again in the backward pass; no value changes).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

Q_BLOCK = 256


def _sizes(cfg) -> Dict[str, Any]:
    held = cfg.experts_held or (0, cfg.num_experts)
    return {"heads": (cfg.num_heads, cfg.num_heads_sliding),
            "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "eps": cfg.rms_norm_eps, "window": cfg.sliding_window,
            "theta": (cfg.rope_theta, cfg.rope_theta_sliding),
            "rotated": int(cfg.head_dim * cfg.partial_rotary_factor),
            "yarn": tuple(cfg.rope_scaling), "pattern": tuple(cfg.pattern),
            "top_k": cfg.top_k, "scale": cfg.routed_scale,
            "held": tuple(held), "aux_coef": cfg.router_aux_coef}


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _yarn_inv_freq(dim: int, theta: float, yarn: Dict[str, Any]):
    """transformers' ``_compute_yarn_parameters`` for ``dim`` rotated
    dims: low frequencies divided by ``factor``, high ones kept, a linear
    ramp between the two correction dims."""
    import jax.numpy as jnp

    factor, old = yarn["factor"], yarn["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(old / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)


def _rope(x, inv_freq, factor: float):
    """x [T, H, hd], positions 0..T-1: the first ``2 * len(inv_freq)``
    dims rotated as two halves, the rest passed."""
    import jax.numpy as jnp

    rot = 2 * inv_freq.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang) * factor)[:, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attention(x, p, sz, sliding: bool):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    T = x.shape[0]
    H, KVH, hd = sz["heads"][sliding], sz["kv_heads"], sz["head_dim"]
    u = _rms_norm(x, p["attn_norm"], sz["eps"])
    q = (u @ p["wq"]).reshape(T, H, hd)
    k = (u @ p["wk"]).reshape(T, KVH, hd)
    v = (u @ p["wv"]).reshape(T, KVH, hd)
    gate = jax.nn.sigmoid(u @ p["wg"])                           # [T, H]
    if sliding:
        inv = 1.0 / (sz["theta"][1] ** (
            jnp.arange(0, hd, 2, dtype=f32) / hd))
        factor = 1.0
    else:
        yarn = dict(sz["yarn"])
        inv = _yarn_inv_freq(sz["rotated"], sz["theta"][0], yarn)
        factor = yarn["attention_factor"]
    q, k = _rope(q, inv, factor), _rope(k, inv, factor)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)

    # blocks of queries, each against every key: memory. A sequence that
    # is not whole blocks (CPU sizes) is one block.
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                              # [qb, H, hd], [qb, 1]
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) / jnp.sqrt(f32(hd))
        mask = (j <= i) & (i - j < sz["window"]) if sliding else j <= i
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, hd), jnp.arange(T).reshape(T // qb, qb, 1)))
    attn = outs.reshape(T, H, hd) * gate[:, :, None]
    return x + attn.reshape(T, H * hd) @ p["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def routed_mlp(u, p, sz, forced=None):
    """u [T, h] float32, the normed input of a routed layer -> (shared
    expert + the held experts' part [T, h], router logits [T, E], chosen
    experts [T, K]). ``p``'s expert weights are the held experts'."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logits = u @ p["router"].astype(f32)
    probs = jax.nn.softmax(logits, axis=-1)
    if forced is None:
        gates, chosen = jax.lax.top_k(probs, sz["top_k"])
    else:
        chosen = forced
        gates = jnp.take_along_axis(probs, chosen, axis=-1)
    gates = sz["scale"] * gates / gates.sum(-1, keepdims=True)
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
    sliding, routed = kind.startswith("sliding"), kind.endswith("_moe")
    big = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down", "router")
    small = {k: v.astype(f32) for k, v in p.items() if k not in big}
    x = _attention(x, small, sz, sliding)
    u = _rms_norm(x, small["mlp_norm"], sz["eps"])
    if not routed:
        return x + _swiglu(u, small["w_gate"], small["w_up"],
                           small["w_down"]), None, None
    out, logits, chosen = routed_mlp(u, p, sz, forced)
    return x + out, logits, chosen


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, the head, and the first layer of each kind
    (its leaves without the stack's axis)."""
    return {**tree, "layers": {kind: {k: v[0] for k, v in leaves.items()}
                               for kind, leaves in tree["layers"].items()}}


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


def _terms(sz, nll, logits, chosen):
    """The loss and its two terms from per-position losses [B, S], router
    logits [Lr, n, E] and chosen experts [Lr, n, K]."""
    import jax
    import jax.numpy as jnp

    E = logits.shape[-1]
    flat = logits.reshape(-1, E)
    one_hot = jax.nn.one_hot(chosen.reshape(-1, sz["top_k"]), E)
    share = one_hot.mean(0).sum(0)          # [E]: sums to K over experts
    prob = jax.nn.softmax(flat, -1).mean(0)
    balance = E * jnp.sum(share * prob)
    ce = nll.mean()
    return {"cross_entropy": ce, "load_balance": balance,
            "loss": ce + sz["aux_coef"] * balance}


def _rows(nll_fn, tokens, forced):
    """tokens [B, S + 1] -> (nll [B, S], router logits [Lr, B * S, E],
    chosen experts [Lr, B * S, K]), one row at a time
    (``nll_fn(b, row, forced)``); forced is indexed as the program lays
    its tokens out, row after row."""
    import jax.numpy as jnp

    S = tokens.shape[1] - 1
    out = [nll_fn(b, tokens[b],
                  None if forced is None else forced[:, b * S:(b + 1) * S])
           for b in range(tokens.shape[0])]
    return (jnp.stack([o[0] for o in out]),
            jnp.concatenate([o[1] for o in out], axis=1),
            jnp.concatenate([o[2] for o in out], axis=1))


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape: at published widths
    the cell cannot wait for it to run eagerly. ``with_grad``: the row's
    weights ``w [S]`` too, and the gradient of ``sum(w * nll)`` back."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, f, w):
        nll, logits, chosen = _nll(sz, p, row, f, first)
        return (w * nll).sum(), (nll, logits, chosen)

    def nll_and_grad(p, row, f, w):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, w)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, grad_weights=None
              ) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy arrays ``nll [B, S]``, ``router_logits
    [Lr, B * S, E]``, ``chosen [Lr, B * S, K]``, and the loss ``terms``
    (floats) computed from them; with ``grad_weights [B, S]`` also
    ``grads``, the gradient of ``sum(grad_weights * nll)`` with respect
    to ``first_layers(params)`` (the module's docstring)."""
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
                     jnp.asarray(grad_weights[b], jnp.float32))
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
    probabilities, not through the choice of experts."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        nll, lg, chosen = _rows(
            lambda b, row, f: _nll(sz, params, row, f),
            jnp.asarray(tokens, jnp.int32), forced_topk)
        return _terms(sz, nll, lg, chosen)["loss"]


def routed_layer(cfg, p, u):
    """One routed layer's MLP on its normed input u [T, h] (CPU sizes):
    the shared expert and the part of the experts ``cfg`` holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return routed_mlp(jnp.asarray(u, jnp.float32), p, _sizes(cfg))[0]
