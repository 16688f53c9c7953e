"""Plain reference of LFM2-MoE (LiquidAI/LFM2-8B-A1B): float32,
``jax.numpy`` only, matmuls at ``highest`` precision, no kernel, no sort,
no grouped matmul, the convolution a loop over its taps, attention a
masked softmax over every key in blocks of queries.

The equations, from transformers' ``Lfm2Moe*`` and the model's
``config.json`` (what its keys do not settle is listed under ``assumed``
in ``benchmark/configs/lfm2-8b-a1b-c1.json``). ``RMSNorm`` has a weight,
eps 1e-5. Every layer is ``x = x + Op(RMSNorm_op(x))`` then ``x = x +
FF(RMSNorm_ffn(x))``; after the last layer one more RMSNorm, then the
head, which is the embedding transposed.

- ``Op`` of a conv layer, ``h = RMSNorm_op(x)``: ``[B, C, X] = split3(h
  W_in)``, ``u = B * X``, ``v_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t``
  per channel with zeros before position 0 (``L`` taps in general:
  ``w_j`` weighs ``u_{t - (L - 1) + j}``), ``Op = (C * v) W_out``.
- ``Op`` of an attention layer: ``q, k, v`` projections without bias,
  RMSNorm over each head's dims of ``q`` and of ``k`` (one weight
  ``[head_dim]`` each), rope on all dims of a head (half-split rotation,
  theta 1e6), causal softmax attention with 32 query heads on 8 kv heads,
  the output projection.
- ``FF`` of the leading dense layers: SwiGLU. Of a routed layer, with
  ``h = RMSNorm_ffn(x)``: ``s = sigmoid(h W_r)`` over all experts; ``T``
  the ``top_k`` experts with the largest ``s + b``; ``g_e = scale * s_e /
  (sum_T s + 1e-6)``; ``FF = sum over e in T that are held of g_e
  SwiGLU_e(h)``. Every held expert runs over every token and a mask keeps
  the chosen ones. An expert that is not held adds nothing.
- Loss = cross entropy alone. After a step ``b_i += gamma * sign(mean(c)
  - c_i)`` from that step's counts ``c`` of the layer (``updated_bias``).

``forced_topk`` ([routed layers, tokens, K] expert ids) replaces the
reference's own choice of experts by the program's, the gate weights
still the reference's own scores (``olmoe_ref.py`` says why).

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields: ``params["layers"][kind][name]``
stacked over the layers of a kind (``conv_dense``, ``attn_moe``,
``conv_moe`` ...), ``cfg.pattern`` the kind of each layer. Weights are
upcast one layer, and inside it one expert, at a time.

``grad_weights`` ([B, S] float32) asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` as well, with respect to the embedding, the
last norm and the first layer of each kind without its router's bias
(``first_layers``), one row at a time: ``laguna_ref.py`` says how and why.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

Q_BLOCK = 256


def _sizes(cfg) -> Dict[str, Any]:
    held = cfg.experts_held or (0, cfg.num_experts)
    return {"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim or cfg.hidden_size // cfg.num_heads,
            "eps": cfg.rms_norm_eps, "theta": cfg.rope_theta,
            "pattern": tuple(cfg.pattern), "top_k": cfg.top_k,
            "scale": cfg.routed_scale, "renorm_eps": cfg.renorm_eps,
            "held": tuple(held)}


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def short_conv(h, p):
    """h [T, hidden] float32 (normed) -> Op(h) [T, hidden]."""
    import jax.numpy as jnp

    T = h.shape[0]
    b, c, x = jnp.split(h @ p["w_in"], 3, axis=-1)
    u = b * x
    w = p["w_conv"]                                   # [channels, taps]
    taps = w.shape[-1]
    v = jnp.zeros_like(u)
    for j in range(taps):                             # w_j on u_{t-(L-1)+j}
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(u[:back]), u[:T - back]]) if back else u
        v = v + w[:, j] * shifted[:T]
    return (c * v) @ p["w_out"]


def _rope(x, theta: float):
    """x [T, H, hd], positions 0..T-1, all dims rotated as two halves."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, sz):
    """h [T, hidden] float32 (normed) -> Op(h) [T, hidden]."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    H, KVH, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = _rms_norm((h @ p["wq"]).reshape(T, H, hd), p["q_norm"], sz["eps"])
    k = _rms_norm((h @ p["wk"]).reshape(T, KVH, hd), p["k_norm"], sz["eps"])
    v = (h @ p["wv"]).reshape(T, KVH, hd)
    q, k = _rope(q, sz["theta"]), _rope(k, sz["theta"])
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)

    # blocks of queries, each against every key: memory. A sequence that
    # is not whole blocks (CPU sizes) is one block.
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                              # [qb, H, hd], [qb, 1]
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) / jnp.sqrt(
            jnp.float32(hd))
        sc = jnp.where((j <= i)[None], sc, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, hd), jnp.arange(T).reshape(T // qb, qb, 1)))
    return outs.reshape(T, H * hd) @ p["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def routed_mlp(u, p, sz, forced=None):
    """u [T, h] float32, the normed input of a routed layer -> (the held
    experts' part [T, h], router logits [T, E], selection scores ``s + b``
    [T, E], chosen experts [T, K]). ``p``'s expert weights are the held
    experts'."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logits = u @ p["router"].astype(f32)
    s = jax.nn.sigmoid(logits)
    select = s + p["router_bias"].astype(f32)
    chosen = jax.lax.top_k(select, sz["top_k"])[1] if forced is None \
        else forced
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    gates = sz["scale"] * gates / (gates.sum(-1, keepdims=True)
                                   + sz["renorm_eps"])
    first, count = sz["held"]

    @jax.checkpoint
    def weighted(u, gate, w_gate, w_up, w_down):
        return gate[:, None] * _swiglu(
            u, w_gate.astype(f32), w_up.astype(f32), w_down.astype(f32))

    def one_expert(acc, ew):
        idx, w_gate, w_up, w_down = ew
        gate = jnp.where(chosen == idx, gates, 0.0).sum(-1)        # [T]
        return acc + weighted(u, gate, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (first + jnp.arange(count), p["e_gate"], p["e_up"], p["e_down"]))
    return out, logits, select, chosen


def _layer(x, p, forced, *, sz, kind: str):
    """x [T, h] float32; p: one layer's weights (any float dtype). ->
    (x, router logits [T, E], selection scores [T, E] and chosen experts
    [T, K], or None three times)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    big = ("e_gate", "e_up", "e_down", "router", "router_bias")
    small = {k: v.astype(f32) for k, v in p.items() if k not in big}
    if kind.startswith("attn"):
        x = x + attention(_rms_norm(x, small["attn_norm"], sz["eps"]),
                          small, sz)
    else:
        x = x + short_conv(_rms_norm(x, small["op_norm"], sz["eps"]), small)
    u = _rms_norm(x, small["mlp_norm"], sz["eps"])
    if kind.endswith("_dense"):
        return x + _swiglu(u, small["w_gate"], small["w_up"],
                           small["w_down"]), None, None, None
    out, logits, select, chosen = routed_mlp(u, p, sz, forced)
    return x + out, logits, select, chosen


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, and the first layer of each kind (its leaves
    without the stack's axis, and without the router's bias, which has
    none)."""
    return {**tree, "layers": {
        kind: {k: v[0] for k, v in leaves.items() if k != "router_bias"}
        for kind, leaves in tree["layers"].items()}}


def _run(sz, params, tokens, forced, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm
    [T, h], router logits [Lr, T, E], selection scores [Lr, T, E], chosen
    experts [Lr, T, K]). ``first`` (``first_layers(params)``) stands in
    for the weights it holds: what a gradient is taken with respect to."""
    import jax
    import jax.numpy as jnp

    x = (first or params)["embed"][tokens].astype(jnp.float32)
    taken = dict.fromkeys(params["layers"], 0)
    logits, select, chosen = [], [], []
    for kind in sz["pattern"]:
        at = taken[kind]
        taken[kind] += 1
        p = {k: v[at] for k, v in params["layers"][kind].items()}
        if first and at == 0:
            p = {**p, **first["layers"][kind]}
        x, lg, sel, ch = jax.checkpoint(partial(_layer, sz=sz, kind=kind))(
            x, p, forced=None if forced is None or kind.endswith("_dense")
            else forced[len(logits)])
        if lg is not None:
            logits.append(lg)
            select.append(sel)
            chosen.append(ch)
    return x, jnp.stack(logits), jnp.stack(select), jnp.stack(chosen)


def _head(x, params, sz):
    import jax.numpy as jnp

    x = _rms_norm(x, params["final_norm"].astype(jnp.float32), sz["eps"])
    return x @ params["embed"].astype(jnp.float32).T


def _nll(sz, params, row, forced, first=None):
    """row [S + 1] -> (next-token loss [S], router logits, selection
    scores, chosen)."""
    import jax
    import jax.numpy as jnp

    x, logits, select, chosen = _run(sz, params, row[:-1], forced, first)
    lg = _head(x, first or params, sz)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, row[1:, None], -1)[:, 0]
    return nll, logits, select, chosen


def _rows(nll_fn, tokens, forced):
    """tokens [B, S + 1] -> (nll [B, S], then router logits and selection
    scores [Lr, B * S, E] and chosen experts [Lr, B * S, K]), one row at a
    time (``nll_fn(b, row, forced)``); forced is indexed as the program
    lays its tokens out, row after row."""
    import jax.numpy as jnp

    S = tokens.shape[1] - 1
    out = [nll_fn(b, tokens[b],
                  None if forced is None else forced[:, b * S:(b + 1) * S])
           for b in range(tokens.shape[0])]
    return (jnp.stack([o[0] for o in out]),) + tuple(
        jnp.concatenate([o[i] for o in out], axis=1) for i in (1, 2, 3))


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
        nll, *router = _nll(sz, p, row, f, first)
        return (w * nll).sum(), (nll, *router)

    def nll_and_grad(p, row, f, w):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, w)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, grad_weights=None
              ) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy arrays ``nll [B, S]``, ``router_logits``
    and ``select_scores`` (``sigmoid(logits) + b``) ``[Lr, B * S, E]``,
    ``chosen [Lr, B * S, K]``, and the loss ``terms`` (floats; there is no
    router term: ``load_balance`` is 0.0); with ``grad_weights [B, S]``
    also ``grads``, the gradient of ``sum(grad_weights * nll)`` with
    respect to ``first_layers(params)``."""
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
        nll, logits, select, chosen = _rows(one_row, tokens, forced_topk)
    ce = float(nll.mean())
    out = {"nll": np.asarray(nll), "router_logits": np.asarray(logits),
           "select_scores": np.asarray(select), "chosen": np.asarray(chosen),
           "terms": {"cross_entropy": ce, "load_balance": 0.0, "loss": ce}}
    if grad_weights is not None:
        out["grads"] = grads[0]
    return out


def updated_bias(cfg, bias, counts):
    """The routers' biases [Lr, E] after a step that sent ``counts [Lr,
    E]`` rows to each expert (numpy): an expert with fewer rows than its
    layer's mean gains ``bias_update_rate``, one with more loses it."""
    import numpy as np

    c = np.asarray(counts, np.float64)
    move = np.sign(c.mean(-1, keepdims=True) - c)
    return (np.asarray(bias, np.float32)
            + np.float32(cfg.bias_update_rate) * move.astype(np.float32))


def router_biases(cfg, params):
    """The routers' biases [Lr, E] (numpy), routed layers in their order."""
    import numpy as np

    taken = dict.fromkeys(params["layers"], 0)
    rows = []
    for kind in cfg.pattern:
        at = taken[kind]
        taken[kind] += 1
        if not kind.endswith("_dense"):
            rows.append(np.asarray(
                params["layers"][kind]["router_bias"][at], np.float32))
    return np.stack(rows)


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
    scores, not through the choice of experts nor into the bias."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        nll = _rows(lambda b, row, f: _nll(sz, params, row, f),
                    jnp.asarray(tokens, jnp.int32), forced_topk)[0]
        return nll.mean()


def routed_layer(cfg, p, u):
    """One routed layer's MLP on its normed input u [T, h] (CPU sizes):
    the part of the experts ``cfg`` holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return routed_mlp(jnp.asarray(u, jnp.float32), p, _sizes(cfg))[0]
