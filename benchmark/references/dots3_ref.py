"""Plain reference of dots3-note-prev's language model
(dots-studio/dots3-note-prev): float32, ``jax.numpy`` only, matmuls at
``highest`` precision, no kernel, no sort but ``lax.top_k``'s, no grouped
matmul; the index's scores and the softmax over the chosen keys by a mask
in blocks of queries, the keys built whole (the shared rotated part copied
to every head).

The equations, from the model's ``config.json`` (what its keys do not
settle is listed under ``assumed`` in ``benchmark/configs/
dots3-note-prev-c1.json``). ``u = RMSNorm(x)``, eps 1e-5, pre-norm, a final
norm before the untied head; ``H`` heads held of a layer's.

- Latent attention, both kinds: ``c_q = RMSNorm(u W_qa)``; ``q = (c_q W_qb)
  (hidden / q_rank) ** 0.5`` [H, d_n + d_r]; ``[c_kv | k_r] = u W_kva``;
  ``c_kv = RMSNorm(c_kv) (hidden / kv_rank) ** 0.5``; ``[k_n | v] = c_kv
  W_kvb`` [H, d_n | d_v]. Rope on the last ``d_r`` dims of ``q`` and on the
  one ``k_r`` of a position: de-interleaved (``x[0::2] | x[1::2]``), then
  rotated as two halves, unscaled. ``A_h = softmax(([q_n | q_r] . [k_n |
  k_r]) (d_n + d_r) ** -0.5 + mask) v``; ``g = sigmoid(u W_g)`` [H]; ``y = x
  + concat_h(g_h A_h) W_o``.
- A full layer (q rank 1024, kv rank 512, keys 128 + 64, theta 8e7): its
  mask is the index's choice. ``q_i = c_q W_iq`` [J, d_i] (the normed,
  unscaled latent); ``k_i = LayerNorm(u W_ik)`` [d_i] with weight and bias;
  the first ``d_r`` dims of both rotated as above; ``w = (u W_iw) J ** -0.5
  d_i ** -0.5``; ``I[t, s] = sum_j w[t, j] ReLU(q_i[t, j] . k_i[s])``, ``s
  <= t``; ``S_t`` the ``min(t + 1, topk)`` largest by ``lax.top_k`` (ties to
  the lower position). Its term of the loss: ``L_I = mean_t KL(p_t ||
  softmax_{s in S_t} I[t, s])``, ``p_t = sum_h A-probabilities / H`` (L1
  normalised), ``p_t``, ``u`` and ``c_q`` under ``stop_gradient``.
- A window layer (ranks 1024 | 1024, keys 192 + 64, theta 5e4): the mask is
  ``t - window < s <= t``; no index.
- MLP: layer 0 ``y + SwiGLU(RMSNorm(y))``; a routed one, ``u =
  RMSNorm(y)``: ``s = sigmoid(u W_r)`` over all experts, ``T`` the ``top_k``
  largest of ``s + b``, ``w_e = scale * s_e / sum_{T} s``; ``out = y +
  SwiGLU_shared(u) + sum over e in T that are held of w_e SwiGLU_e(u)``: a
  loop over the held experts, each over every token under a mask.
- Loss = cross entropy + ``index_loss_coef`` x the full layers' sum of
  ``L_I``.

``forced_topk`` ([routed layers, tokens, K] expert ids) and ``forced_keys``
([full layers, T, T / 8] uint8, a query's chosen keys packed eight a byte,
key ``8 i + j`` the bit ``7 - j`` of byte ``i``) replace the reference's own
choices by the program's; gate weights, attention and ``L_I`` are still the
reference's own numbers on those choices.

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields; the norm, the SwiGLU and
``first_layers`` are ``laguna_ref.py``'s, by import. ``grad_weights`` asks
``token_nll`` for the gradient of ``sum(grad_weights * nll) + index_weight x
sum over full layers of L_I`` with respect to ``first_layers(params)``: the
embedding, the last norm, the head and the first layer of each kind (layers
0, 1 and 2).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

from benchmark.references.laguna_ref import (_rms_norm, _swiglu,  # noqa: F401
                                              first_layers)

Q_BLOCK = 128


def _sizes(cfg) -> Dict[str, Any]:
    def kind(prefix, theta):
        def of(name):
            return getattr(cfg, prefix + name)
        return (of("num_heads"), of("q_lora_rank"), of("kv_lora_rank"),
                of("qk_nope_head_dim"), of("qk_rope_head_dim"),
                of("v_head_dim"), theta)

    return {"full": kind("", cfg.rope_theta),
            "sliding": kind("swa_", cfg.swa_rope_theta),
            "window": cfg.sliding_window,
            "index": (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
                      cfg.index_norm_eps),
            "index_coef": cfg.index_loss_coef,
            "eps": cfg.rms_norm_eps, "pattern": tuple(cfg.pattern),
            "top_k": cfg.top_k, "scale": cfg.routed_scale,
            "held": tuple(cfg.experts_held or (0, cfg.num_experts))}


def _lowered(tree, sz):
    """``tree``'s float leaves rounded to ``sz["lower"]`` (a dtype's name)
    and back, inside the program that reads them: a control's reference
    one precision lower, without a second copy of the weights."""
    import jax
    import jax.numpy as jnp

    if not sz.get("lower"):
        return tree
    low = getattr(jnp, sz["lower"])
    return jax.tree_util.tree_map(
        lambda x: x.astype(low).astype(x.dtype)
        if x.dtype in (jnp.bfloat16, jnp.float32) else x, tree)


def _rope(x, theta: float):
    """x [T, heads, d_r], positions 0..T-1: de-interleaved, then rotated
    as two halves, ``inv_freq = theta ** (-2 i / d_r)``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w + b


def index_scores(q_i, k_i, w):
    """q_i [n, J, d], k_i [S, d], w [n, J] -> ``I [n, S]``: ``sum_j w[., j]
    ReLU(q_i[., j] . k_i)``. No mask."""
    import jax.numpy as jnp

    x = jnp.einsum("njd,sd->njs", q_i, k_i)
    return (jnp.maximum(x, 0.0) * w[:, :, None]).sum(1)


def plain_top_k(scores, first_q, topk: int):
    """scores [n, S] of the queries at ``first_q + 0 .. n - 1`` -> bool [n,
    S]: each query's ``topk`` causal keys of largest score by
    ``lax.top_k`` (ties to the lower position), all where it sees fewer."""
    import jax
    import jax.numpy as jnp

    n, S = scores.shape
    causal = jnp.arange(S)[None] <= first_q + jnp.arange(n)[:, None]
    # + 0.0: a negative zero is a zero, and ties with one
    _, at = jax.lax.top_k(jnp.where(causal, scores + 0.0, -jnp.inf),
                          min(topk, S))
    picked = jnp.zeros((n, S), bool).at[jnp.arange(n)[:, None], at].set(True)
    return picked & causal


def index_inputs(u, c_q, p, sz):
    """(q_i [T, J, d_i], k_i [T, d_i], w [T, J]) of a full layer from its
    normed input and its normed query latent."""
    import jax
    import jax.numpy as jnp

    J, di, _, eps = sz["index"]
    dr, theta = sz["full"][4], sz["full"][6]
    T = u.shape[0]
    u, c_q = jax.lax.stop_gradient(u), jax.lax.stop_gradient(c_q)
    q_i = (c_q @ p["wi_q"]).reshape(T, J, di)
    k_i = _layer_norm(u @ p["wi_k"], p["wi_k_norm"], p["wi_k_bias"], eps)
    q_i = jnp.concatenate([_rope(q_i[..., :dr], theta), q_i[..., dr:]], -1)
    k_i = jnp.concatenate(
        [_rope(k_i[:, None, :dr], theta)[:, 0], k_i[:, dr:]], -1)
    return q_i, k_i, (u @ p["wi_w"]) * (J ** -0.5 * di ** -0.5)


def attention(x, p, sz, kind: str, forced_keys=None):
    """x [T, h], one layer's attention weights -> (what the held heads add
    to the residual stream [T, h]; of a full layer the sum over positions
    of its KL terms and its index's inputs, else None twice)."""
    import jax
    import jax.numpy as jnp

    full = kind.startswith("full")
    H, rq, rkv, dn, dr, dv, theta = sz["full" if full else "sliding"]
    T, h = x.shape
    u = _rms_norm(x, p["attn_norm"], sz["eps"])
    c_q = _rms_norm(u @ p["wq_a"], p["q_a_norm"], sz["eps"])
    q = (c_q @ p["wq_b"]).reshape(T, H, dn + dr) * (h / rq) ** 0.5
    ckv = u @ p["wkv_a"]
    c_kv = _rms_norm(ckv[:, :rkv], p["kv_a_norm"], sz["eps"]) \
        * (h / rkv) ** 0.5
    kv = (c_kv @ p["wkv_b"]).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    k_r = _rope(ckv[:, None, rkv:], theta)                   # [T, 1, d_r]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (T, H, dr))], -1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    gate = jax.nn.sigmoid(u @ p["wg"])                       # [T, H]
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]
    index = index_inputs(u, c_q, p, sz) if full else None

    @jax.checkpoint
    def block(_, at):
        q_blk, i, first, own = at            # [qb, H, .], [qb, 1], scalar
        if not full:
            seen = (j <= i) & (j > i - sz["window"])
            kl = jnp.zeros(())
        else:
            q_i, k_i, w = index
            scores = index_scores(
                jax.lax.dynamic_slice_in_dim(q_i, first, qb), k_i,
                jax.lax.dynamic_slice_in_dim(w, first, qb))
            seen = plain_top_k(jax.lax.stop_gradient(scores), first,
                               sz["index"][2]) if own is None else (
                jnp.unpackbits(own, axis=-1)[:, :T].astype(bool))
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        if full:
            target = jax.lax.stop_gradient(pr.sum(0))
            target = target / target.sum(-1, keepdims=True)
            log_q = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), -1)
            kl = jnp.where(target > 0, target * (jnp.log(jnp.where(
                target > 0, target, 1.0)) - jnp.where(seen, log_q, 0.0)),
                0.0).sum()
        return None, (jnp.einsum("hqk,khd->qhd", pr, v), kl)

    _, (outs, kl) = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, dn + dr),
        jnp.arange(T).reshape(T // qb, qb, 1),
        jnp.arange(T // qb) * qb,
        None if forced_keys is None or not full
        else forced_keys.reshape(T // qb, qb, -1)))
    heads = outs.reshape(T, H, dv) * gate[:, :, None]
    return (heads.reshape(T, H * dv) @ p["wo"],
            kl.sum() if full else None, index)


def routed_mlp(u, p, sz, forced=None):
    """u [T, h] float32, the normed input of a routed layer -> (the shared
    expert + the held experts' part [T, h], router logits [T, E], chosen
    experts [T, K]). ``p``'s expert weights are the held experts'."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logits = u @ p["router"].astype(f32)
    scores = jax.nn.sigmoid(logits)
    chosen = (jax.lax.top_k(scores + p["router_bias"].astype(f32),
                            sz["top_k"])[1] if forced is None else forced)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = sz["scale"] * picked / picked.sum(-1, keepdims=True)
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


def _layer(x, p, forced, keys, *, sz, kind: str):
    """x [T, h] float32; p: one layer's weights (any float dtype). -> (x,
    router logits and chosen experts or None twice, the full layer's KL sum
    and index inputs or None twice)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    big = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down", "router",
           "router_bias")
    p = {**_lowered({k: v for k, v in p.items() if k != "router_bias"}, sz),
         **{k: v for k, v in p.items() if k == "router_bias"}}
    small = {k: v.astype(f32) for k, v in p.items() if k not in big}
    out, kl, index = attention(x, small, sz, kind, keys)
    x = x + out
    u = _rms_norm(x, small["mlp_norm"], sz["eps"])
    if not kind.endswith("_moe"):
        return x + _swiglu(u, small["w_gate"], small["w_up"],
                           small["w_down"]), None, None, kl, index
    out, logits, chosen = routed_mlp(u, p, sz, forced)
    return x + out, logits, chosen, kl, index


def _run(sz, params, tokens, forced, keys, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm [T,
    h], router logits [Lr, T, E], chosen experts [Lr, T, K], the full
    layers' KL sums [Lf] and index inputs, a list). ``first``
    (``first_layers(params)``) stands in for the weights it holds."""
    import jax
    import jax.numpy as jnp

    x = _lowered((first or params)["embed"][tokens], sz).astype(jnp.float32)
    taken = dict.fromkeys(params["layers"], 0)
    logits, chosen, kls, index = [], [], [], []
    for kind in sz["pattern"]:
        at = taken[kind]
        taken[kind] += 1
        full = kind.startswith("full")
        x, lg, ch, kl, ix = jax.checkpoint(partial(_layer, sz=sz, kind=kind))(
            x, first["layers"][kind] if first and at == 0
            else {k: v[at] for k, v in params["layers"][kind].items()},
            None if forced is None or not kind.endswith("_moe")
            else forced[len(logits)],
            None if keys is None or not full else keys[len(kls)])
        if lg is not None:
            logits.append(lg)
            chosen.append(ch)
        if full:
            kls.append(kl)
            index.append(ix)
    return x, jnp.stack(logits), jnp.stack(chosen), jnp.stack(kls), index


def _head(x, params, sz):
    import jax.numpy as jnp

    x = _rms_norm(x, _lowered(params["final_norm"], sz).astype(jnp.float32),
                  sz["eps"])
    return x @ _lowered(params["lm_head"], sz).astype(jnp.float32)


def _nll(sz, params, row, forced, keys, first=None):
    """row [S + 1] -> (next-token loss [S], router logits, chosen experts,
    the full layers' ``L_I`` [Lf], their index inputs)."""
    import jax
    import jax.numpy as jnp

    x, logits, chosen, kls, index = _run(sz, params, row[:-1], forced, keys,
                                         first)
    lg = _head(x, first or params, sz)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, row[1:, None], -1)[:, 0]
    return nll, logits, chosen, kls / nll.shape[0], index


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape (``laguna_ref.py``)."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, f, keys, w, aux):
        nll, logits, chosen, l_i, index = _nll(sz, p, row, f, keys, first)
        return ((w * nll).sum() + aux * l_i.sum(),
                (nll, logits, chosen, l_i, index))

    def nll_and_grad(p, row, f, keys, w, aux):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, keys, w, aux)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f, keys: _nll(sz, p, row, f,
                                                          keys))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, forced_keys=None,
              grad_weights=None, index_weight: float = 0.0,
              weight_dtype: Optional[str] = None) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy arrays ``nll [B, S]``, ``router_logits
    [Lr, B * S, E]``, ``chosen [Lr, B * S, K]``, ``index_loss [Lf]`` (each
    full layer's ``L_I``, the mean over the batch's rows), the loss
    ``terms`` (floats) and ``index`` (device arrays: for each row and full
    layer the index's ``(q_i, k_i, w)``); with ``grad_weights [B, S]`` also
    ``grads``, the gradient of ``sum(grad_weights * nll) + index_weight x
    mean over rows of sum over full layers of L_I`` with respect to
    ``first_layers(params)``: with weights of ``1 / (B S)`` and
    ``index_weight = cfg.index_loss_coef`` the train step's loss.
    ``forced_keys [Lf, B, S, S / 8]``: the module's docstring.
    ``weight_dtype`` (a dtype's name, "float8_e4m3fn"): every weight but
    the routers' biases rounded to it as it is read (``_lowered``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = {**_sizes(cfg), "lower": weight_dtype}
    fn = _jitted_nll(sz, grad_weights is not None)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, S = tokens.shape[0], tokens.shape[1] - 1
    if forced_topk is not None:
        forced_topk = jnp.asarray(forced_topk, jnp.int32)
    grads = []           # the sum of the rows' gradients so far
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            f = (None if forced_topk is None
                 else forced_topk[:, b * S:(b + 1) * S])
            keys = None if forced_keys is None else forced_keys[:, b]
            if grad_weights is None:
                rows.append(fn(params, tokens[b], f, keys))
                continue
            *out, g = fn(params, tokens[b], f, keys,
                         jnp.asarray(grad_weights[b], jnp.float32),
                         index_weight / B)
            grads[:] = [g if not grads
                        else jax.tree_util.tree_map(jnp.add, grads[0], g)]
            rows.append(out)
    nll = np.stack([np.asarray(r[0]) for r in rows])
    l_i = np.mean([np.asarray(r[3]) for r in rows], axis=0)
    ce = float(nll.mean())
    out = {"nll": nll,
           "router_logits": np.concatenate(
               [np.asarray(r[1]) for r in rows], axis=1),
           "chosen": np.concatenate([np.asarray(r[2]) for r in rows], axis=1),
           "index_loss": l_i, "index": [r[4] for r in rows],
           "terms": {"cross_entropy": ce, "index_loss": float(l_i.sum()),
                     "loss": ce + sz["index_coef"] * float(l_i.sum())}}
    if grad_weights is not None:
        out["grads"] = grads[0]
    return out


def logits(cfg, params, tokens, forced_topk=None, forced_keys=None):
    """tokens [B, S] -> logits [B, S, V] float32 (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    S = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head(_run(sz, params, jnp.asarray(row, jnp.int32),
                       None if forced_topk is None
                       else forced_topk[:, b * S:(b + 1) * S],
                       None if forced_keys is None else forced_keys[:, b])[0],
                  params, sz) for b, row in enumerate(tokens)])


def loss_terms(cfg, params, tokens, forced_topk: Optional[Any] = None,
               forced_keys: Optional[Any] = None):
    """(cross entropy, the full layers' sum of ``L_I``) as differentiable
    functions of ``params`` (CPU sizes). The gradient flows through the
    gate weights and the router's scores, not through a choice."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[1] - 1
    with jax.default_matmul_precision("highest"):
        rows = [_nll(sz, params, row,
                     None if forced_topk is None
                     else forced_topk[:, b * S:(b + 1) * S],
                     None if forced_keys is None else forced_keys[:, b])
                for b, row in enumerate(tokens)]
        return (jnp.stack([r[0] for r in rows]).mean(),
                jnp.stack([r[3] for r in rows]).mean(0).sum())


def chosen_keys(cfg, params, tokens):
    """tokens [B, S] -> bool [Lf, B, S, S]: the reference's own choice of
    keys in every full layer (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    topk = sz["index"][2]
    with jax.default_matmul_precision("highest"):
        per_row = []
        for row in jnp.asarray(tokens, jnp.int32):
            index = _run(sz, params, row, None, None)[4]
            per_row.append(jnp.stack([
                plain_top_k(index_scores(q_i, k_i, w), 0, topk)
                for q_i, k_i, w in index]))
        return jnp.stack(per_row, axis=1)


def attention_layer(cfg, p, x, kind: str):
    """One layer's attention on x [T, h] (CPU sizes): what the heads
    ``cfg`` holds add to the residual stream and, of a full layer, the sum
    over positions of its KL terms."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
        out, kl, _ = attention(jnp.asarray(x, jnp.float32), p, _sizes(cfg),
                               kind)
        return out, kl


def routed_layer(cfg, p, u, shared: bool = True):
    """One routed layer's MLP on its normed input u [T, h] (CPU sizes):
    the shared expert (``shared``) and the part of the experts ``cfg``
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


def router_biases(cfg, params):
    """The routers' biases [Lr, E] float32 (numpy), in layer order."""
    import numpy as np

    taken = dict.fromkeys(params["layers"], 0)
    rows = []
    for kind in cfg.pattern:
        at = taken[kind]
        taken[kind] += 1
        if kind.endswith("_moe"):
            rows.append(np.asarray(
                params["layers"][kind]["router_bias"][at], np.float32))
    return np.stack(rows)


def updated_bias(cfg, bias, counts):
    """The rule: ``b_i += rate x sign(mean(c) - c_i)``, [Lr, E] numpy."""
    import numpy as np

    c = np.asarray(counts, np.float32)
    return bias + np.float32(cfg.bias_update_rate) * np.sign(
        c.mean(-1, keepdims=True) - c)
