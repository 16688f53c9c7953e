"""Plain reference of Keye-VL-2.0-30B-A3B's language model
(Kwai-Keye/Keye-VL-2.0-30B-A3B): float32, ``jax.numpy`` only, matmuls at
``highest`` precision, no kernel, no sort but ``lax.top_k``'s, no grouped
matmul; the index's scores and the softmax over the chosen keys by a mask in
blocks of queries.

The equations, from the model's ``config.json`` (what its keys do not
settle is listed under ``assumed`` in ``benchmark/configs/
keye-vl-2.0-30b-a3b-c1.json``). Every layer, ``u = RMSNorm(x)``, eps 1e-6,
pre-norm, a final norm before the untied head, no bias:

- ``q = N_d(u W_q)`` [H, d], ``k = N_d(u W_k)`` [G, d] (an RMSNorm over each
  head's ``d`` dims with a weight ``[d]``), ``v = u W_v`` [G, d]; query head
  ``h`` reads key/value head ``h // (H / G)``.
- Rope in three position streams, ``positions [3, T]`` (temporal, height,
  width): frequency pair ``i`` of the ``d / 2`` (``inv_freq_i = theta ** (-2
  i / d)``) turns by the stream its section names, ``mrope_section`` [16,
  24, 24] in order; the head's halves ``(x[:d/2], x[d/2:])`` rotate as
  ``(x1 cos - x2 sin, x2 cos + x1 sin)``. Text: all three streams ``0 .. T -
  1``.
- The index: ``q_i = u W_iq`` [J, d_i]; ``k_i = LayerNorm(u W_ik)`` [d_i]
  with weight and bias; both rotated whole as above with sections
  ``index_mrope_section``; ``w = (u W_iw) J ** -0.5 d_i ** -0.5``; ``I[t, s]
  = sum_j w[t, j] ReLU(q_i[t, j] . k_i[s])``, ``s <= t`` in sequence order;
  ``S_t`` the ``min(t + 1, topk)`` largest by ``lax.top_k`` (ties to the
  lower position). ``A_h = softmax_{s in S_t}(q_h . k_{h // (H / G)} d **
  -0.5) v``; ``y = x + concat_h(A_h) W_o``. Its term of the loss: ``L_I =
  mean_t KL(p_t || softmax_{s in S_t} I[t, s])``, ``p_t = sum_h
  A-probabilities`` L1-normalised; ``p_t`` and ``u`` under ``stop_gradient``.
- MLP, ``u = RMSNorm(y)``: ``s = softmax(u W_r)`` over all experts, ``T`` the
  ``top_k`` largest, ``w_e = s_e / sum_T s``; ``out = y + sum over e in T
  that are held of w_e SwiGLU_e(u)``: a loop over the held experts, each
  over every token under a mask. No shared expert.
- Loss = mean over the text targets (``mask``) of the next-token loss +
  ``index_loss_coef`` x the layers' sum of ``L_I`` + ``router_aux_coef`` x
  transformers' ``load_balancing_loss_func`` over all layers.

``forced_topk`` ([layers, tokens, K] expert ids) and ``forced_keys``
([layers, B, T, T / 8] uint8, a query's chosen keys packed eight a byte, key
``8 i + j`` the bit ``7 - j`` of byte ``i``) replace the reference's own
choices by the program's; gate weights, attention and ``L_I`` are still the
reference's own numbers on those choices.

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields; the norm and the SwiGLU are
``laguna_ref.py``'s, the index's scores, ``lax.top_k``'s choice, the
LayerNorm and the rounding of weights ``dots3_ref.py``'s, by import. ``grad_weights`` asks ``token_nll`` for the
gradient of ``sum(grad_weights * nll) + index_weight x sum over layers of
L_I + router_weight x the balancing term`` with respect to
``first_layers(params)``: the embedding, the last norm, the head and the
first two layers, apart (``layer_0``, ``layer_1``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

from benchmark.references.dots3_ref import (_layer_norm, _lowered,  # noqa: F401
                                            index_scores, plain_top_k)
from benchmark.references.laguna_ref import _rms_norm, _swiglu  # noqa: F401

Q_BLOCK = 128
HEAD_BLOCK = 2048
GRAD_LAYERS = 2
_BIG = ("e_gate", "e_up", "e_down", "router")


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, the head, and the stack's first
    ``GRAD_LAYERS`` layers, each a kind of its own (``layer_<n>``: its
    leaves without the stack's axis)."""
    leaves = tree["layers"]["sparse_moe"]
    depth = min(GRAD_LAYERS, next(iter(leaves.values())).shape[0])
    return {**tree, "layers": {
        f"layer_{n}": {k: v[n] for k, v in leaves.items()}
        for n in range(depth)}}


def _sizes(cfg) -> Dict[str, Any]:
    return {"heads": (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_),
            "theta": cfg.rope_theta,
            "sections": tuple(cfg.mrope_section),
            "index_sections": tuple(cfg.index_mrope_section),
            "index": (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
                      cfg.index_norm_eps),
            "index_coef": cfg.index_loss_coef,
            "aux_coef": cfg.router_aux_coef,
            "eps": cfg.rms_norm_eps, "layers": cfg.num_layers,
            "top_k": cfg.top_k, "scale": cfg.routed_scale,
            "held": tuple(cfg.experts_held or (0, cfg.num_experts))}


def rope_angles(positions, width: int, sections, theta: float):
    """positions [3, T] -> the angle of each of the ``width / 2`` frequency
    pairs at each position [T, width / 2]: pair ``i`` turns by the stream
    whose section it lies in."""
    import jax.numpy as jnp
    import numpy as np

    stream = np.repeat(np.arange(len(sections)), sections)
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    return positions.astype(jnp.float32)[stream].T * inv[None]


def _rope(x, ang):
    """x [T, heads, d] by the angles [T, d / 2]: the halves rotated."""
    import jax.numpy as jnp

    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_inputs(u, p, sz, positions):
    """(q_i [T, J, d_i], k_i [T, d_i], w [T, J]) of a layer from its normed
    input."""
    import jax

    J, di, _, eps = sz["index"]
    T = u.shape[0]
    u = jax.lax.stop_gradient(u)
    ang = rope_angles(positions, di, sz["index_sections"], sz["theta"])
    q_i = _rope((u @ p["wi_q"]).reshape(T, J, di), ang)
    k_i = _rope(_layer_norm(u @ p["wi_k"], p["wi_k_norm"], p["wi_k_bias"],
                            eps)[:, None], ang)[:, 0]
    return q_i, k_i, (u @ p["wi_w"]) * (J ** -0.5 * di ** -0.5)


def attention(x, p, sz, positions, forced_keys=None):
    """x [T, h], one layer's attention weights, positions [3, T] -> (what
    the heads add to the residual stream [T, h], the sum over positions of
    the layer's KL terms, its index's inputs)."""
    import jax
    import jax.numpy as jnp

    H, G, d = sz["heads"]
    T = x.shape[0]
    u = _rms_norm(x, p["attn_norm"], sz["eps"])
    ang = rope_angles(positions, d, sz["sections"], sz["theta"])
    q = _rope(_rms_norm((u @ p["wq"]).reshape(T, H, d), p["q_norm"],
                        sz["eps"]), ang).reshape(T, G, H // G, d)
    k = _rope(_rms_norm((u @ p["wk"]).reshape(T, G, d), p["k_norm"],
                        sz["eps"]), ang)
    v = (u @ p["wv"]).reshape(T, G, d)
    scale = d ** -0.5
    qb = Q_BLOCK if T % Q_BLOCK == 0 else T
    index = index_inputs(u, p, sz, positions)

    @jax.checkpoint
    def block(_, at):
        q_blk, first, own = at               # [qb, G, R, d], scalar
        q_i, k_i, w = index
        scores = index_scores(
            jax.lax.dynamic_slice_in_dim(q_i, first, qb), k_i,
            jax.lax.dynamic_slice_in_dim(w, first, qb))
        seen = plain_top_k(jax.lax.stop_gradient(scores), first,
                           sz["index"][2]) if own is None else (
            jnp.unpackbits(own, axis=-1)[:, :T].astype(bool))
        sc = jnp.einsum("qgrd,kgd->grqk", q_blk, k) * scale
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        target = jax.lax.stop_gradient(pr.sum((0, 1)))
        target = target / target.sum(-1, keepdims=True)
        log_q = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), -1)
        kl = jnp.where(target > 0, target * (jnp.log(jnp.where(
            target > 0, target, 1.0)) - jnp.where(seen, log_q, 0.0)),
            0.0).sum()
        return None, (jnp.einsum("grqk,kgd->qgrd", pr, v), kl)

    _, (outs, kl) = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, G, H // G, d), jnp.arange(T // qb) * qb,
        None if forced_keys is None
        else forced_keys.reshape(T // qb, qb, -1)))
    return outs.reshape(T, H * d) @ p["wo"], kl.sum(), index


def routed_mlp(u, p, sz, forced=None):
    """u [T, h] float32, the normed input of the MLP -> (the held experts'
    part [T, h], router logits [T, E], chosen experts [T, K]). ``p``'s
    expert weights are the held experts'."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    logits = u @ p["router"].astype(f32)
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = (jax.lax.top_k(probs, sz["top_k"])[1] if forced is None
              else forced)
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
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

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (first + jnp.arange(count), p["e_gate"], p["e_up"], p["e_down"]))
    return out, logits, chosen


def _layer(x, p, forced, keys, positions, *, sz):
    """x [T, h] float32; p: one layer's weights (any float dtype). -> (x,
    router logits, chosen experts, the layer's KL sum, its index inputs)."""
    import jax.numpy as jnp

    p = _lowered(p, sz)
    small = {k: v.astype(jnp.float32) for k, v in p.items() if k not in _BIG}
    out, kl, index = attention(x, small, sz, positions, keys)
    x = x + out
    out, logits, chosen = routed_mlp(
        _rms_norm(x, small["mlp_norm"], sz["eps"]), p, sz, forced)
    return x + out, logits, chosen, kl, index


def _run(sz, params, tokens, positions, forced, keys, first=None):
    """One sequence: tokens [T], positions [3, T] -> (hidden states before
    the last norm [T, h], router logits [L, T, E], chosen experts [L, T,
    K], the layers' KL sums [L] and index inputs, a list). ``first``
    (``first_layers(params)``) stands in for the weights it holds."""
    import jax
    import jax.numpy as jnp

    x = _lowered((first or params)["embed"][tokens], sz).astype(jnp.float32)
    stacked = params["layers"]["sparse_moe"]
    logits, chosen, kls, index = [], [], [], []
    for at in range(sz["layers"]):
        own = (first or {"layers": {}})["layers"].get(f"layer_{at}")
        x, lg, ch, kl, ix = jax.checkpoint(partial(_layer, sz=sz))(
            x, own or {k: v[at] for k, v in stacked.items()},
            None if forced is None else forced[at],
            None if keys is None else keys[at], positions)
        logits.append(lg)
        chosen.append(ch)
        kls.append(kl)
        index.append(ix)
    return x, jnp.stack(logits), jnp.stack(chosen), jnp.stack(kls), index


def _head(x, params, sz):
    import jax.numpy as jnp

    x = _rms_norm(x, _lowered(params["final_norm"], sz).astype(jnp.float32),
                  sz["eps"])
    return x @ _lowered(params["lm_head"], sz).astype(jnp.float32)


def balance(sz, logits, chosen):
    """transformers' ``load_balancing_loss_func`` before its coefficient,
    of router logits [L, n, E] and chosen experts [L, n, K]."""
    import jax
    import jax.numpy as jnp

    E = logits.shape[-1]
    one_hot = jax.nn.one_hot(chosen.reshape(-1, sz["top_k"]), E)
    share = one_hot.mean(0).sum(0)          # [E]: sums to K over experts
    prob = jax.nn.softmax(logits.reshape(-1, E), -1).mean(0)
    return E * jnp.sum(share * prob)


def _nll(sz, params, row, positions, forced, keys, first=None):
    """row [S + 1], positions [3, S] -> (next-token loss [S], router
    logits, chosen experts, the layers' ``L_I`` [L], their index inputs).
    The head in blocks of ``HEAD_BLOCK`` positions, a block's logits alive
    for that block alone (37,984 x 16,384 float32 and their gradient do not
    fit beside the weights)."""
    import jax
    import jax.numpy as jnp

    x, logits, chosen, kls, index = _run(sz, params, row[:-1], positions,
                                         forced, keys, first)
    top = first or params
    T = x.shape[0]
    hb = HEAD_BLOCK if T % HEAD_BLOCK == 0 else T

    @jax.checkpoint
    def block(xb, tb):
        lg = _head(xb, top, sz)
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[:, None], -1)[:, 0]

    nll = jax.lax.map(lambda a: block(*a), (
        x.reshape(T // hb, hb, -1), row[1:].reshape(T // hb, hb))).reshape(T)
    return nll, logits, chosen, kls / T, index


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape (``laguna_ref.py``)."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, pos, f, keys, w, aux, router):
        nll, logits, chosen, l_i, index = _nll(sz, p, row, pos, f, keys,
                                               first)
        return ((w * nll).sum() + aux * l_i.sum()
                + router * balance(sz, logits, chosen),
                (nll, logits, chosen, l_i, index))

    def nll_and_grad(p, row, pos, f, keys, w, aux, router):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, pos, f, keys, w, aux, router)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, pos, f, keys: _nll(
                            sz, p, row, pos, f, keys))
    return _JIT[key]


def _positions(positions, B: int, S: int):
    """[3, B, S] int32, text's where none are given."""
    import jax.numpy as jnp

    if positions is None:
        return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (3, B, S))
    return jnp.asarray(positions, jnp.int32)


def token_nll(cfg, params, tokens, positions=None, forced_topk=None,
              forced_keys=None, grad_weights=None, index_weight: float = 0.0,
              router_weight: float = 0.0,
              weight_dtype: Optional[str] = None) -> Dict[str, Any]:
    """tokens [B, S + 1], positions [3, B, S] (None: text) -> numpy arrays
    ``nll [B, S]``, ``router_logits [L, B * S, E]``, ``chosen [L, B * S,
    K]``, ``index_loss [L]`` (each layer's ``L_I``, the mean over the
    batch's rows), the ``balance`` term over all rows and layers, and
    ``index`` (device arrays: for each row and layer the index's ``(q_i,
    k_i, w)``); with ``grad_weights [B, S]`` also ``grads``, the gradient of
    ``sum(grad_weights * nll) + index_weight x mean over rows of sum over
    layers of L_I + router_weight x the balancing term`` with respect to
    ``first_layers(params)`` (a ``router_weight`` asks for one row: the
    term is over all rows' tokens together). ``weight_dtype`` (a dtype's
    name, "float8_e4m3fn"): every weight rounded to it as it is read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = {**_sizes(cfg), "lower": weight_dtype}
    fn = _jitted_nll(sz, grad_weights is not None)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, S = tokens.shape[0], tokens.shape[1] - 1
    if router_weight and B != 1:
        raise ValueError("the balancing term's gradient is taken a row at a "
                         "time: one row")
    positions = _positions(positions, B, S)
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
                rows.append(fn(params, tokens[b], positions[:, b], f, keys))
                continue
            *out, g = fn(params, tokens[b], positions[:, b], f, keys,
                         jnp.asarray(grad_weights[b], jnp.float32),
                         index_weight / B, float(router_weight))
            grads[:] = [g if not grads
                        else jax.tree_util.tree_map(jnp.add, grads[0], g)]
            rows.append(out)
        logits = np.concatenate([np.asarray(r[1]) for r in rows], axis=1)
        chosen = np.concatenate([np.asarray(r[2]) for r in rows], axis=1)
        bal = float(balance(sz, jnp.asarray(logits), jnp.asarray(chosen)))
    out = {"nll": np.stack([np.asarray(r[0]) for r in rows]),
           "router_logits": logits, "chosen": chosen,
           "index_loss": np.mean([np.asarray(r[3]) for r in rows], axis=0),
           "balance": bal, "index": [r[4] for r in rows]}
    if grad_weights is not None:
        out["grads"] = grads[0]
    return out


def logits(cfg, params, tokens, positions=None, forced_topk=None,
           forced_keys=None):
    """tokens [B, S] -> logits [B, S, V] float32 (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    B, S = tokens.shape
    positions = _positions(positions, B, S)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head(_run(sz, params, jnp.asarray(row, jnp.int32),
                       positions[:, b],
                       None if forced_topk is None
                       else forced_topk[:, b * S:(b + 1) * S],
                       None if forced_keys is None else forced_keys[:, b])[0],
                  params, sz) for b, row in enumerate(tokens)])


def loss_terms(cfg, params, tokens, positions=None, mask=None,
               forced_topk: Optional[Any] = None,
               forced_keys: Optional[Any] = None):
    """(cross entropy over the targets ``mask [B, S + 1]`` keeps, the
    layers' sum of ``L_I``, the balancing term) as differentiable functions
    of ``params`` (CPU sizes). The gradient flows through the gate weights
    and the router's probabilities, not through a choice."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, S = tokens.shape[0], tokens.shape[1] - 1
    positions = _positions(positions, B, S)
    with jax.default_matmul_precision("highest"):
        rows = [_nll(sz, params, row, positions[:, b],
                     None if forced_topk is None
                     else forced_topk[:, b * S:(b + 1) * S],
                     None if forced_keys is None else forced_keys[:, b])
                for b, row in enumerate(tokens)]
        nll = jnp.stack([r[0] for r in rows])
        weights = (jnp.ones_like(nll) if mask is None
                   else jnp.asarray(mask, jnp.float32)[:, 1:])
        return ((nll * weights).sum() / jnp.maximum(weights.sum(), 1),
                jnp.stack([r[3] for r in rows]).mean(0).sum(),
                balance(sz, jnp.concatenate([r[1] for r in rows], 1),
                        jnp.concatenate([r[2] for r in rows], 1)))


def chosen_keys(cfg, params, tokens, positions=None):
    """tokens [B, S] -> bool [L, B, S, S]: the reference's own choice of
    keys in every layer (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    topk = sz["index"][2]
    positions = _positions(positions, *tokens.shape)
    with jax.default_matmul_precision("highest"):
        per_row = []
        for b, row in enumerate(jnp.asarray(tokens, jnp.int32)):
            index = _run(sz, params, row, positions[:, b], None, None)[4]
            per_row.append(jnp.stack([
                plain_top_k(index_scores(q_i, k_i, w), 0, topk)
                for q_i, k_i, w in index]))
        return jnp.stack(per_row, axis=1)


def routed_layer(cfg, p, u, shared: bool = False):
    """One layer's MLP on its normed input u [T, h] (CPU sizes): the part
    of the experts ``cfg`` holds (``shared``: the model has no shared
    expert, and nothing is added for one)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return routed_mlp(jnp.asarray(u, jnp.float32), p, _sizes(cfg))[0]
