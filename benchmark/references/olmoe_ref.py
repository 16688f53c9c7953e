"""Plain reference of OLMoE (allenai/OLMoE-1B-7B): float32, ``jax.numpy``
only, matmuls at ``highest`` precision, no kernel, no sort, no grouped
matmul. It follows transformers' ``OlmoeForCausalLM``: the Llama-shaped
decoder of ``llama_ref.py`` with an RMSNorm over the whole q and k
projection vectors before the split into heads, and every MLP a mixture
of experts: router logits, softmax over all experts in float32, the
``top_k`` largest, gate weights not renormalised (unless the config says
so), each expert a SwiGLU. Every expert runs over every token and a mask
keeps the chosen ones: a loop over the experts, nothing dropped.

Loss = cross entropy + ``router_aux_coef`` x load-balancing loss
(transformers' ``load_balancing_loss_func``: all layers' router logits
concatenated; E * sum over experts of (mean over tokens and choices of
the one-hot choice, summed over the k choices) x (mean router
probability)) + ``router_z_coef`` x router z-loss (mean over tokens and
layers of logsumexp(router logits)^2; OLMoE paper, arXiv:2409.02060).

``forced_topk`` ([L, tokens, K] expert ids) replaces the reference's own
choice of experts by the program's, gate weights still the reference's
own probabilities: with seeded random weights the 8th and 9th largest
router logits lie within a bf16 rounding of each other for a share of
tokens, and a comparison of what follows must not hang on which one won.

It shares nothing with ``ray_tpu/models`` or ``ray_tpu/ops`` but the
layout of the parameter pytree: ``llama_ref``'s, with ``router [L, h,
E]``, ``e_gate``, ``e_up [L, E, h, f]``, ``e_down [L, E, f, h]``,
``q_norm`` and ``k_norm`` in place of the dense MLP. Weights are upcast
one layer, and inside it one expert, at a time. Departures from the
published model: none in the mathematics (the z-loss is the paper's, not
transformers'); the weights are seeded random.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.references.llama_ref import Q_BLOCK, _head, _rms_norm, _rope


def _sizes(cfg) -> Dict[str, Any]:
    get = (lambda k: getattr(cfg, k)) if not isinstance(cfg, dict) else cfg.get
    hd = get("head_dim") or get("hidden_size") // get("num_heads")
    return {"heads": get("num_heads"), "kv_heads": get("num_kv_heads"),
            "head_dim": hd, "eps": get("rms_norm_eps"),
            "theta": get("rope_theta"), "tied": bool(get("tie_embeddings")),
            "top_k": get("top_k"), "renorm": bool(get("norm_topk_prob")),
            "aux_coef": get("router_aux_coef"), "z_coef": get("router_z_coef")}


def _layer(x, p, sz, forced):
    """x [T, h] float32; p: one layer's weights (any float dtype);
    forced: None or [T, K] expert ids. -> (x, router logits [T, E],
    chosen experts [T, K])."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    experts = {k: p[k] for k in ("e_gate", "e_up", "e_down")}
    p = {k: v.astype(f32) for k, v in p.items() if k not in experts}
    T = x.shape[0]
    H, KVH, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    h1 = _rms_norm(x, p["attn_norm"], sz["eps"])
    q = _rms_norm(h1 @ p["wq"], p["q_norm"], sz["eps"])
    k = _rms_norm(h1 @ p["wk"], p["k_norm"], sz["eps"])
    v = h1 @ p["wv"]
    q = _rope(q.reshape(T, H, hd), sz["theta"])
    k = _rope(k.reshape(T, KVH, hd), sz["theta"])
    v = v.reshape(T, KVH, hd)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    outs = []
    for s in range(0, T, Q_BLOCK):
        e = min(s + Q_BLOCK, T)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e]) / jnp.sqrt(f32(hd))
        mask = (jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                               v[:e]))
    x = x + jnp.concatenate(outs, 0).reshape(T, H * hd) @ p["wo"]

    h2 = _rms_norm(x, p["mlp_norm"], sz["eps"])
    logits = h2 @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    if forced is None:
        gates, chosen = jax.lax.top_k(probs, sz["top_k"])
    else:
        chosen = forced
        gates = jnp.take_along_axis(probs, chosen, axis=-1)
    if sz["renorm"]:
        gates = gates / gates.sum(-1, keepdims=True)

    def one_expert(acc, ew):
        idx, w_gate, w_up, w_down = ew
        gate = jnp.where(chosen == idx, gates, 0.0).sum(-1)        # [T]
        y = (jax.nn.silu(h2 @ w_gate.astype(f32))
             * (h2 @ w_up.astype(f32))) @ w_down.astype(f32)
        return acc + gate[:, None] * y, None

    n_experts = logits.shape[-1]
    moe, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(n_experts), experts["e_gate"], experts["e_up"],
         experts["e_down"]))
    return x + moe, logits, chosen


def _run(sz, params, tokens, forced):
    """One sequence: tokens [T] -> (hidden states before the last norm
    [T, h], router logits [L, T, E], chosen experts [L, T, K])."""
    import jax.numpy as jnp

    x = params["embed"][tokens].astype(jnp.float32)
    logits, chosen = [], []
    for l in range(params["layers"]["wq"].shape[0]):
        x, lg, ch = _layer(
            x, {k: v[l] for k, v in params["layers"].items()}, sz,
            None if forced is None else forced[l])
        logits.append(lg)
        chosen.append(ch)
    return x, jnp.stack(logits), jnp.stack(chosen)


def _nll(sz, params, row, forced):
    """row [S + 1] -> (next-token loss [S], router logits, chosen)."""
    import jax
    import jax.numpy as jnp

    x, logits, chosen = _run(sz, params, row[:-1], forced)
    lg = _head(x, params, sz)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, row[1:, None], -1)[:, 0]
    return nll, logits, chosen


def _terms(sz, nll, logits, chosen):
    """The loss and its three terms from per-position losses [B, S],
    router logits [L, n, E] and chosen experts [L, n, K]."""
    import jax
    import jax.numpy as jnp

    E = logits.shape[-1]
    flat = logits.reshape(-1, E)
    one_hot = jax.nn.one_hot(chosen.reshape(-1, sz["top_k"]), E)
    share = one_hot.mean(0).sum(0)          # [E]: sums to K over experts
    prob = jax.nn.softmax(flat, -1).mean(0)
    balance = E * jnp.sum(share * prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(flat, -1)))
    ce = nll.mean()
    return {"cross_entropy": ce, "load_balance": balance, "router_z": z,
            "loss": ce + sz["aux_coef"] * balance + sz["z_coef"] * z}


def _rows(nll_fn, tokens, forced):
    """tokens [B, S + 1] -> (nll [B, S], router logits [L, B * S, E],
    chosen experts [L, B * S, K]), one row at a time; forced is indexed
    as the program lays its tokens out, row after row."""
    import jax.numpy as jnp

    S = tokens.shape[1] - 1
    out = [nll_fn(tokens[b],
                  None if forced is None else forced[:, b * S:(b + 1) * S])
           for b in range(tokens.shape[0])]
    return (jnp.stack([o[0] for o in out]),
            jnp.concatenate([o[1] for o in out], axis=1),
            jnp.concatenate([o[2] for o in out], axis=1))


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz):
    """The per-row function compiled once a shape: at published widths
    the cell cannot wait for it to run eagerly."""
    import jax

    key = tuple(sorted(sz.items()))
    if key not in _JIT:
        _JIT[key] = jax.jit(lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy arrays ``nll [B, S]``, ``router_logits
    [L, B * S, E]``, ``chosen [L, B * S, K]``, and the loss ``terms``
    (floats) computed from them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = _sizes(cfg)
    fn = _jitted_nll(sz)
    if forced_topk is not None:
        forced_topk = jnp.asarray(forced_topk, jnp.int32)
    with jax.default_matmul_precision("highest"):
        nll, logits, chosen = _rows(
            lambda row, f: fn(params, row, f),
            jnp.asarray(tokens, jnp.int32), forced_topk)
        terms = _terms(sz, nll, logits, chosen)
    return {"nll": np.asarray(nll), "router_logits": np.asarray(logits),
            "chosen": np.asarray(chosen),
            "terms": {k: float(v) for k, v in terms.items()}}


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
    sizes): ``jax.grad(lambda p: olmoe_ref.loss(cfg, p, tokens))``. The
    gradient flows through the gate weights and the router's
    probabilities, not through the choice of experts."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        nll, lg, chosen = _rows(
            lambda row, f: _nll(sz, params, row, f),
            jnp.asarray(tokens, jnp.int32), forced_topk)
        return _terms(sz, nll, lg, chosen)["loss"]
