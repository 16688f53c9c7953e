"""Plain reference of the Llama-shaped decoder (Qwen2.5, Mistral):
float32, ``jax.numpy`` only, no kernel, no cache, no batching tricks,
matmuls at ``highest`` precision. It follows the published description
(pre-norm RMSNorm, rotary embedding in the half-split convention of the
Hugging Face implementations, grouped-query causal attention, SwiGLU,
optional q/k/v bias, optional tied output head).

It shares nothing with ``ray_tpu/models`` but the layout of the
parameter pytree it is handed: ``embed [V, h]``, ``layers`` stacked on a
leading layer axis (``attn_norm, wq, wk, wv, wo, mlp_norm, w_gate, w_up,
w_down`` and, with bias, ``bq, bk, bv``), ``final_norm`` and, untied,
``lm_head [h, V]``. Weights are upcast one layer at a time, so the
reference fits beside a serving engine or a train state.

Departures from the published models: none in the mathematics. The
weights are seeded random, not trained.
"""

from __future__ import annotations

from typing import Any, Dict, List

Q_BLOCK = 512
VOCAB_BLOCK = 32768


def _sizes(cfg) -> Dict[str, Any]:
    """The few sizes needed, from the program's config object or from a
    dict with the same names."""
    get = (lambda k: getattr(cfg, k)) if not isinstance(cfg, dict) else cfg.get
    hd = get("head_dim") or get("hidden_size") // get("num_heads")
    return {"heads": get("num_heads"), "kv_heads": get("num_kv_heads"),
            "head_dim": hd, "eps": get("rms_norm_eps"),
            "theta": get("rope_theta"), "tied": bool(get("tie_embeddings"))}


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _rope(x, theta):
    """x [T, H, hd], positions 0..T-1, half-split rotation."""
    import jax.numpy as jnp

    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, sz):
    """x [T, h] float32; p: this layer's weights (any float dtype)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    p = {k: v.astype(f32) for k, v in p.items()}
    T = x.shape[0]
    H, KVH, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    h1 = _rms_norm(x, p["attn_norm"], sz["eps"])
    q, k, v = h1 @ p["wq"], h1 @ p["wk"], h1 @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(T, H, hd), sz["theta"])
    k = _rope(k.reshape(T, KVH, hd), sz["theta"])
    v = v.reshape(T, KVH, hd)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    outs = []
    for s in range(0, T, Q_BLOCK):                 # query blocks: memory
        e = min(s + Q_BLOCK, T)
        sc = jnp.einsum("qhd,khd->hqk", q[s:e], k[:e]) / jnp.sqrt(f32(hd))
        mask = (jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                               v[:e]))
    attn = jnp.concatenate(outs, 0).reshape(T, H * hd)
    x = x + attn @ p["wo"]
    h2 = _rms_norm(x, p["mlp_norm"], sz["eps"])
    return x + (jax.nn.silu(h2 @ p["w_gate"]) * (h2 @ p["w_up"])) @ p["w_down"]


def _head(x, params, sz):
    """x [n, h] float32 -> logits [n, V] float32, by blocks of the
    vocabulary."""
    import jax.numpy as jnp

    x = _rms_norm(x, params["final_norm"].astype(jnp.float32), sz["eps"])
    if sz["tied"]:
        w, V = params["embed"], params["embed"].shape[0]
        blocks = [x @ w[s:s + VOCAB_BLOCK].astype(jnp.float32).T
                  for s in range(0, V, VOCAB_BLOCK)]
    else:
        w, V = params["lm_head"], params["lm_head"].shape[1]
        blocks = [x @ w[:, s:s + VOCAB_BLOCK].astype(jnp.float32)
                  for s in range(0, V, VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)


def _jitted(sz):
    import jax

    key = tuple(sorted(sz.items()))
    if key not in _JIT:
        _JIT[key] = (jax.jit(lambda x, p: _layer(x, p, sz)),
                     jax.jit(lambda x, params: _head(x, params, sz)))
    return _JIT[key]


_JIT: Dict[Any, Any] = {}


def hidden_states(cfg, params, tokens):
    """tokens [T] -> final hidden states [T, h] float32, before the
    last norm."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    layer_j, _ = _jitted(sz)
    n_layers = params["layers"]["wq"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for l in range(n_layers):
            x = layer_j(x, {k: v[l] for k, v in params["layers"].items()})
    return x


def logits_at(cfg, params, tokens, positions):
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    _, head_j = _jitted(sz)
    x = hidden_states(cfg, params, tokens)
    with jax.default_matmul_precision("highest"):
        return head_j(x[jnp.asarray(positions)], params)


def greedy_regret(cfg, params, prompt: List[int], produced: List[int],
                  pad_to: int) -> Dict[str, float]:
    """How far each token the engine produced (greedy) lies below the
    reference's best, in logits: max_v ref[t, v] - ref[t, produced[t]],
    with the engine's own earlier tokens as context (teacher forcing).
    A correct engine differs from the reference by rounding only, so the
    regret is 0 except between near-ties; a wrong mask, page, position or
    weight gives the regret of a random token, several standard
    deviations of the logits."""
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt) + list(produced[:-1])
    n = len(seq)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds pad_to={pad_to}")
    toks = jnp.asarray(seq + [0] * (pad_to - n), jnp.int32)   # causal: the
    pos = list(range(len(prompt) - 1, n))      # padding cannot reach back
    lg = np.asarray(logits_at(cfg, params, toks, pos))
    regret = lg.max(axis=-1) - lg[np.arange(len(pos)), np.asarray(produced)]
    return {"max_regret": float(regret.max()),
            "mean_regret": float(regret.mean()),
            "logit_std": float(lg.std()), "positions": len(pos),
            "exact": int((regret == 0).sum())}


def token_nll(cfg, params, tokens):
    """Next-token cross-entropy at every position of tokens [B, S+1] (a
    numpy array): a numpy array [B, S] float32, one row at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows = []
    for row in np.asarray(tokens):
        lg = logits_at(cfg, params, jnp.asarray(row[:-1], jnp.int32),
                       list(range(len(row) - 1)))
        rows.append(np.asarray(
            jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, jnp.asarray(row[1:], jnp.int32)[:, None], -1)[:, 0]))
    return np.stack(rows)
