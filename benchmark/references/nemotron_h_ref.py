"""Plain reference of Nemotron-H (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B):
float32, ``jax.numpy`` only, matmuls at ``highest`` precision, no kernel,
the selective scan's recurrence **token by token** on each head's ``[P, N]``
state with its group's B and C, the gated norm a group at a time written
out, attention a masked softmax over every key in blocks of queries, the
mixture a loop over the held experts in the latent, the multi-token
prediction module as DeepSeek-V3's section 2.2 states it.

The equations (what the published ``config.json`` does not settle is listed
under ``assumed`` in ``benchmark/configs/nemotron-3-super-120b-a12b-c1.json``).
``N(x; w) = x / sqrt(mean(x^2) + 1e-5) * w``. ``h0 = embed[tokens]``; every
layer is ``h = h + F(N(h))`` with one ``F``; ``logits = N(h_L) W_head``.

- ``mamba``: ``[z | xBC | dt] = u W_in`` (widths ``d | d + 2 G N | H``, ``d =
  H P``); ``xBC = silu(conv(xBC) + b)``, causal, depthwise, 4 taps (``w_j``
  weighs ``xBC_{t-3+j}``, zeros before position 0); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; head ``h`` with group ``g = h // (H / G)``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t^g``, ``y_t = S_t C_t^g + D
  x_t``; ``y = w * GroupNorm(y * silu(z))``, each group of ``d / G`` channels
  divided by the root of its own mean square; ``F = y W_out``.
- ``attention``: q, k, v without bias and **without** rotation, causal
  softmax, 32 query heads on 2 key-value heads, scores over ``sqrt(head)``.
- ``moe``: ``s = sigmoid(u W_r)``; the choice is the ``K`` largest of ``s +
  b``; ``g = scale * s[choice] / (sum s[choice] + 1e-20)``; ``l = u W_dn``;
  ``r = sum_k g_k relu(l W1_e)^2 W2_e`` over the held experts among the
  choice; ``F = r W_up + relu(u S1)^2 S2``.
- The prediction module, for position ``i`` of the ``S`` that have both
  targets: ``h'_i = [N(embed[t_{i+1}]; w_e) ; N(h_{L,i}; w_h)] W_eh``, one
  attention layer and one mixture of the module's own weights as above,
  ``logits'_i = N(h''_i; w_m) W_head`` with the model's own embedding and
  head, the target ``t_{i+2}``. Loss = CE + ``mtp_loss_scale`` x CE'.

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields. On the chip it runs in blocks so that
it fits (``granite_ref.py``'s scheme: a layer at a time under
``jax.checkpoint``, the recurrence in blocks of blocks, rows in blocks of
``ROW_BLOCK``, queries in blocks of ``Q_BLOCK``).

``forced_topk`` ([routed layers and the module's, tokens, K] expert ids)
replaces the reference's own choice, so that a comparison is of the same
experts. ``grad_weights`` ([B, 2, S] float32) asks ``token_nll`` for the
gradient of ``sum(w[:, 0] * nll + w[:, 1] * nll')`` with respect to
``first_layers(params)``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

Q_BLOCK = 64
T_BLOCK = 32
ROW_BLOCK = 2048
_KIND = {"M": "mamba", "E": "moe", "*": "attention"}


def _sizes(cfg) -> Dict[str, Any]:
    return {"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim or cfg.hidden_size // cfg.num_heads,
            "eps": cfg.rms_norm_eps,
            "pattern": tuple(_KIND[c] for c in cfg.layer_pattern),
            "module": tuple(_KIND[c] for c in cfg.mtp_layer_pattern),
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
            "top_k": cfg.top_k, "scale": cfg.routed_scale,
            "renorm_eps": cfg.renorm_eps,
            "held": tuple(cfg.experts_held or (0, cfg.num_experts)),
            "mtp_loss_scale": cfg.mtp_loss_scale}


def _f32(v, sz):
    """A weight in float32; with ``sz["mantissa_bits"]`` rounded to that many
    mantissa bits where it is used (3 is float8 e4m3's), the gradient passing
    the rounding untouched: the reference one precision lower, with no second
    copy of the weights."""
    import jax
    import jax.numpy as jnp

    f = v.astype(jnp.float32)
    if not sz.get("mantissa_bits"):
        return f
    return f + jax.lax.stop_gradient(jax.lax.reduce_precision(
        f, exponent_bits=8, mantissa_bits=sz["mantissa_bits"]) - f)


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _blocks(T: int, block: int) -> int:
    return block if T % block == 0 else T


def _by_rows(fn, x, *more):
    """``fn`` over blocks of ``ROW_BLOCK`` rows under ``jax.checkpoint``."""
    import jax

    T = x.shape[0]
    rb = _blocks(T, ROW_BLOCK)
    split = tuple(a.reshape((T // rb, rb) + a.shape[1:]) for a in (x,) + more)
    _, out = jax.lax.scan(lambda _, a: (None, jax.checkpoint(fn)(*a)), None,
                          split)
    return out.reshape((T,) + out.shape[2:])


def _recurrence_rows(xbc, dt, A, H, P, G, N):
    """rows ``[x | B | C]`` [T, H P + 2 G N], dt [T, H] (after its softplus),
    A [H] -> (y [T, H P] without the skip, the last state [H, P, N]): one
    position after another, head ``h`` reading group ``h // (H / G)``."""
    import jax
    import jax.numpy as jnp

    T = xbc.shape[0]

    def step(S, xs):
        row, dt_t = xs
        x_t, B_t, C_t = jnp.split(row, (H * P, H * P + G * N))
        x_t = x_t.reshape(H, P)
        B_t = jnp.repeat(B_t.reshape(G, N), H // G, axis=0)
        C_t = jnp.repeat(C_t.reshape(G, N), H // G, axis=0)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t).reshape(H * P)

    def run(S, xs, levels):
        if len(levels) == 1:
            return jax.lax.scan(step, S, xs)
        n = levels[0]
        S, y = jax.lax.scan(
            jax.checkpoint(lambda S_, xb: run(S_, xb, levels[1:])), S,
            tuple(a.reshape((n, a.shape[0] // n) + a.shape[1:])
                  for a in xs))
        return S, y.reshape((-1,) + y.shape[2:])

    tb = T_BLOCK * T_BLOCK
    S, y = run(jnp.zeros((H, P, N), jnp.float32), (xbc, dt),
               (T // tb, T_BLOCK, T_BLOCK) if T % tb == 0 else (T,))
    return y, S


def mamba_mixer(u, p, sz):
    """u [T, hidden] float32 (normed) -> (F(u) [T, hidden], the state after
    the last position [H, P, N])."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, P, N, G = (sz["ssm_heads"], sz["ssm_head_dim"], sz["ssm_state"],
                  sz["ssm_groups"])
    d = H * P
    w_z, w_rest = p["m_in"][:, :d], p["m_in"][:, d:]

    @jax.checkpoint
    def project_taps_silu(u):
        xbc, dt = jnp.split(_by_rows(lambda ub: ub @ w_rest, u),
                            (d + 2 * G * N,), axis=-1)
        w = p["m_conv"]                               # [channels, taps]
        taps = w.shape[-1]
        v = jnp.zeros_like(xbc)
        for j in range(taps):                         # w_j on xbc_{t-(L-1)+j}
            back = taps - 1 - j
            shifted = jnp.concatenate(
                [jnp.zeros_like(xbc[:back]), xbc[:T - back]]) if back else xbc
            v = v + w[:, j] * shifted[:T]
        return jax.nn.silu(v + p["m_conv_bias"]), dt

    xbc, dt = project_taps_silu(u)
    y, S = _recurrence_rows(xbc, jax.nn.softplus(dt + p["dt_bias"]),
                            -jnp.exp(p["A_log"]), H, P, G, N)

    def skip_norm_out(yb, xbc_b, ub):
        yb = (yb + jnp.repeat(p["D"], P) * xbc_b[:, :d]) * jax.nn.silu(
            ub @ w_z)
        # a group of d / G channels at a time, each normed on its own
        groups = [g / jnp.sqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                               + sz["eps"])
                  for g in jnp.split(yb, G, axis=-1)]
        return (jnp.concatenate(groups, axis=-1) * p["m_norm"]) @ p["m_out"]

    return _by_rows(skip_norm_out, y, xbc, u), S


def attention(h, p, sz):
    """h [T, hidden] float32 (normed) -> F(h) [T, hidden]: no rotation, the
    scores over the root of the head size."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    H, KVH, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = (h @ p["wq"]).reshape(T, H, hd)
    k = jnp.repeat((h @ p["wk"]).reshape(T, KVH, hd), H // KVH, axis=1)
    v = jnp.repeat((h @ p["wv"]).reshape(T, KVH, hd), H // KVH, axis=1)
    qb = _blocks(T, Q_BLOCK)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                              # [qb, H, hd], [qb, 1]
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) / jnp.sqrt(1.0 * hd)
        sc = jnp.where((j <= i)[None], sc, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, hd), jnp.arange(T).reshape(T // qb, qb, 1)))
    return outs.reshape(T, H * hd) @ p["wo"]


def _relu2(u, w_up, w_down):
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


def latent_moe(u, p, sz, forced=None):
    """u [T, h] float32, the normed input of a mixture -> (the held experts'
    part through ``W_up`` plus the shared expert [T, h], router logits [T,
    E], selection scores ``s + b`` [T, E], chosen experts [T, K]). ``p``'s
    expert weights are the held experts'; every leaf of ``p`` is float32 but
    ``e_up`` and ``e_down``, which are upcast an expert at a time."""
    import jax
    import jax.numpy as jnp

    logits = u @ p["router"]
    s = jax.nn.sigmoid(logits)
    select = s + p["router_bias"]
    chosen = (jax.lax.top_k(select, sz["top_k"])[1] if forced is None
              else forced)
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    gates = sz["scale"] * gates / (gates.sum(-1, keepdims=True)
                                   + sz["renorm_eps"])
    first, count = sz["held"]
    lat = _by_rows(lambda ub: ub @ p["l_down"], u)

    @jax.checkpoint
    def weighted(lat, gate, w_up, w_down):
        return gate[:, None] * _relu2(lat, _f32(w_up, sz), _f32(w_down, sz))

    def one_expert(acc, ew):
        idx, w_up, w_down = ew
        gate = jnp.where(chosen == idx, gates, 0.0).sum(-1)        # [T]
        return acc + weighted(lat, gate, w_up, w_down), None

    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(lat),
                        (first + jnp.arange(count), p["e_up"], p["e_down"]))
    out = _by_rows(lambda rb, ub: rb @ p["l_up"]
                   + _relu2(ub, p["s_up"], p["s_down"]), r, u)
    return out, logits, select, chosen


_RAW = ("e_up", "e_down")


def _layer(x, stacked, own, forced, *, sz, kind: str, at: int):
    """x [T, h] float32; ``stacked``: the weights of the kind's layers, of
    which this is layer ``at``; ``own``: leaves that stand in for this
    layer's, or None -> (x, the scan's last state or None, (router logits,
    selection scores, chosen) or None)."""
    import jax.numpy as jnp

    p = {k: v[at] for k, v in stacked.items()}
    if own is not None:
        p.update(own)
    p = {k: v if k in _RAW else
         (v.astype(jnp.float32) if k == "router_bias" else _f32(v, sz))
         for k, v in p.items()}
    if kind == "attention":
        return x + attention(_rms_norm(x, p["attn_norm"], sz["eps"]), p,
                             sz), None, None
    if kind == "mamba":
        out, S = mamba_mixer(_rms_norm(x, p["op_norm"], sz["eps"]), p, sz)
        return x + out, S, None
    out, *routed = latent_moe(_rms_norm(x, p["mlp_norm"], sz["eps"]), p, sz,
                              forced)
    return x + out, None, tuple(routed)


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for, as one
    flat table: the embedding, the last norm, the head, the module's norms
    and joining matrix (``mtp_*``), and the first layer of each kind of the
    stack and of the module (``mtp_<kind>``), its leaves without the
    stack's axis and without the router's bias, which has no gradient."""
    def firsts(layers, prefix=""):
        return {prefix + kind: {k: v[0] for k, v in leaves.items()
                                if k != "router_bias"}
                for kind, leaves in layers.items()}

    out = {k: v for k, v in tree.items() if k not in ("layers", "mtp")}
    out["layers"] = firsts(tree["layers"])
    if "mtp" in tree:
        out.update({"mtp_" + k: v for k, v in tree["mtp"].items()
                    if k != "layers"})
        out["layers"].update(firsts(tree["mtp"]["layers"], "mtp_"))
    return out


def _walk(sz, x, layers, pattern, forced, lo, first, prefix=""):
    """The layers of ``pattern`` over x [T, h] -> (x, the scans' last
    states, the mixtures' (logits, select, chosen)); ``forced[lo + n]`` is
    the ``n``-th mixture's choice."""
    import jax

    taken = dict.fromkeys(layers, 0)
    states, routed = [], []
    for kind in pattern:
        at = taken[kind]
        taken[kind] += 1
        own = (first["layers"][prefix + kind]
               if first is not None and at == 0 else None)
        f = (forced[lo + len(routed)]
             if forced is not None and kind == "moe" else None)
        x, S, r = jax.checkpoint(partial(_layer, sz=sz, kind=kind, at=at))(
            x, layers[kind], own, f)
        if S is not None:
            states.append(S)
        if r is not None:
            routed.append(r)
    return x, states, routed


def _head_nll(x, targets, norm_w, head, sz):
    """x [T, h] -> the loss of ``targets`` [T]; the logits in blocks."""
    import jax
    import jax.numpy as jnp

    x = _rms_norm(x, norm_w, sz["eps"])

    def nll(xb, tb):
        lg = xb @ head
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[:, None], -1)[:, 0]

    return _by_rows(nll, x, targets)


def _nll(sz, params, row, forced=None, first=None):
    """row [S + 2] -> (next-token loss [S], the module's loss of the token
    after [S], the scans' last states [Lm, H, P, N], router logits, selection
    scores and chosen experts of the stack's mixtures and then the module's
    [Lr, S, .])."""
    import jax
    import jax.numpy as jnp

    top = first if first is not None else params
    module = params.get("mtp") if sz["module"] else None
    ahead = 2 if module is not None else 1
    embed = _f32(top["embed"], sz)
    head = _f32(top["lm_head"], sz)
    x, states, routed = _walk(sz, embed[row[:-ahead]], params["layers"],
                              sz["pattern"], forced, 0, first)
    nll = _head_nll(x, row[1:len(row) - ahead + 1],
                    _f32(top["final_norm"], sz), head, sz)
    more = jnp.zeros_like(nll)
    if module is not None:
        m = ({k[4:]: v for k, v in first.items() if k.startswith("mtp_")}
             if first is not None else module)
        joined = jnp.concatenate(
            [_rms_norm(embed[row[1:-1]], _f32(m["embed_norm"], sz),
                       sz["eps"]),
             _rms_norm(x, _f32(m["hidden_norm"], sz), sz["eps"])], axis=-1)
        h, _, routed_m = _walk(sz, joined @ _f32(m["join"], sz),
                               module["layers"], sz["module"], forced,
                               len(routed), first, "mtp_")
        routed += routed_m
        more = _head_nll(h, row[2:], _f32(m["final_norm"], sz), head, sz)
    logits, select, chosen = (jnp.stack(a) for a in zip(*routed))
    return (nll, more, jax.lax.stop_gradient(jnp.stack(states)), logits,
            select, chosen)


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, f, w):
        out = _nll(sz, p, row, f, first)
        return (w[0] * out[0]).sum() + (w[1] * out[1]).sum(), out

    def nll_and_grad(p, row, f, w):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, w)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, grad_weights=None,
              mantissa_bits=None) -> Dict[str, Any]:
    """tokens [B, S + 2] (``S + 1`` for a config without a module) -> numpy
    ``nll`` and ``mtp_nll`` [B, S], ``router_logits`` and ``select_scores``
    [Lr, B * S, E] and ``chosen`` [Lr, B * S, K] (the stack's mixtures in
    their order, then the module's), ``last_states`` [Lm, B, H, P, N],
    ``state_abs_max`` and the loss ``terms``; with ``grad_weights [B, 2, S]``
    also ``grads``, the gradient of ``sum(w[:, 0] * nll + w[:, 1] *
    mtp_nll)`` with respect to ``first_layers(params)``. ``forced_topk [Lr,
    B * S, K]`` replaces the choice; ``mantissa_bits``: every weight is
    rounded to that many where it is used."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = {**_sizes(cfg), "mantissa_bits": mantissa_bits}
    fn = _jitted_nll(sz, grad_weights is not None)
    tokens = jnp.asarray(tokens, jnp.int32)
    B = tokens.shape[0]
    if forced_topk is not None:
        forced_topk = jnp.asarray(forced_topk, jnp.int32)
        forced_topk = forced_topk.reshape(forced_topk.shape[0], B, -1,
                                          forced_topk.shape[-1])
    rows, grads = [], None
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            f = None if forced_topk is None else forced_topk[:, b]
            if grad_weights is None:
                rows.append(fn(params, tokens[b], f))
                continue
            *out, g = fn(params, tokens[b], f,
                         jnp.asarray(grad_weights[b], jnp.float32))
            rows.append(out)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)

    def joined(i, axis):
        return np.concatenate([np.asarray(r[i]) for r in rows], axis=axis)

    nll, more = (np.stack([np.asarray(r[i]) for r in rows]) for i in (0, 1))
    states = np.stack([np.asarray(r[2]) for r in rows], axis=1)
    ce, ce_more = float(nll.mean()), float(more.mean())
    out = {"nll": nll, "mtp_nll": more, "last_states": states,
           "state_abs_max": float(np.abs(states).max()),
           "router_logits": joined(3, 1), "select_scores": joined(4, 1),
           "chosen": joined(5, 1),
           "terms": {"cross_entropy": ce, "mtp_cross_entropy": ce_more,
                     "loss": ce + (sz["mtp_loss_scale"] * ce_more
                                   if sz["module"] else 0.0)}}
    if grad_weights is not None:
        out["grads"] = grads
    return out


def updated_bias(cfg, bias, counts):
    """The routers' biases [Lr, E] after a step that sent ``counts [Lr, E]``
    rows to each expert (numpy): an expert with fewer rows than its layer's
    mean gains ``bias_update_rate``, one with more loses it."""
    import numpy as np

    c = np.asarray(counts, np.float64)
    move = np.sign(c.mean(-1, keepdims=True) - c)
    return (np.asarray(bias, np.float32)
            + np.float32(cfg.bias_update_rate) * move.astype(np.float32))


def router_biases(cfg, params):
    """The routers' biases [Lr, E] (numpy): the stack's mixtures in their
    order, then the module's."""
    import numpy as np

    rows = [np.asarray(b, np.float32)
            for b in params["layers"]["moe"]["router_bias"]]
    if "mtp" in params and "moe" in params["mtp"]["layers"]:
        rows += [np.asarray(b, np.float32)
                 for b in params["mtp"]["layers"]["moe"]["router_bias"]]
    return np.stack(rows)


def logits(cfg, params, tokens):
    """tokens [B, S] -> the main model's logits [B, S, V] float32 (CPU
    sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _rms_norm(_walk(sz, params["embed"].astype(f32)[
                jnp.asarray(row, jnp.int32)], params["layers"],
                sz["pattern"], None, 0, None)[0],
                params["final_norm"].astype(f32), sz["eps"])
            @ params["lm_head"].astype(f32) for row in tokens])


def loss(cfg, params, tokens, forced_topk: Optional[Any] = None):
    """The whole loss (both heads) as one differentiable function of
    ``params`` (CPU sizes), and its two cross entropies."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    B = tokens.shape[0]
    if forced_topk is not None:
        forced_topk = jnp.asarray(forced_topk, jnp.int32).reshape(
            len(forced_topk), B, -1, cfg.top_k)
    with jax.default_matmul_precision("highest"):
        rows = [_nll(sz, params, tokens[b],
                     None if forced_topk is None else forced_topk[:, b])
                for b in range(B)]
    ce = jnp.stack([r[0] for r in rows]).mean()
    more = jnp.stack([r[1] for r in rows]).mean()
    return ce + (sz["mtp_loss_scale"] * more if sz["module"] else 0.0), (
        ce, more)


def mixer(cfg, p, u):
    """One scan layer's ``F`` on its normed input u [T, hidden] (CPU sizes)
    -> (F(u), the last state [H, P, N])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return mamba_mixer(jnp.asarray(u, jnp.float32),
                           {k: jnp.asarray(v, jnp.float32)
                            for k, v in p.items()}, _sizes(cfg))


def mixture(cfg, p, u, held=None):
    """One mixture's ``F`` on its normed input u [T, hidden] (CPU sizes),
    ``p`` one layer's leaves with the ``held=(first, count)`` experts'
    weights (all by default) -> F(u) [T, hidden]."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    if held is not None:
        sz["held"] = tuple(held)
    with jax.default_matmul_precision("highest"):
        return latent_moe(jnp.asarray(u, jnp.float32),
                          {k: jnp.asarray(v, jnp.float32)
                           for k, v in p.items()}, sz)[0]
