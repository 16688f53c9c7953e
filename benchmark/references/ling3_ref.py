"""Plain reference of Ling-3.0-flash-VL's language model
(inclusionAI/Ling-3.0-flash-VL, ``ling3``): float32, ``jax.numpy`` only,
matmuls at ``highest`` precision, no kernel, no chunk, no sort, no grouped
matmul, nothing from ``ray_tpu/ops/``; Kimi Delta Attention's recurrence
**token by token** (a ``lax.scan`` over positions on the float32 ``[128,
128]`` state of every head), the taps a loop, attention a masked softmax
over every key in blocks of queries, the mixture a loop over the experts
held.

The equations, from the catalog row's ``config`` and ``described_as`` (what
they do not settle is listed under ``assumed`` in
``benchmark/configs/ling-3.0-flash-vl-c1.json``). ``N(x; w) = x /
sqrt(mean(x^2) + 1e-6) * w``. ``h0 = embed[tokens]``; ``h = h +
Mixer(N(h))``; ``h = h + MLP(N(h))``; after the last layer ``N`` once more,
then the untied head.

- ``Mixer`` of a ``kda`` layer on ``u [T, hidden]``, ``H`` heads of ``K``
  key and ``V`` value channels (32, 128, 128): ``[q~ k~ v~ | f | b | a] = u
  W_in`` (widths ``H K, H K, H V | H K | H | H``); ``q, k, v =
  silu(taps(q~, k~, v~))``, a causal depthwise convolution of 4 taps with
  own taps for each channel and no bias (``w_j`` weighs the value ``3 - j``
  positions back, zeros before position 0); a head at a time ``q_t <- q_t /
  sqrt(|q_t|^2 + 1e-6) * K^-1/2``, ``k_t <- k_t / sqrt(|k_t|^2 + 1e-6)``;
  ``g_t = lower x sigmoid(exp(A_log[h]) (f_t + dt_bias))`` ``[H, K]``,
  ``lower`` -5; ``beta_t = sigmoid(b_t)``; ``S_t = S_{t-1} Diag(exp g_t) (I
  - beta_t k_t k_t^T) + beta_t v_t k_t^T`` with ``S [V, K]`` zero before
  the sequence (decay the state's columns, read ``S k_t``, add ``beta_t
  (v_t - S k_t) k_t^T``); ``o_t = S_t q_t``; ``y_t = w_n * o_t /
  sqrt(mean(o_t^2) + 1e-6) * sigmoid(a_t[h])`` a head at a time; ``Mixer =
  y W_out``.
- ``Mixer`` of an ``mla`` layer: ``q = u W_q`` ``[H, d_n + d_r]``; ``[c |
  k_r] = u W_kva``; ``[k_n | v] = N(c) W_kvb`` ``[H, d_n | d_v]``; rope
  (pairs ``(2i, 2i + 1)`` de-interleaved, then rotated as two halves, theta
  6e6) on q's last ``d_r`` and on the one shared ``k_r``; ``A_h =
  softmax(q_h [k_n,h | k_r]^T (d_n + d_r)^-1/2 + mask) v_h``; ``Mixer =
  concat_h(A_h sigmoid(u W_g)[h]) W_o``.
- ``MLP`` of a dense layer a SwiGLU; of a routed layer on ``u``: ``s =
  sigmoid(u W_r)``; the choice on ``s + b``: ``n_group`` groups of
  neighbours, a group's score the sum of its two largest ``s + b``, the
  ``topk_group`` best groups kept, the ``top_k`` largest ``s + b`` inside
  them; ``w_e = routed_scale s_e / (sum over the chosen of s + 1e-20)``;
  ``MLP = SwiGLU_shared(u) + sum over chosen e that are held of w_e
  SwiGLU_e(u)``, a loop over the held experts under a mask.
- Loss = cross entropy. After a step ``b_i += rate x sign(mean(c) -
  c_i)`` (``updated_bias``).

``forced_topk`` ([routed layers, tokens, K] expert ids) replaces the
reference's own choice of experts by the program's, the weights still the
reference's own scores (``olmoe_ref.py`` says why).

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields. On the chip it runs in blocks so
that it fits: a layer at a time under ``jax.checkpoint``, a KDA mixer
``HEAD_GROUP`` heads at a time, the recurrence in blocks of ``T_BLOCK``
blocks of ``T_BLOCK`` positions, projections, an expert and the head in
blocks of ``ROW_BLOCK`` tokens, attention in blocks of ``Q_BLOCK``
queries.

``grad_weights`` ([B, S] float32) asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` as well, with respect to the embedding, the
last norm, the head and the first layer of each kind (``first_layers``:
layer 0, the first routed KDA layer and the latent layer).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

Q_BLOCK = 64
T_BLOCK = 32
ROW_BLOCK = 2048
HEAD_GROUP = 4
L2_EPS = 1e-6


def _sizes(cfg) -> Dict[str, Any]:
    held = cfg.experts_held or (0, cfg.num_experts)
    return {"heads": cfg.num_heads, "eps": cfg.rms_norm_eps,
            "theta": cfg.rope_theta, "kv_rank": cfg.kv_lora_rank,
            "d_n": cfg.qk_nope_head_dim, "d_r": cfg.qk_rope_head_dim,
            "d_v": cfg.v_head_dim, "pattern": tuple(cfg.pattern),
            "linear_heads": cfg.linear_heads,
            "linear_key_dim": cfg.linear_key_dim,
            "linear_value_dim": cfg.linear_value_dim,
            "lower": cfg.kda_lower_bound,
            "top_k": cfg.top_k, "held": tuple(held),
            "groups": (cfg.n_group, cfg.topk_group),
            "routed_scale": cfg.routed_scale, "renorm_eps": cfg.renorm_eps}


def _f32(v, sz):
    """A weight in float32; with ``sz["mantissa_bits"]`` rounded to that
    many mantissa bits where it is used (``lax.reduce_precision`` at
    float32's exponent range), the gradient passing the rounding untouched:
    ``benchmark/tests/kda_moe_limits.py``'s reference one precision lower,
    with no second copy of the weights."""
    import jax
    import jax.numpy as jnp

    f = v.astype(jnp.float32)
    if not sz.get("mantissa_bits"):
        return f
    return f + jax.lax.stop_gradient(jax.lax.reduce_precision(
        f, exponent_bits=8, mantissa_bits=sz["mantissa_bits"]) - f)


def _norm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _blocks(T: int, block: int) -> int:
    """``block`` where ``T`` is whole blocks, else one block (CPU sizes)."""
    return block if T % block == 0 else T


def _by_rows(fn, x, *more):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of ``x`` (and of each of
    ``more``) under ``jax.checkpoint``."""
    import jax

    T = x.shape[0]
    rb = _blocks(T, ROW_BLOCK)
    split = tuple(a.reshape((T // rb, rb) + a.shape[1:]) for a in (x,) + more)
    _, out = jax.lax.scan(lambda _, a: (None, jax.checkpoint(fn)(*a)), None,
                          split)
    return out.reshape((T,) + out.shape[2:])


def recurrence(q, k, v, g, beta):
    """q, k [T, H, K] (as the taps leave them: not normed), v [T, H, V], g
    [T, H, K] (the decay's log a channel) and beta [T, H] -> (o [T, H, V],
    the state after the last position [H, V, K]): one position after
    another; a step norms its own q and k."""
    import jax
    import jax.numpy as jnp

    T, H, K = q.shape
    V = v.shape[-1]

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                            + L2_EPS)

    def step(S, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        q_t, k_t = unit(q_t) * K ** -0.5, unit(k_t)
        S = S * jnp.exp(g_t)[:, None, :]
        seen = jnp.einsum("hvk,hk->hv", S, k_t)
        S = S + (beta_t[:, None] * (v_t - seen))[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q_t)

    def run(S, xs, levels):
        """The steps over ``xs``; with more than one level, in
        ``levels[0]`` blocks under ``jax.checkpoint``, each run the same
        way: the backward keeps the state before each block of each level
        and runs the steps inside again."""
        if len(levels) == 1:
            return jax.lax.scan(step, S, xs)
        n = levels[0]
        S, y = jax.lax.scan(
            jax.checkpoint(lambda S_, xb: run(S_, xb, levels[1:])), S,
            tuple(a.reshape((n, a.shape[0] // n) + a.shape[1:])
                  for a in xs))
        return S, y.reshape((-1,) + y.shape[2:])

    tb = T_BLOCK * T_BLOCK
    S, o = run(jnp.zeros((H, V, K), jnp.float32), (q, k, v, g, beta),
               (T // tb, T_BLOCK, T_BLOCK) if T % tb == 0 else (T,))
    return o, S


def _taps_silu(x, w):
    """x [T, c], w [c, taps] -> silu of the causal depthwise convolution,
    a loop over the taps (``w_j`` on ``x_{t - (taps - 1) + j}``)."""
    import jax
    import jax.numpy as jnp

    T, taps = x.shape[0], w.shape[-1]
    v = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:back]), x[:T - back]]) if back else x
        v = v + w[:, j] * shifted[:T]
    return jax.nn.silu(v)


def kda_mixer(u, p, sz):
    """u [T, hidden] float32 (normed) -> (Mixer(u) [T, hidden], the state
    after the last position [H, V, K], the smallest g), ``HEAD_GROUP``
    heads at a time."""
    import jax
    import jax.numpy as jnp

    H, K, V = (sz["linear_heads"], sz["linear_key_dim"],
               sz["linear_value_dim"])
    hg = _blocks(H, HEAD_GROUP)
    n = H // hg
    hk, hv = H * K, H * V
    conv = 2 * hk + hv

    def cols(w, lo, width):
        """Columns ``lo : lo + H width`` of ``w`` a group of heads at a
        time: [n, rows, hg width]."""
        return jnp.moveaxis(w[:, lo:lo + H * width].reshape(
            w.shape[0], n, hg * width), 1, 0)

    w_in, taps = p["k_in"], p["k_conv"].T                  # [taps, channels]
    groups = {
        "q": cols(w_in, 0, K), "k": cols(w_in, hk, K),
        "v": cols(w_in, 2 * hk, V), "f": cols(w_in, conv, K),
        "b": cols(w_in, conv + hk, 1), "a": cols(w_in, conv + hk + H, 1),
        "taps_q": cols(taps, 0, K), "taps_k": cols(taps, hk, K),
        "taps_v": cols(taps, 2 * hk, V),
        "A_log": p["k_A_log"].reshape(n, hg),
        "dt_bias": p["k_dt_bias"].reshape(n, hg, K),
        "out": p["k_out"].reshape(n, hg * V, -1)}

    @jax.checkpoint
    def group(u, w):
        """-> (what the group's heads add to Mixer(u), their last states
        [hg, V, K], their smallest g); kept for the backward: u."""
        T = u.shape[0]

        def proj(name, width):
            return _taps_silu(_by_rows(lambda ub: ub @ w[name], u),
                              w["taps_" + name].T).reshape(T, hg, width)

        f = _by_rows(lambda ub: ub @ w["f"], u).reshape(T, hg, K)
        g = sz["lower"] * jax.nn.sigmoid(
            jnp.exp(w["A_log"])[:, None] * (f + w["dt_bias"]))
        o, S = recurrence(proj("q", K), proj("k", K), proj("v", V), g,
                          jax.nn.sigmoid(u @ w["b"]))

        def norm_gate_out(ob, ub):
            y = ob / jnp.sqrt(jnp.mean(jnp.square(ob), -1, keepdims=True)
                              + sz["eps"]) * p["k_norm"]
            y = y * jax.nn.sigmoid(ub @ w["a"])[..., None]
            return y.reshape(-1, hg * V) @ w["out"]

        return _by_rows(norm_gate_out, o, u), S, g.min()

    def add(out, w):
        part, S, least = group(u, w)
        return out + part, (S, least)

    out, (states, least) = jax.lax.scan(add, jnp.zeros_like(u), groups)
    return out, states.reshape(H, V, K), least.min()


def _rope(x, theta):
    """x [T, heads, d_r], positions 0..T-1: de-interleaved, then rotated
    as two halves."""
    import jax.numpy as jnp

    dr = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_attention(u, p, sz):
    """u [T, hidden] float32 (normed) -> Mixer(u) [T, hidden]."""
    import jax
    import jax.numpy as jnp

    T, H = u.shape[0], sz["heads"]
    dn, dr, dv, rank = sz["d_n"], sz["d_r"], sz["d_v"], sz["kv_rank"]
    q = _by_rows(lambda ub: ub @ p["wq"], u).reshape(T, H, dn + dr)
    ckv = u @ p["wkv_a"]
    kv = _by_rows(lambda cb: _norm(cb, p["kv_a_norm"], sz["eps"])
                  @ p["wkv_b"], ckv[:, :rank]).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], sz["theta"])], -1)
    k_r = _rope(ckv[:, None, rank:], sz["theta"])[:, 0]        # [T, d_r]
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5
    qb = _blocks(T, Q_BLOCK)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                              # [qb, H, d_n + d_r]
        sc = (jnp.einsum("qhd,khd->hqk", q_blk[..., :dn], k_n)
              + jnp.einsum("qhd,kd->hqk", q_blk[..., dn:], k_r)) * scale
        sc = jnp.where((j <= i)[None], sc, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, dn + dr),
        jnp.arange(T).reshape(T // qb, qb, 1)))
    attn = outs.reshape(T, H, dv) * jax.nn.sigmoid(u @ p["wg"])[..., None]
    return _by_rows(lambda ab: ab @ p["wo"], attn.reshape(T, H * dv))


def _swiglu(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def choose(select, sz):
    """select [T, E] (``s + b``) -> the chosen experts [T, K]: the
    ``top_k`` largest inside the ``topk_group`` groups whose two largest
    sum highest."""
    import jax
    import jax.numpy as jnp

    n_group, keep = sz["groups"]
    by_group = select.reshape(select.shape[0], n_group, -1)
    _, best = jax.lax.top_k(jax.lax.top_k(by_group, 2)[0].sum(-1), keep)
    kept = (best[:, :, None] == jnp.arange(n_group)[None, None]).any(1)
    inside = jnp.where(kept[:, :, None], by_group, -jnp.inf)
    return jax.lax.top_k(inside.reshape(select.shape), sz["top_k"])[1]


def routed_mlp(u, p, sz, forced=None, shared: bool = True):
    """u [T, h] float32, the normed input of the MLP -> (the shared expert
    (``shared``) + the held experts' part [T, h], router logits [T, E],
    the selection scores ``s + b`` [T, E], chosen experts [T, K]). ``p``'s
    expert weights are the held experts', any float dtype; the rest
    float32 (the bias float32 always)."""
    import jax
    import jax.numpy as jnp

    logits = u @ p["router"]
    scores = jax.nn.sigmoid(logits)
    select = jax.lax.stop_gradient(scores) + p["router_bias"].astype(
        jnp.float32)
    chosen = choose(select, sz) if forced is None else forced
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = sz["routed_scale"] * gates / (
        gates.sum(-1, keepdims=True) + sz["renorm_eps"])
    first, count = sz["held"]

    def one_expert(acc, ew):
        idx, w_gate, w_up, w_down = ew
        gate = jnp.where(chosen == idx, gates, 0.0).sum(-1)        # [T]
        return acc + _by_rows(
            lambda ub, gb: gb[:, None] * _swiglu(
                ub, _f32(w_gate, sz), _f32(w_up, sz), _f32(w_down, sz)),
            u, gate), None

    out = _by_rows(lambda ub: _swiglu(ub, p["s_gate"], p["s_up"],
                                      p["s_down"]),
                   u) if shared else jnp.zeros_like(u)
    out, _ = jax.lax.scan(
        one_expert, out,
        (first + jnp.arange(count), p["e_gate"], p["e_up"], p["e_down"]))
    return out, logits, select, chosen


_EXPERTS = ("e_gate", "e_up", "e_down")
_RAW = _EXPERTS + ("router_bias",)


def _layer(x, stacked, own, forced, *, sz, kind: str, at: int):
    """x [T, h] float32; ``stacked``: the weights of the kind's layers (any
    float dtype), of which this is layer ``at``; ``own``: leaves that stand
    in for this layer's, or None -> (x, (the rule's last state, its
    smallest g) or None for a latent layer, (router logits, selection
    scores, chosen experts) or None for a dense layer)."""
    p = {k: (v[at] if k in _RAW else _f32(v[at], sz))
         for k, v in stacked.items()}
    if own is not None:
        p.update({k: (v if k in _RAW else _f32(v, sz))
                  for k, v in own.items()})
    mixer, mlp = kind.split("+")
    rule = None
    if mixer == "mla":
        x = x + latent_attention(_norm(x, p["attn_norm"], sz["eps"]), p, sz)
    else:
        out, S, least = kda_mixer(_norm(x, p["op_norm"], sz["eps"]), p, sz)
        x, rule = x + out, (S, least)
    u = _norm(x, p["mlp_norm"], sz["eps"])
    if mlp == "dense":
        return x + _by_rows(lambda ub: _swiglu(
            ub, p["w_gate"], p["w_up"], p["w_down"]), u), rule, None
    out, logits, select, chosen = routed_mlp(u, p, sz, forced)
    return x + out, rule, (logits, select, chosen)


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, the head, and the first layer of each kind
    (its leaves without the stack's axis; a router's bias, which no
    gradient reaches, left out)."""
    return {**tree, "layers": {
        kind: {k: v[0] for k, v in leaves.items() if k != "router_bias"}
        for kind, leaves in tree["layers"].items()}}


def _run(sz, params, tokens, forced, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm [T,
    h], the KDA layers' states after the last position [Lk, H, V, K] and
    their smallest g, which no gradient passes, router logits and
    selection scores [Lr, T, E], chosen experts [Lr, T, K])."""
    import jax
    import jax.numpy as jnp

    x = _f32((first or params)["embed"][tokens], sz)
    taken = dict.fromkeys(params["layers"], 0)
    states, least, logits, select, chosen = [], [], [], [], []
    for kind in sz["pattern"]:
        at = taken[kind]
        taken[kind] += 1
        routed = kind.endswith("+moe")
        x, rule, router = jax.checkpoint(
            partial(_layer, sz=sz, kind=kind, at=at))(
            x, params["layers"][kind],
            first["layers"][kind] if first and at == 0 else None,
            None if forced is None or not routed else forced[len(logits)])
        if rule is not None:
            states.append(rule[0])
            least.append(rule[1])
        if router is not None:
            logits.append(router[0])
            select.append(router[1])
            chosen.append(router[2])
    stop = jax.lax.stop_gradient
    return (x, stop(jnp.stack(states)), stop(jnp.stack(least).min()),
            jnp.stack(logits), stop(jnp.stack(select)), jnp.stack(chosen))


def _head_nll(x, targets, params, sz):
    """x [T, h] -> the next-token loss [T]; the logits in blocks."""
    import jax
    import jax.numpy as jnp

    x = _norm(x, _f32(params["final_norm"], sz), sz["eps"])
    head = _f32(params["lm_head"], sz)

    def nll(xb, tb):
        lg = xb @ head
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[:, None], -1)[:, 0]

    return _by_rows(nll, x, targets)


def _nll(sz, params, row, forced, first=None):
    """row [S + 1] -> (next-token loss [S], then ``_run``'s other
    results)."""
    x, *rest = _run(sz, params, row[:-1], forced, first)
    return (_head_nll(x, row[1:], first or params, sz), *rest)


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape. ``with_grad``: the row's
    weights ``w [S]`` too, and the gradient of ``sum(w * nll)`` back."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, f, w):
        nll, *rest = _nll(sz, p, row, f, first)
        return (w * nll).sum(), (nll, *rest)

    def nll_and_grad(p, row, f, w):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, w)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, grad_weights=None,
              mantissa_bits=None) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy ``nll [B, S]``, ``last_states`` (the KDA
    layers' states after a sequence's last position [Lk, B, H, V, K]),
    ``state_abs_max``, ``log_decay_min``, ``router_logits`` and
    ``select_scores`` ``[Lr, B * S, E]``, ``chosen [Lr, B * S, K]`` and the
    loss ``terms`` (floats); with ``grad_weights [B, S]`` also ``grads``,
    the gradient of ``sum(grad_weights * nll)`` with respect to
    ``first_layers(params)``. ``forced_topk [Lr, B * S, K]``: the choices
    of experts, laid out as the program lays its tokens, row after row.
    ``mantissa_bits``: every weight is rounded to that many where it is
    used (``_f32``). The loss has no router term."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = {**_sizes(cfg), "mantissa_bits": mantissa_bits}
    fn = _jitted_nll(sz, grad_weights is not None)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, S = tokens.shape[0], tokens.shape[1] - 1
    if forced_topk is not None:
        forced_topk = jnp.asarray(forced_topk, jnp.int32)
    rows, grads = [], None
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            f = (None if forced_topk is None
                 else forced_topk[:, b * S:(b + 1) * S])
            if grad_weights is None:
                rows.append(fn(params, tokens[b], f))
                continue
            *out, g = fn(params, tokens[b], f,
                         jnp.asarray(grad_weights[b], jnp.float32))
            rows.append(out)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
    nll = np.stack([np.asarray(r[0]) for r in rows])
    states = np.stack([np.asarray(r[1]) for r in rows], axis=1)
    ce = float(nll.mean())
    out = {"nll": nll, "last_states": states,
           "state_abs_max": float(np.abs(states).max()),
           "log_decay_min": float(min(float(r[2]) for r in rows)),
           "router_logits": np.concatenate(
               [np.asarray(r[3]) for r in rows], axis=1),
           "select_scores": np.concatenate(
               [np.asarray(r[4]) for r in rows], axis=1),
           "chosen": np.concatenate([np.asarray(r[5]) for r in rows], axis=1),
           "terms": {"cross_entropy": ce, "loss": ce}}
    if grad_weights is not None:
        out["grads"] = grads
    return out


def router_biases(cfg, params):
    """The routers' biases [Lr, E] float32 (numpy), in layer order."""
    import numpy as np

    taken = dict.fromkeys(params["layers"], 0)
    rows = []
    for kind in cfg.pattern:
        at = taken[kind]
        taken[kind] += 1
        if kind.endswith("+moe"):
            rows.append(np.asarray(
                params["layers"][kind]["router_bias"][at], np.float32))
    return np.stack(rows)


def updated_bias(cfg, bias, counts):
    """The rule: ``b_i += rate x sign(mean(c) - c_i)``, [Lr, E] numpy."""
    import numpy as np

    c = np.asarray(counts, np.float32)
    return bias + np.float32(cfg.bias_update_rate) * np.sign(
        c.mean(-1, keepdims=True) - c)


def logits(cfg, params, tokens, forced_topk=None):
    """tokens [B, S] -> logits [B, S, V] float32 (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    S = jnp.asarray(tokens).shape[1]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _norm(_run(sz, params, jnp.asarray(row, jnp.int32),
                       None if forced_topk is None
                       else forced_topk[:, b * S:(b + 1) * S])[0],
                  params["final_norm"].astype(jnp.float32), sz["eps"])
            @ params["lm_head"].astype(jnp.float32)
            for b, row in enumerate(tokens)])


def loss(cfg, params, tokens, forced_topk: Optional[Any] = None):
    """The whole loss as one differentiable function of ``params`` (CPU
    sizes). The gradient flows through the gate weights and the router's
    scores, not through the choice of experts."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[1] - 1
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _nll(sz, params, row, None if forced_topk is None
                 else forced_topk[:, b * S:(b + 1) * S])[0]
            for b, row in enumerate(tokens)]).mean()


def _floats(p):
    import jax.numpy as jnp

    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def mixer(cfg, p, u):
    """One KDA layer's mixer on its normed input u [T, hidden] (CPU sizes)
    -> (Mixer(u), the last state [H, V, K])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return kda_mixer(jnp.asarray(u, jnp.float32), _floats(p),
                         _sizes(cfg))[:2]


def latent_layer(cfg, p, u):
    """One latent layer's mixer on its normed input u [T, hidden] (CPU
    sizes)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return latent_attention(jnp.asarray(u, jnp.float32), _floats(p),
                                _sizes(cfg))


def routed_layer(cfg, p, u, shared: bool = True):
    """One layer's MLP on its normed input u [T, h] (CPU sizes): the shared
    expert (``shared``) and the part of the experts ``cfg`` holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return routed_mlp(jnp.asarray(u, jnp.float32), _floats(p),
                          _sizes(cfg), shared=shared)[0]
