"""Plain reference of Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct,
``qwen3_next``): float32, ``jax.numpy`` only, matmuls at ``highest``
precision, no kernel, no sort, no grouped matmul; the gated delta rule's
recurrence **token by token** (a ``lax.scan`` over positions on the float32
``[128, 128]`` state of every value head), the convolution a loop over its
taps, attention a masked softmax over every key in blocks of queries, the
mixture a loop over the experts held.

The equations, from the model's ``config.json`` and transformers'
``modeling_qwen3_next.py`` (what the config's keys do not settle is listed
under ``assumed`` in ``benchmark/configs/qwen3-next-80b-a3b-c1.json``).
``N(x; w) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)``. ``h0 = embed[tokens]``;
``h = h + Mixer(N(h))``; ``h = h + MoE(N(h))``; after the last layer ``N``
once more, then the untied head.

- ``Mixer`` of a ``linear_attention`` layer on ``u [T, hidden]``, ``Hk`` key
  heads of size ``K`` under ``Hv`` value heads of size ``V`` (16 under 32,
  128 and 128): ``[z | q~ k~ v~ | a | b] = u W_in`` (widths ``Hv V | Hk K,
  Hk K, Hv V | Hv | Hv``); ``q, k, v = silu(conv(q~, k~, v~))``, a causal
  depthwise convolution of 4 taps with own taps for each channel and no bias
  (``w_j`` weighs the value ``3 - j`` positions back, zeros before position
  0); a key head at a time ``q_t <- q_t / sqrt(|q_t|^2 + 1e-6) * K^-1/2``,
  ``k_t <- k_t / sqrt(|k_t|^2 + 1e-6)``; ``g_t = -exp(A_log) softplus(a_t +
  dt_bias)``; ``beta_t = sigmoid(b_t)``; value head ``i`` reads key head ``i
  // (Hv / Hk)``; a value head at a time ``S_t = exp(g_t) S_{t-1} (I - beta_t
  k_t k_t^T) + beta_t v_t k_t^T`` with ``S [V, K]`` zero before the
  sequence, computed as the module's own recurrence does (decay the state,
  read ``S k_t``, add ``beta_t (v_t - S k_t) k_t^T``); ``o_t = S_t q_t``;
  ``y_t = w_n * o_t / sqrt(mean(o_t^2) + 1e-6) * silu(z_t)`` a head at a
  time (a plain weight, not ``1 + w``); ``Mixer = y W_out``.
- ``Mixer`` of a ``full_attention`` layer, ``H`` query heads over ``KVH``
  key/value heads of size ``d`` (16 over 2, 256): ``u W_q`` gives each head
  ``2 d`` columns, its query and then its gate; ``q = N_d(q)``, ``k = N_d(u
  W_k)`` a head at a time; rope, half-split rotation of the first
  ``partial_rotary_factor d`` dims at ``rope_theta``, the rest passed;
  ``A_h = softmax(q_h k_{h // (H / KVH)}^T d^-1/2 + mask) v``, mask ``j <=
  i``; ``Mixer = concat_h(A_h * sigmoid(gate_h)) W_o``. No bias anywhere.
- ``MoE`` on ``u [T, hidden]``: ``s = softmax(u W_r)`` over all experts in
  float32, ``T`` the ``top_k`` largest, ``w_e = s_e / sum_T s``; ``MoE =
  sigmoid(u . w_sg) SwiGLU_shared(u) + sum over e in T that are held of w_e
  SwiGLU_e(u)``. Every held expert runs over every token and a mask keeps
  the chosen ones: a loop over the experts. An expert that is not held adds
  nothing.
- Loss = cross entropy + ``router_aux_coef`` x transformers'
  ``load_balancing_loss_func`` over all experts and all layers.

Departures from the published implementation: (1) the projections of a
linear layer are the columns of one matrix ``g_in`` in the order above and
the three convolutions the rows of one ``g_conv`` (the same functions: the
published ``in_proj_qkvz`` interleaves them a key head at a time); (2) the
published kernels compute the rule in chunks of 64: the recurrence above is
what they compute; (3) everything is float32; (4) one sequence at a time,
unpacked; (5) no multi-token prediction module.

``forced_topk`` ([layers, tokens, K] expert ids) replaces the reference's
own choice of experts by the program's, the gate weights still the
reference's own probabilities (``olmoe_ref.py`` says why).

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields: ``params["layers"][kind][name]``
stacked over the layers of a kind (``linear``, ``full``), ``cfg.pattern``
the kind of each layer. On the chip it runs in blocks so that it fits: a
layer at a time under ``jax.checkpoint``, a linear layer's mixer
``HEAD_GROUP`` value heads at a time, the recurrence in blocks of
``T_BLOCK`` blocks of ``T_BLOCK`` positions, the projections, an expert and
the head in blocks of ``ROW_BLOCK`` tokens, attention in blocks of
``Q_BLOCK`` queries.

``grad_weights`` ([B, S] float32) asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` as well (with ``router_term`` plus
``router_aux_coef`` x the balancing term: the train step's loss under
weights of ``1 / S``), with respect to the embedding, the last norm, the
head and the first layer of each kind (``first_layers``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

Q_BLOCK = 64
T_BLOCK = 32
ROW_BLOCK = 2048
HEAD_GROUP = 8
L2_EPS = 1e-6


def _sizes(cfg) -> Dict[str, Any]:
    held = cfg.experts_held or (0, cfg.num_experts)
    hd = cfg.head_dim or cfg.hidden_size // cfg.num_heads
    return {"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": hd, "eps": cfg.rms_norm_eps,
            "theta": cfg.rope_theta,
            "rotated": int(hd * cfg.partial_rotary_factor),
            "pattern": tuple(cfg.pattern),
            "linear_heads": cfg.linear_heads,
            "linear_key_heads": cfg.linear_key_heads,
            "linear_key_dim": cfg.linear_key_dim,
            "linear_value_dim": cfg.linear_value_dim,
            "top_k": cfg.top_k, "held": tuple(held),
            "aux_coef": cfg.router_aux_coef}


def _f32(v, sz):
    """A weight in float32; with ``sz["mantissa_bits"]`` rounded to that
    many mantissa bits where it is used (``lax.reduce_precision`` at
    float32's exponent range: 3 is float8 e4m3's mantissa), the gradient
    passing the rounding untouched: ``benchmark/tests/delta_moe_limits.py``'s
    reference one precision lower, with no second copy of the weights."""
    import jax
    import jax.numpy as jnp

    f = v.astype(jnp.float32)
    if not sz.get("mantissa_bits"):
        return f
    return f + jax.lax.stop_gradient(jax.lax.reduce_precision(
        f, exponent_bits=8, mantissa_bits=sz["mantissa_bits"]) - f)


def _norm(x, w, eps):
    """The block's norm: zero-centred, ``1 + w``."""
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _blocks(T: int, block: int) -> int:
    """``block`` where ``T`` is whole blocks, else one block (CPU sizes)."""
    return block if T % block == 0 else T


def _by_rows(fn, x, *more):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of ``x`` (and of each of
    ``more``) under ``jax.checkpoint``: what ``fn`` builds for a block is
    freed before the next and built again in the backward."""
    import jax

    T = x.shape[0]
    rb = _blocks(T, ROW_BLOCK)
    split = tuple(a.reshape((T // rb, rb) + a.shape[1:]) for a in (x,) + more)
    _, out = jax.lax.scan(lambda _, a: (None, jax.checkpoint(fn)(*a)), None,
                          split)
    return out.reshape((T,) + out.shape[2:])


def _recurrence_rows(qkv, g, beta, Hk, H, K, V):
    """Rows ``[q | k | v]`` as the taps leave them (qkv [T, 2 Hk K + H V]:
    ``Hk`` heads of q and of k, ``H`` of v), g (the decay's log) and beta
    [T, H] -> (o [T, H V], the state after the last position [H, V, K]):
    the recurrence one position after another; a step splits and norms its
    own row and hands value head ``i`` key head ``i // (H / Hk)``."""
    import jax
    import jax.numpy as jnp

    T = qkv.shape[0]

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                            + L2_EPS)

    def step(S, xs):
        row, g_t, beta_t = xs
        q_t, k_t, v_t = jnp.split(row, (Hk * K, 2 * Hk * K))
        q_t = jnp.repeat(unit(q_t.reshape(Hk, K)) * K ** -0.5, H // Hk, 0)
        k_t = jnp.repeat(unit(k_t.reshape(Hk, K)), H // Hk, 0)
        S = jnp.exp(g_t)[:, None, None] * S
        seen = jnp.einsum("hvk,hk->hv", S, k_t)
        S = S + (beta_t[:, None] * (v_t.reshape(H, V) - seen)
                 )[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q_t).reshape(H * V)

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
    S, o = run(jnp.zeros((H, V, K), jnp.float32), (qkv, g, beta),
               (T // tb, T_BLOCK, T_BLOCK) if T % tb == 0 else (T,))
    return o, S


def recurrence(q, k, v, g, beta):
    """q and k [T, Hk, K] (as the taps leave them: not normed), v [T, H, V],
    g and beta [T, H] -> (o [T, H, V], the last state [H, V, K])."""
    import jax.numpy as jnp

    T, Hk, K = q.shape
    H, V = v.shape[1:]
    o, S = _recurrence_rows(
        jnp.concatenate([q.reshape(T, Hk * K), k.reshape(T, Hk * K),
                         v.reshape(T, H * V)], axis=-1), g, beta, Hk, H, K, V)
    return o.reshape(T, H, V), S


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


def delta_mixer(u, p, sz):
    """u [T, hidden] float32 (normed) -> (Mixer(u) [T, hidden], the state
    after the last position [H, V, K]), ``HEAD_GROUP`` value heads (and the
    key heads they read) at a time."""
    import jax
    import jax.numpy as jnp

    H, Hk, K, V = (sz["linear_heads"], sz["linear_key_heads"],
                   sz["linear_key_dim"], sz["linear_value_dim"])
    hg = _blocks(H, HEAD_GROUP)
    n = H // hg
    kg = hg * Hk // H                       # key heads a group
    hk, hv = Hk * K, H * V

    def cols(w, lo, heads, width):
        """Columns ``lo : lo + heads width`` of ``w`` a group of heads at a
        time: [n, rows, (heads / n) width]."""
        return jnp.moveaxis(w[:, lo:lo + heads * width].reshape(
            w.shape[0], n, heads // n * width), 1, 0)

    w_in, taps = p["g_in"], p["g_conv"].T                  # [taps, channels]
    ab = 2 * (hv + hk)
    groups = {
        "z": cols(w_in, 0, H, V),
        "qkv": jnp.concatenate([cols(w_in, hv, Hk, K),
                                cols(w_in, hv + hk, Hk, K),
                                cols(w_in, hv + 2 * hk, H, V)], axis=-1),
        "ab": jnp.concatenate([cols(w_in, ab, H, 1),
                               cols(w_in, ab + H, H, 1)], axis=-1),
        "taps": jnp.concatenate([cols(taps, 0, Hk, K), cols(taps, hk, Hk, K),
                                 cols(taps, 2 * hk, H, V)], axis=-1),
        "A_log": p["g_A_log"].reshape(n, hg),
        "dt_bias": p["g_dt_bias"].reshape(n, hg),
        "out": p["g_out"].reshape(n, hg * V, -1)}

    @jax.checkpoint
    def group(u, w):
        """-> (what the group's heads add to Mixer(u), their last states
        [hg, V, K]); kept for the backward: u."""
        qkv = _taps_silu(_by_rows(lambda ub: ub @ w["qkv"], u), w["taps"].T)
        a, b = jnp.split(u @ w["ab"], 2, axis=-1)
        o, S = _recurrence_rows(
            qkv, -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"]),
            jax.nn.sigmoid(b), kg, hg, K, V)

        def norm_gate_out(ob, ub):
            ob = ob.reshape(-1, hg, V)
            y = ob / jnp.sqrt(jnp.mean(jnp.square(ob), -1, keepdims=True)
                              + sz["eps"]) * p["g_norm"]
            z = (ub @ w["z"]).reshape(-1, hg, V)
            return (y * jax.nn.silu(z)).reshape(-1, hg * V) @ w["out"]

        return _by_rows(norm_gate_out, o, u), S

    def add(out, w):
        part, S = group(u, w)
        return out + part, S

    out, states = jax.lax.scan(add, jnp.zeros_like(u), groups)
    return out, states.reshape(H, V, K)


def _rope(x, sz):
    """x [T, H, d], positions 0..T-1: the first ``rotated`` dims rotated
    as two halves, the rest passed."""
    import jax.numpy as jnp

    rot = sz["rotated"]
    inv = 1.0 / (sz["theta"] ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(u, p, sz):
    """u [T, hidden] float32 (normed) -> Mixer(u) [T, hidden]."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, KVH, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    qg = _by_rows(lambda ub: ub @ p["wq"], u).reshape(T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    q = _rope(_norm(q, p["q_norm"], sz["eps"]), sz)
    k = _rope(_norm((u @ p["wk"]).reshape(T, KVH, hd), p["k_norm"],
                    sz["eps"]), sz)
    v = (u @ p["wv"]).reshape(T, KVH, hd)
    q = q.reshape(T, KVH, H // KVH, hd)
    qb = _blocks(T, Q_BLOCK)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                        # [qb, KVH, H / KVH, hd], [qb, 1]
        sc = jnp.einsum("qgrd,kgd->grqk", q_blk, k) * hd ** -0.5
        sc = jnp.where((j <= i)[None, None], sc, -jnp.inf)
        return None, jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, KVH, H // KVH, hd),
        jnp.arange(T).reshape(T // qb, qb, 1)))
    attn = outs.reshape(T, H, hd) * jax.nn.sigmoid(gate)
    return _by_rows(lambda ab: ab @ p["wo"], attn.reshape(T, H * hd))


def _swiglu(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def routed_mlp(u, p, sz, forced=None, shared: bool = True):
    """u [T, h] float32, the normed input of the MLP -> (the gated shared
    expert (``shared``) + the held experts' part [T, h], router logits [T,
    E], chosen experts [T, K]). ``p``'s expert weights are the held
    experts', any float dtype; the rest float32."""
    import jax
    import jax.numpy as jnp

    logits = u @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    if forced is None:
        gates, chosen = jax.lax.top_k(probs, sz["top_k"])
    else:
        chosen = forced
        gates = jnp.take_along_axis(probs, chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True)
    first, count = sz["held"]

    def one_expert(acc, ew):
        idx, w_gate, w_up, w_down = ew
        gate = jnp.where(chosen == idx, gates, 0.0).sum(-1)        # [T]
        return acc + _by_rows(
            lambda ub, gb: gb[:, None] * _swiglu(
                ub, _f32(w_gate, sz), _f32(w_up, sz), _f32(w_down, sz)),
            u, gate), None

    out = _by_rows(
        lambda ub: jax.nn.sigmoid(ub @ p["s_sigmoid"])[:, None] * _swiglu(
            ub, p["s_gate"], p["s_up"], p["s_down"]),
        u) if shared else jnp.zeros_like(u)
    out, _ = jax.lax.scan(
        one_expert, out,
        (first + jnp.arange(count), p["e_gate"], p["e_up"], p["e_down"]))
    return out, logits, chosen


_EXPERTS = ("e_gate", "e_up", "e_down")


def _layer(x, stacked, own, forced, *, sz, kind: str, at: int):
    """x [T, h] float32; ``stacked``: the weights of the kind's layers (any
    float dtype), of which this is layer ``at``; ``own``: leaves that stand
    in for this layer's, or None -> (x, the rule's state after the last
    position [H, V, K] or None for a full layer, router logits [T, E],
    chosen experts [T, K]). The layer's weights are sliced and upcast in
    here (the experts' one at a time in their loop), so that a
    ``jax.checkpoint`` around the call keeps neither a slice nor a float32
    copy."""
    p = {k: (v[at] if k in _EXPERTS else _f32(v[at], sz))
         for k, v in stacked.items()}
    if own is not None:
        p.update({k: (v if k in _EXPERTS else _f32(v, sz))
                  for k, v in own.items()})
    if kind == "full":
        x = x + attention(_norm(x, p["attn_norm"], sz["eps"]), p, sz)
        S = None
    else:
        out, S = delta_mixer(_norm(x, p["op_norm"], sz["eps"]), p, sz)
        x = x + out
    out, logits, chosen = routed_mlp(_norm(x, p["mlp_norm"], sz["eps"]), p,
                                     sz, forced)
    return x + out, S, logits, chosen


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, the head, and the first layer of each kind
    (its leaves without the stack's axis): the first linear layer and the
    full layer."""
    return {**tree, "layers": {
        kind: {k: v[0] for k, v in leaves.items()}
        for kind, leaves in tree["layers"].items()}}


def _run(sz, params, tokens, forced, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm
    [T, h], the linear layers' states after the last position [Ll, H, V,
    K], which no gradient passes, router logits [L, T, E], chosen experts
    [L, T, K]). ``first`` (``first_layers(params)``) stands in for the
    weights it holds: what a gradient is taken with respect to."""
    import jax
    import jax.numpy as jnp

    x = _f32((first or params)["embed"][tokens], sz)
    taken = dict.fromkeys(params["layers"], 0)
    states, logits, chosen = [], [], []
    for l, kind in enumerate(sz["pattern"]):
        at = taken[kind]
        taken[kind] += 1
        x, S, lg, ch = jax.checkpoint(
            partial(_layer, sz=sz, kind=kind, at=at))(
            x, params["layers"][kind],
            first["layers"][kind] if first and at == 0 else None,
            None if forced is None else forced[l])
        if S is not None:
            states.append(S)
        logits.append(lg)
        chosen.append(ch)
    return (x, jax.lax.stop_gradient(jnp.stack(states)), jnp.stack(logits),
            jnp.stack(chosen))


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
    """row [S + 1] -> (next-token loss [S], the linear layers' last states,
    router logits [L, S, E], chosen experts [L, S, K])."""
    x, states, logits, chosen = _run(sz, params, row[:-1], forced, first)
    return (_head_nll(x, row[1:], first or params, sz), states, logits,
            chosen)


def _balance(sz, logits, chosen):
    """transformers' ``load_balancing_loss_func`` before its coefficient,
    of router logits [L, n, E] and chosen experts [L, n, K]."""
    import jax
    import jax.numpy as jnp

    E = logits.shape[-1]
    one_hot = jax.nn.one_hot(chosen.reshape(-1, sz["top_k"]), E)
    share = one_hot.mean(0).sum(0)          # [E]: sums to K over experts
    prob = jax.nn.softmax(logits.reshape(-1, E), -1).mean(0)
    return E * jnp.sum(share * prob)


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape: at published widths
    the cell cannot wait for it to run eagerly. ``with_grad``: the row's
    weights ``w [S]`` and the balancing term's ``coef`` too, and the
    gradient of ``sum(w * nll) + coef * balance`` back."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, f, w, coef):
        nll, states, logits, chosen = _nll(sz, p, row, f, first)
        return ((w * nll).sum() + coef * _balance(sz, logits, chosen),
                (nll, states, logits, chosen))

    def nll_and_grad(p, row, f, w, coef):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, f, w, coef)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row, f: _nll(sz, p, row, f))
    return _JIT[key]


def token_nll(cfg, params, tokens, forced_topk=None, grad_weights=None,
              mantissa_bits=None, router_term: bool = False
              ) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy ``nll [B, S]``, ``last_states`` (the
    linear layers' states after a sequence's last position [Ll, B, H, V,
    K]), ``state_abs_max`` (their largest ``|S|``), ``router_logits [L, B *
    S, E]``, ``chosen [L, B * S, K]`` and the loss ``terms`` (floats); with
    ``grad_weights [B, S]`` also ``grads``, the gradient of
    ``sum(grad_weights * nll)`` (with ``router_term``, one row alone, plus
    ``router_aux_coef`` x the balancing term) with respect to
    ``first_layers(params)``. ``forced_topk [L, B * S, K]``: the choices of
    experts, laid out as the program lays its tokens, row after row.
    ``mantissa_bits``: every weight is rounded to that many where it is
    used (``_f32``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = {**_sizes(cfg), "mantissa_bits": mantissa_bits}
    fn = _jitted_nll(sz, grad_weights is not None)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, S = tokens.shape[0], tokens.shape[1] - 1
    if router_term and B != 1:
        raise ValueError("the balancing term of several rows is no sum of "
                         "the rows' own: its gradient is asked a row alone")
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
                         jnp.asarray(grad_weights[b], jnp.float32),
                         sz["aux_coef"] * router_term)
            rows.append(out)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        logits = jnp.concatenate([r[2] for r in rows], axis=1)
        chosen = jnp.concatenate([r[3] for r in rows], axis=1)
        balance = float(_balance(sz, logits, chosen))
    nll = np.stack([np.asarray(r[0]) for r in rows])
    states = np.stack([np.asarray(r[1]) for r in rows], axis=1)
    ce = float(nll.mean())
    out = {"nll": nll, "last_states": states,
           "state_abs_max": float(np.abs(states).max()),
           "router_logits": np.asarray(logits), "chosen": np.asarray(chosen),
           "terms": {"cross_entropy": ce, "load_balance": balance,
                     "loss": ce + sz["aux_coef"] * balance}}
    if grad_weights is not None:
        out["grads"] = grads
    return out


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
    probabilities, not through the choice of experts."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[1] - 1
    with jax.default_matmul_precision("highest"):
        rows = [_nll(sz, params, row, None if forced_topk is None
                     else forced_topk[:, b * S:(b + 1) * S])
                for b, row in enumerate(tokens)]
        balance = _balance(sz, jnp.concatenate([r[2] for r in rows], 1),
                           jnp.concatenate([r[3] for r in rows], 1))
        return (jnp.stack([r[0] for r in rows]).mean()
                + sz["aux_coef"] * balance)


def mixer(cfg, p, u):
    """One linear layer's mixer on its normed input u [T, hidden] (CPU
    sizes) -> (Mixer(u), the last state [H, V, K])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return delta_mixer(jnp.asarray(u, jnp.float32),
                           {k: jnp.asarray(v, jnp.float32)
                            for k, v in p.items()}, _sizes(cfg))


def routed_layer(cfg, p, u, shared: bool = True):
    """One layer's MLP on its normed input u [T, h] (CPU sizes): the gated
    shared expert (``shared``) and the part of the experts ``cfg`` holds."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return routed_mlp(jnp.asarray(u, jnp.float32),
                          {k: jnp.asarray(v, jnp.float32)
                           for k, v in p.items()}, _sizes(cfg),
                          shared=shared)[0]
