"""Plain reference of Olmo-Hybrid (allenai/Olmo-Hybrid-7B, ``olmo_hybrid``):
float32, ``jax.numpy`` only, matmuls at ``highest`` precision, no kernel,
the gated delta rule's recurrence **token by token** (a ``lax.scan`` over
positions on the float32 ``[192, 96]`` state of every head, never the
chunked algebra and no triangular inverse), the convolution a loop over
its taps, attention a masked softmax over every key in blocks of queries.

The equations, from the model's ``config.json``, the Gated DeltaNet paper
(arXiv:2412.06464) and its published implementation (flash-linear-attention's
``GatedDeltaNet`` layer), and OLMo 2's block (what the config's keys do not
settle is listed under ``assumed`` in
``benchmark/configs/olmo-hybrid-7b-c1.json``). ``RMSNorm`` has a weight,
eps 1e-6. ``h0 = embed[tokens]``; every layer norms a sublayer's output and
not its input, ``h = h + RMSNorm(Mixer(h))`` then ``h = h +
RMSNorm(SwiGLU(h))``; after the last layer one more RMSNorm, then the
untied head.

- ``Mixer`` of a ``linear_attention`` layer on ``x [T, hidden]``, ``H``
  heads of key size ``K`` and value size ``V``: ``[z | q~ k~ v~ | a | b] = x
  W_in`` (widths ``H V | H K, H K, H V | H | H``); ``q, k, v = silu(conv(q~,
  k~, v~))``, a causal depthwise convolution of 4 taps with own taps for
  each channel and no bias (``w_j`` weighs the value ``3 - j`` positions
  back, zeros before position 0); a head at a time ``q_t <- q_t / sqrt(|q_t|^2
  + 1e-6) * K^-1/2``, ``k_t <- k_t / sqrt(|k_t|^2 + 1e-6)``; ``g_t = -exp(A_log)
  softplus(a_t + dt_bias)``; ``beta_t = 2 sigmoid(b_t)``
  (``linear_allow_neg_eigval``); ``S_t = exp(g_t) S_{t-1} (I - beta_t k_t
  k_t^T) + beta_t v_t k_t^T`` with ``S [V, K]`` zero before the sequence,
  computed as the published kernels' recurrence does (decay the state,
  read ``S k_t``, add ``beta_t (v_t - S k_t) k_t^T``); ``o_t = S_t q_t``;
  ``y_t = RMSNorm_V(o_t; w) * silu(z_t)`` a head at a time; ``Mixer = y
  W_out``. No projection has a bias.
- ``Mixer`` of a ``full_attention`` layer: ``q, k, v`` projections without
  bias, an RMSNorm over the whole q and the whole k vector, **no** position
  embedding (``rope_theta`` null), causal softmax attention with 30 query
  and 30 key/value heads of 128 at scale ``128^-1/2``, the output
  projection.
- Loss = cross entropy.

Departures from the published implementation: (1) the five projections of
a linear layer are the columns of one matrix ``g_in`` and the three
convolutions the rows of one ``g_conv`` (the same functions); (2) the
published kernels compute the rule in chunks of 64: the recurrence above
is what they compute; (3) everything is float32, where the published
kernels round ``T``, ``W`` and the state to the activations' dtype where
they are multiplied; (4) one sequence at a time, unpacked: no
``cu_seqlens`` and no padding mask.

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields: ``params["layers"][kind][name]``
stacked over the layers of a kind (``linear``, ``full``), ``cfg.pattern``
the kind of each layer. On the chip it runs in blocks so that it fits: a
layer at a time under ``jax.checkpoint``, a linear layer's mixer
``HEAD_GROUP`` heads at a time, the recurrence in blocks of ``T_BLOCK``
blocks of ``T_BLOCK`` positions (the state before each block of each level
is kept, the steps inside run again in the backward), the projections, the
SwiGLU and the head in blocks of ``ROW_BLOCK`` tokens, attention in blocks
of ``Q_BLOCK`` queries.

``grad_weights`` ([B, S] float32) asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` as well, with respect to the embedding, the
last norm, the head and the first layer of each kind (``first_layers``),
one row at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

Q_BLOCK = 64
T_BLOCK = 32
ROW_BLOCK = 2048
HEAD_GROUP = 10
L2_EPS = 1e-6


def _sizes(cfg) -> Dict[str, Any]:
    return {"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim or cfg.hidden_size // cfg.num_heads,
            "eps": cfg.rms_norm_eps, "pattern": tuple(cfg.pattern),
            "linear_heads": cfg.linear_heads,
            "linear_key_dim": cfg.linear_key_dim,
            "linear_value_dim": cfg.linear_value_dim}


def _f32(v, sz):
    """A weight in float32; with ``sz["mantissa_bits"]`` rounded to that
    many mantissa bits where it is used (``lax.reduce_precision`` at
    float32's exponent range: 3 is float8 e4m3's mantissa), the gradient
    passing the rounding untouched: ``benchmark/tests/delta_limits.py``'s
    reference one precision lower, with no second copy of the weights."""
    import jax
    import jax.numpy as jnp

    f = v.astype(jnp.float32)
    if not sz.get("mantissa_bits"):
        return f
    return f + jax.lax.stop_gradient(jax.lax.reduce_precision(
        f, exponent_bits=8, mantissa_bits=sz["mantissa_bits"]) - f)


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


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


def recurrence(q, k, v, g, beta):
    """q and k [T, H, K] (as the taps leave them: not normed), v [T, H, V],
    g (the decay's log) and beta [T, H] -> (o [T, H, V], the state after
    the last position [H, V, K]): the recurrence one position after
    another."""
    import jax.numpy as jnp

    T, H, K = q.shape
    V = v.shape[-1]
    o, S = _recurrence_rows(
        jnp.concatenate([q.reshape(T, H * K), k.reshape(T, H * K),
                         v.reshape(T, H * V)], axis=-1), g, beta, H, K, V)
    return o.reshape(T, H, V), S


def _recurrence_rows(qkv, g, beta, H, K, V):
    """``recurrence`` on rows ``[q | k | v]`` as the taps leave them (qkv
    [T, H (2 K + V)]) -> (o [T, H V], the last state): a step splits and
    norms its own row."""
    import jax
    import jax.numpy as jnp

    T = qkv.shape[0]

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                            + L2_EPS)

    def step(S, xs):
        row, g_t, beta_t = xs
        q_t, k_t, v_t = jnp.split(row, (H * K, 2 * H * K))
        q_t = unit(q_t.reshape(H, K)) * K ** -0.5
        k_t = unit(k_t.reshape(H, K))
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
    """u [T, hidden] float32 -> (Mixer(u) [T, hidden], the state after the
    last position [H, V, K]), ``HEAD_GROUP`` heads at a time."""
    import jax
    import jax.numpy as jnp

    H, K, V = (sz["linear_heads"], sz["linear_key_dim"],
               sz["linear_value_dim"])
    hg = _blocks(H, HEAD_GROUP)
    n = H // hg
    hk, hv = H * K, H * V

    def cols(w, lo, width):
        """Columns ``lo : lo + H width`` of ``w`` a group of heads at a
        time: [n, rows, hg width]."""
        return jnp.moveaxis(
            w[:, lo:lo + H * width].reshape(w.shape[0], n, hg * width), 1, 0)

    w_in, taps = p["g_in"], p["g_conv"].T                  # [taps, channels]
    groups = {
        "z": cols(w_in, 0, V),
        "qkv": jnp.concatenate([cols(w_in, hv, K), cols(w_in, hv + hk, K),
                                cols(w_in, hv + 2 * hk, V)], axis=-1),
        "ab": jnp.concatenate([cols(w_in, 2 * (hv + hk), 1),
                               cols(w_in, 2 * (hv + hk) + H, 1)], axis=-1),
        "taps": jnp.concatenate([cols(taps, 0, K), cols(taps, hk, K),
                                 cols(taps, 2 * hk, V)], axis=-1),
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
            2.0 * jax.nn.sigmoid(b), hg, K, V)

        def norm_gate_out(ob, ub):
            y = _rms_norm(ob.reshape(-1, hg, V), p["g_norm"], sz["eps"])
            z = (ub @ w["z"]).reshape(-1, hg, V)
            return (y * jax.nn.silu(z)).reshape(-1, hg * V) @ w["out"]

        return _by_rows(norm_gate_out, o, u), S

    def add(out, w):
        part, S = group(u, w)
        return out + part, S

    out, states = jax.lax.scan(add, jnp.zeros_like(u), groups)
    return out, states.reshape(H, V, K)


def attention(h, p, sz):
    """h [T, hidden] float32 -> Mixer(h) [T, hidden]: q and k normed over
    their whole vectors, no rope, scores times ``head_dim ** -0.5``."""
    import jax
    import jax.numpy as jnp

    T = h.shape[0]
    H, KVH, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = _rms_norm(h @ p["wq"], p["q_norm"], sz["eps"]).reshape(T, H, hd)
    k = _rms_norm(h @ p["wk"], p["k_norm"], sz["eps"]).reshape(T, KVH, hd)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat((h @ p["wv"]).reshape(T, KVH, hd), H // KVH, axis=1)
    qb = _blocks(T, Q_BLOCK)
    j = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(_, qi):
        q_blk, i = qi                              # [qb, H, hd], [qb, 1]
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) * hd ** -0.5
        sc = jnp.where((j <= i)[None], sc, -jnp.inf)
        return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    _, outs = jax.lax.scan(block, None, (
        q.reshape(T // qb, qb, H, hd), jnp.arange(T).reshape(T // qb, qb, 1)))
    return outs.reshape(T, H * hd) @ p["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _layer(x, stacked, own, *, sz, kind: str, at: int):
    """x [T, h] float32; ``stacked``: the weights of the kind's layers (any
    float dtype), of which this is layer ``at``; ``own``: leaves that stand
    in for this layer's, or None -> (x, the rule's state after the last
    position [H, V, K]; None for a full layer). The layer's weights are
    sliced and upcast in here, so that a ``jax.checkpoint`` around the call
    keeps neither a slice nor a float32 copy."""
    p = {k: _f32(v[at], sz) for k, v in stacked.items()}
    if own is not None:
        p.update({k: _f32(v, sz) for k, v in own.items()})
    if kind == "full":
        x = x + _rms_norm(attention(x, p, sz), p["attn_post_norm"], sz["eps"])
        S = None
    else:
        out, S = delta_mixer(x, p, sz)
        x = x + _rms_norm(out, p["op_post_norm"], sz["eps"])
    return x + _rms_norm(_by_rows(
        lambda ub: _swiglu(ub, p["w_gate"], p["w_up"], p["w_down"]), x),
        p["mlp_post_norm"], sz["eps"]), S


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, the head, and the first layer of each kind
    (its leaves without the stack's axis): the first linear layer and the
    first full layer."""
    return {**tree, "layers": {
        kind: {k: v[0] for k, v in leaves.items()}
        for kind, leaves in tree["layers"].items()}}


def _run(sz, params, tokens, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm
    [T, h], the linear layers' states after the last position [Ll, H, V,
    K], which no gradient passes). ``first`` (``first_layers(params)``)
    stands in for the weights it holds: what a gradient is taken with
    respect to."""
    import jax
    import jax.numpy as jnp

    x = _f32((first or params)["embed"][tokens], sz)
    taken = dict.fromkeys(params["layers"], 0)
    states = []
    for kind in sz["pattern"]:
        at = taken[kind]
        taken[kind] += 1
        x, S = jax.checkpoint(partial(_layer, sz=sz, kind=kind, at=at))(
            x, params["layers"][kind],
            first["layers"][kind] if first and at == 0 else None)
        if S is not None:
            states.append(S)
    return x, jax.lax.stop_gradient(jnp.stack(states))


def _head_nll(x, targets, params, sz):
    """x [T, h] -> the next-token loss [T]; the logits in blocks."""
    import jax
    import jax.numpy as jnp

    x = _rms_norm(x, _f32(params["final_norm"], sz), sz["eps"])
    head = _f32(params["lm_head"], sz)

    def nll(xb, tb):
        lg = xb @ head
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[:, None], -1)[:, 0]

    return _by_rows(nll, x, targets)


def _nll(sz, params, row, first=None):
    """row [S + 1] -> (next-token loss [S], the linear layers' last states
    [Ll, H, V, K])."""
    x, states = _run(sz, params, row[:-1], first)
    return _head_nll(x, row[1:], first or params, sz), states


_JIT: Dict[Any, Any] = {}


def _jitted_nll(sz, with_grad: bool = False):
    """The per-row function compiled once a shape: at published widths
    the cell cannot wait for it to run eagerly. ``with_grad``: the row's
    weights ``w [S]`` too, and the gradient of ``sum(w * nll)`` back."""
    import jax

    key = tuple(sorted(sz.items())) + (with_grad,)
    if key in _JIT:
        return _JIT[key]

    def weighted(first, p, row, w):
        nll, states = _nll(sz, p, row, first)
        return (w * nll).sum(), (nll, states)

    def nll_and_grad(p, row, w):
        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            first_layers(p), p, row, w)
        return out + (grads,)

    _JIT[key] = jax.jit(nll_and_grad if with_grad
                        else lambda p, row: _nll(sz, p, row))
    return _JIT[key]


def token_nll(cfg, params, tokens, grad_weights=None, mantissa_bits=None
              ) -> Dict[str, Any]:
    """tokens [B, S + 1] -> numpy ``nll [B, S]``, ``last_states`` (the
    linear layers' states after a sequence's last position [Ll, B, H, V,
    K]), ``state_abs_max`` (their largest ``|S|``) and the loss ``terms``
    (floats); with ``grad_weights [B, S]`` also ``grads``, the gradient of
    ``sum(grad_weights * nll)`` with respect to ``first_layers(params)``.
    ``mantissa_bits``: every weight is rounded to that many where it is
    used (``_f32``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sz = {**_sizes(cfg), "mantissa_bits": mantissa_bits}
    fn = _jitted_nll(sz, grad_weights is not None)
    tokens = jnp.asarray(tokens, jnp.int32)
    rows, grads = [], None
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            if grad_weights is None:
                rows.append(fn(params, tokens[b]))
                continue
            *out, g = fn(params, tokens[b],
                         jnp.asarray(grad_weights[b], jnp.float32))
            rows.append(out)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
    nll = np.stack([np.asarray(r[0]) for r in rows])
    states = np.stack([np.asarray(r[1]) for r in rows], axis=1)
    ce = float(nll.mean())
    out = {"nll": nll, "last_states": states,
           "state_abs_max": float(np.abs(states).max()),
           "terms": {"cross_entropy": ce, "loss": ce}}
    if grad_weights is not None:
        out["grads"] = grads
    return out


def logits(cfg, params, tokens):
    """tokens [B, S] -> logits [B, S, V] float32 (CPU sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _rms_norm(_run(sz, params, jnp.asarray(row, jnp.int32))[0],
                      params["final_norm"].astype(jnp.float32), sz["eps"])
            @ params["lm_head"].astype(jnp.float32) for row in tokens])


def loss(cfg, params, tokens):
    """The whole loss as one differentiable function of ``params`` (CPU
    sizes)."""
    import jax
    import jax.numpy as jnp

    sz = _sizes(cfg)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_nll(sz, params, row)[0] for row in
                          jnp.asarray(tokens, jnp.int32)]).mean()


def mixer(cfg, p, u):
    """One linear layer's mixer on its input u [T, hidden] (CPU sizes) ->
    (Mixer(u), the last state [H, V, K])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return delta_mixer(jnp.asarray(u, jnp.float32),
                           {k: jnp.asarray(v, jnp.float32)
                            for k, v in p.items()}, _sizes(cfg))
