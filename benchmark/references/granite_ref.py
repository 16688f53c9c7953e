"""Plain reference of Granite 4.0-H (ibm-granite/granite-4.0-h-micro):
float32, ``jax.numpy`` only, matmuls at ``highest`` precision, no kernel,
the selective scan's recurrence **token by token** (a ``lax.scan`` over
positions with a float32 state, never the chunked algebra), the
convolution a loop over its taps, attention a masked softmax over every
key in blocks of queries.

The equations, from transformers' ``GraniteMoeHybrid*`` and the model's
``config.json`` (what its keys do not settle is listed under ``assumed``
in ``benchmark/configs/granite-4.0-h-micro-c1.json``). ``RMSNorm`` has a
weight, eps 1e-5. ``h0 = embedding_multiplier * embed[tokens]``; every
layer, with ``r = residual_multiplier``, is ``h = h + r *
Mixer(RMSNorm(h))`` then ``h = h + r * SwiGLU(RMSNorm(h))``; after the last
layer one more RMSNorm, then the head, which is the embedding transposed,
and the logits divided by ``logits_scaling``.

- ``Mixer`` of a mamba layer, ``u = RMSNorm(h)``: ``[z | xBC | dt] = u
  W_in`` (widths ``d | d + 2 G N | H``, ``d = H P``); ``xBC = silu(conv(xBC)
  + b_conv)``, a causal depthwise convolution of 4 taps (``w_j`` weighs
  ``xBC_{t - 3 + j}``, zeros before position 0); ``[x | B | C] =
  split(xBC)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; a
  head at a time ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  S_t C_t + D x_t`` with ``S [P, N]`` zero before the sequence; ``y =
  RMSNorm(y * silu(z))`` over all ``d`` channels (one group); ``Mixer = y
  W_out``. No projection has a bias.
- ``Mixer`` of an attention layer: ``q, k, v`` projections without bias,
  **no** position embedding, causal softmax attention with 32 query heads
  on 8 kv heads, scores scaled by ``attention_multiplier`` (1/64, not
  ``head_dim ** -0.5``), the output projection.
- Loss = cross entropy.

Departures from transformers' implementation: (1) the SwiGLU's gate and up
halves are two matrices ``w_gate`` and ``w_up``, where ``shared_mlp.
input_linear`` holds them as one of twice the width (the same function);
(2) transformers' torch path computes the scan in chunks too
(``mamba_chunk_size``), and clamps ``dt`` to ``time_step_limit``, which is
(0, inf) and clamps nothing: the recurrence above is what both compute;
(3) transformers' gated norm multiplies by ``silu(z)`` in float32 and
rounds before the weight; here everything is float32; (4) one sequence at
a time, unpacked: no ``seq_idx`` and no padding mask.

It shares nothing with ``ray_tpu`` but the layout of the parameter pytree
and the names of the config's fields: ``params["layers"][kind][name]``
stacked over the layers of a kind (``mamba``, ``attention``),
``cfg.pattern`` the kind of each layer. On the chip it runs in blocks so
that it fits: a layer at a time under ``jax.checkpoint``, the recurrence in
blocks of ``T_BLOCK`` blocks of ``T_BLOCK`` positions (the state before
each block of each level is kept, the steps inside run again in the
backward), the projections, the SwiGLU and the head in blocks of
``ROW_BLOCK`` tokens, attention in blocks of ``Q_BLOCK`` queries.

``grad_weights`` ([B, S] float32) asks ``token_nll`` for the gradient of
``sum(grad_weights * nll)`` as well, with respect to the embedding, the
last norm and the first layer of each kind (``first_layers``), one row at
a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

Q_BLOCK = 64
T_BLOCK = 32
ROW_BLOCK = 2048


def _sizes(cfg) -> Dict[str, Any]:
    return {"heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim or cfg.hidden_size // cfg.num_heads,
            "eps": cfg.rms_norm_eps, "pattern": tuple(cfg.pattern),
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
            "embedding_multiplier": cfg.embedding_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling}


def _f32(v, sz):
    """A weight in float32; with ``sz["mantissa_bits"]`` rounded to that
    many mantissa bits where it is used (``lax.reduce_precision`` at
    float32's exponent range: 3 is float8 e4m3's mantissa), the gradient
    passing the rounding untouched: ``benchmark/tests/scan_limits.py``'s
    reference one precision lower, with no second copy of the weights. (A
    cast to a float8 dtype and back inside a jitted program is no rounding
    on a v5e: the compiler keeps the wider type. Read on the chip, PR 36.)"""
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
    """``block`` where the sequence is whole blocks, else one block (CPU
    sizes)."""
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


def recurrence(x, dt, A, B, C):
    """x [T, H, P], dt [T, H] (after its softplus), A [H] (negative), B and
    C [T, G, N] (a group's are its ``H / G`` heads') -> (y [T, H, P]
    without the skip, the state after the last position [H, P, N]): the
    recurrence one position after another."""
    import jax.numpy as jnp

    T, H, P = x.shape
    G, N = B.shape[1:]
    y, S = _recurrence_rows(
        jnp.concatenate([x.reshape(T, H * P), B.reshape(T, G * N),
                         C.reshape(T, G * N)], axis=-1), dt, A, H, P, G, N)
    return y.reshape(T, H, P), S


def _recurrence_rows(xbc, dt, A, H, P, G, N):
    """``recurrence`` on rows ``[x | B | C]`` as the taps leave them (xbc
    [T, H P + 2 G N]) -> (y [T, H P], the last state): a step splits its
    own row, so that neither x nor its gradient exists beside xbc."""
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
    S, y = run(jnp.zeros((H, P, N), jnp.float32), (xbc, dt),
               (T // tb, T_BLOCK, T_BLOCK) if T % tb == 0 else (T,))
    return y, S


def mamba_mixer(u, p, sz):
    """u [T, hidden] float32 (normed) -> (Mixer(u) [T, hidden], the state
    after the last position [H, P, N])."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, P, N, G = (sz["ssm_heads"], sz["ssm_head_dim"], sz["ssm_state"],
                  sz["ssm_groups"])
    d = H * P
    # [z | xBC | dt] = u W_in in two groups of columns: xBC and dt here, z
    # where it is used (the last block of rows below), so that z and its
    # gradient never exist for the whole sequence
    w_z, w_rest = p["m_in"][:, :d], p["m_in"][:, d:]

    @jax.checkpoint
    def project_taps_silu(u):
        """-> (silu(conv(xBC) + bias), dt); kept for the backward: u."""
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
        yb = yb + jnp.repeat(p["D"], P) * xbc_b[:, :d]
        return _rms_norm(yb * jax.nn.silu(ub @ w_z), p["m_norm"],
                         sz["eps"]) @ p["m_out"]

    return _by_rows(skip_norm_out, y, xbc, u), S


def attention(h, p, sz):
    """h [T, hidden] float32 (normed) -> Mixer(h) [T, hidden]: no rope,
    scores times ``attention_multiplier``."""
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
        sc = jnp.einsum("qhd,khd->hqk", q_blk, k) * sz["attention_multiplier"]
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
    in for this layer's, or None -> (x, the scan's state after the last
    position [H, P, N]; None for attention). The layer's weights are
    sliced and upcast in here, so that a ``jax.checkpoint`` around the call
    keeps neither a slice nor a float32 copy."""
    p = {k: _f32(v[at], sz) for k, v in stacked.items()}
    if own is not None:
        p.update({k: _f32(v, sz) for k, v in own.items()})
    r = sz["residual_multiplier"]
    if kind == "attention":
        x = x + r * attention(_rms_norm(x, p["attn_norm"], sz["eps"]), p, sz)
        S = None
    else:
        out, S = mamba_mixer(_rms_norm(x, p["op_norm"], sz["eps"]), p, sz)
        x = x + r * out
    u = _rms_norm(x, p["mlp_norm"], sz["eps"])
    return x + r * _by_rows(
        lambda ub: _swiglu(ub, p["w_gate"], p["w_up"], p["w_down"]), u), S


def first_layers(tree):
    """Of arrays like the parameters, those a gradient is asked for: the
    embedding, the last norm, and the first layer of each kind (its leaves
    without the stack's axis): the first Mamba layer and the first
    attention layer."""
    return {**tree, "layers": {
        kind: {k: v[0] for k, v in leaves.items()}
        for kind, leaves in tree["layers"].items()}}


def _run(sz, params, tokens, first=None):
    """One sequence: tokens [T] -> (hidden states before the last norm
    [T, h], the scan layers' states after the last position [Lm, H, P, N],
    which no gradient passes). ``first`` (``first_layers(params)``) stands
    in for the weights it holds: what a gradient is taken with respect
    to."""
    import jax
    import jax.numpy as jnp

    x = (_f32((first or params)["embed"][tokens], sz)
         * sz["embedding_multiplier"])
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
    head = _f32(params["embed"], sz).T

    def nll(xb, tb):
        lg = (xb @ head) / sz["logits_scaling"]
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tb[:, None], -1)[:, 0]

    return _by_rows(nll, x, targets)


def _nll(sz, params, row, first=None):
    """row [S + 1] -> (next-token loss [S], the scan layers' last states
    [Lm, H, P, N])."""
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
    """tokens [B, S + 1] -> numpy ``nll [B, S]``, ``last_states`` (the scan
    layers' states after a sequence's last position [Lm, B, H, P, N]),
    ``state_abs_max`` (their largest ``|S|``) and the loss ``terms``
    (floats); with ``grad_weights [B,
    S]`` also ``grads``, the gradient of ``sum(grad_weights * nll)`` with
    respect to ``first_layers(params)``. ``mantissa_bits``: every
    weight is rounded to that many where it is used (``_f32``)."""
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
            @ params["embed"].astype(jnp.float32).T / sz["logits_scaling"]
            for row in tokens])


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
    """One Mamba layer's mixer on its normed input u [T, hidden] (CPU
    sizes) -> (Mixer(u), the last state [H, P, N])."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return mamba_mixer(jnp.asarray(u, jnp.float32),
                           {k: jnp.asarray(v, jnp.float32)
                            for k, v in p.items()}, _sizes(cfg))
