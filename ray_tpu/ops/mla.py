"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) as a layer's
mixer, for training.

Queries and keys/values each pass through a low-rank latent with an
RMSNorm of its own (``q_lora_rank`` None: the queries come straight from
the layer's normed input, ``q = u W_q``, one leaf ``wq``, and the first
rung keeps the kv latent alone):

- ``c_q = RMSNorm(u W_qa)`` ``[q_lora_rank]``; ``q = c_q W_qb`` ``[H, d_n +
  d_r]``, a head's first ``d_n`` dims position-free, its last ``d_r``
  rotated;
- ``[c_kv | k_r] = u W_kva`` ``[kv_lora_rank | d_r]``; ``c_kv =
  RMSNorm(c_kv)``; ``k_r`` is ONE rotated key vector a position, shared by
  every head;
- ``[k_n | v] = c_kv W_kvb`` ``[H, d_n | d_v]``;
- scores ``([q_n | q_r] . [k_n | k_r]) * s``, causal softmax, times ``v``,
  ``W_o [H * d_v, hidden]``, added to ``x``.

Keys are ``d_n + d_r`` wide and values ``d_v``: the flash kernels take the
two widths and the shared ``k_r`` as they are (``ops/attention.py``,
``flash_kv_*``), so no key is broadcast to the heads in HBM and no value is
padded. Rope follows the published code: the ``d_r`` dims are
de-interleaved (pairs ``(2i, 2i + 1)`` to ``(i, i + d_r / 2)``) and then
rotated as two halves. The softmax scale is ``(d_n + d_r) ** -0.5 * m ** 2``
with ``m = 0.1 * mscale_all_dim * ln(factor) + 1`` under yarn scaling
(``softmax_scale``); the rope tables' own factor is
``rope_frequencies``'.

``cfg.heads_of`` says that the config's ``num_heads`` are this chip's
share of a layer of ``heads_of`` heads under tensor parallelism, run
without its reduction: ``W_qb`` and ``W_kvb`` hold the held heads' columns,
``W_o`` their rows (drawn for the whole layer's fan-in), the two
down-projections are whole, and the block adds the held heads' part of the
sum to ``x``. Which heads they are changes no operation.

A model with more than one kind of latent layer names each kind's sizes
by a ``prefix`` of the config's fields (``latent_attention_part(prefix=
"swa_")`` reads ``swa_num_heads``, ``swa_q_lora_rank``, ...) and gives each
its own rope tables (``rope``). Further options, all off in DeepSeek-V2,
whose traced program they leave as it was:

- ``window``: the config's field with the keys a query sees, its own
  position among them (the ``flash_kv_*`` kernels under a band);
- ``gate``: a per-head output gate ``sigmoid(u W_g)`` on each head's
  output before ``W_o`` (arXiv:2505.06708, headwise; as
  ``llama.attention_block``'s ``wg``);
- ``rescale``: ``(hidden / rank) ** 0.5`` on the queries after ``W_qb``
  and on the normed kv latent before ``W_kvb``, the rope key unscaled
  (LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``). The two
  expansions then start from a fan-in of ``hidden``, not of the rank: the
  factor stands for exactly that difference, and drawn from the rank under
  it the scores' spread at the start is 6 where it should be 1 (measured:
  the softmax so sharp that bf16 rounding reads 10% at the last layer);
- ``index``: a learned index chooses ``cfg.index_topk`` keys a query and
  the layer attends over those (``ops/dsa.py``, whose ``index_leaves``,
  ``index_inputs``, ``index_report`` and ``index_terms`` this part shares
  with ``models/llama.attention_part(index=True)``; here the keys are
  per head with one shared rope key, there grouped): ``q_i = c_q W_iq``
  ``[index_heads, index_head_dim]`` from the normed query latent (a layer
  without one reads its normed input ``u``), ``k_i =
  LayerNorm(u W_ik)`` one key a position, the first ``d_r`` dims of both
  rotated with the layer's tables, ``w = (u W_iw) * index_heads ** -0.5 *
  index_head_dim ** -0.5`` in float32. ``u`` and ``c_q`` reach the index
  under ``stop_gradient`` and the choice is not differentiated, so the
  cross entropy gives the index's five leaves nothing; the layer reports
  (``Part.reports`` "dsa") the sum of its positions' ``KL(p_t ||
  softmax_{S_t} I)`` and the pairs chosen (asked for,
  ``ctx.keep_index_choice``, ``q_i``, ``k_i``, ``w`` and the choice packed
  eight keys a byte too), and ``terms`` adds
  ``cfg.index_loss_coef`` x the layers' sum of the positions' mean to the
  loss with the counters ``dsa_index_loss`` and ``dsa_pairs_chosen_share``.

Named scopes: ``mla_q`` (the layer's norm, both query projections and the
latent's norm), ``mla_kv`` (the two key/value projections and the latent's
norm), ``mla_rope``, ``flash`` (a window layer's kernels ``flash_window``
inside it), ``mla_out``; ``attn_gate``; ``dsa_proj`` (the index's three
projections, its LayerNorm and rope) and ``ops/dsa.py``'s. One kept span as
the part is traced, ``rtpu.mla.shapes``. Training only: a latent cache and
the absorbed decode path are the serving engines' to come.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import dsa
from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   with_shared_key)
from ray_tpu.ops.layers import (Leaf, Part, apply_rope, kept, layer_norm,
                                rms_norm, rope_frequencies)
from ray_tpu.util import tracing


class _Sizes(NamedTuple):
    """One kind of latent layer's sizes, read from the config's fields
    under the kind's prefix."""
    heads: int
    heads_of: int
    q_rank: int
    kv_rank: int
    d_n: int
    d_r: int
    d_v: int


def sizes(cfg, prefix: str = "") -> _Sizes:
    def of(name):
        return getattr(cfg, prefix + name)

    return _Sizes(of("num_heads"),
                  getattr(cfg, prefix + "heads_of", None) or of("num_heads"),
                  of("q_lora_rank"), of("kv_lora_rank"),
                  of("qk_nope_head_dim"), of("qk_rope_head_dim"),
                  of("v_head_dim"))


def softmax_scale(cfg, prefix: str = "") -> float:
    """``(d_n + d_r) ** -0.5``, times ``m ** 2`` where the config's rope
    scaling states an ``mscale_all_dim`` (the published code's
    ``yarn_get_mscale``)."""
    sz = sizes(cfg, prefix)
    scale = (sz.d_n + sz.d_r) ** -0.5
    scaling = cfg.rope_scaling_dict or {}
    factor, all_dim = scaling.get("factor", 1.0), scaling.get("mscale_all_dim")
    if all_dim and factor > 1:
        scale *= (0.1 * all_dim * math.log(factor) + 1.0) ** 2
    return scale


def rope_tables(cfg, tokens: jax.Array):
    """(cos, sin) over the ``d_r`` rotated dims, made once a forward."""
    return rope_frequencies(cfg.qk_rope_head_dim, tokens.shape[1],
                            cfg.rope_theta, dtype=cfg.dtype,
                            scaling=cfg.rope_scaling_dict)


def _rotate(x: jax.Array, cos, sin) -> jax.Array:
    """x [b, s, heads, d_r]: de-interleaved, then rotated as two halves."""
    x = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return apply_rope(x.swapaxes(-1, -2).reshape(x.shape[:-2] + (-1,)),
                      cos, sin)


def rescale_factor(cfg, rank: int) -> float:
    """``(hidden / rank) ** 0.5``: what ``rescale`` multiplies the queries
    and the normed kv latent by."""
    return (cfg.hidden_size / rank) ** 0.5


def head_gate(cfg, u: jax.Array, wg: jax.Array) -> jax.Array:
    """``sigmoid(u W_g)`` [b, s, H] float32: a gate a head and position."""
    return jax.nn.sigmoid(jnp.dot(u, wg.astype(cfg.dtype),
                                  preferred_element_type=jnp.float32))


def index_inputs(cfg, u, c_q, p, cos, sin):
    """``dsa.index_inputs`` of a latent layer: the queries from the normed
    query latent ``c_q``, the first ``d_r`` dims of the queries and of the
    key de-interleaved and rotated with the layer's tables."""
    dr = 2 * cos.shape[-1]

    def rotate(x):
        if x.ndim == 3:                       # the one key a position
            return jnp.concatenate(
                [_rotate(x[:, :, None, :dr], cos, sin)[:, :, 0], x[..., dr:]],
                axis=-1)
        return jnp.concatenate(
            [_rotate(x[..., :dr], cos, sin), x[..., dr:]], axis=-1)

    # ``layer_norm`` is looked up here as the layer is traced: a control of
    # benchmark/tests/sparse_limits.py replaces it
    return dsa.index_inputs(cfg, u, c_q, p, rotate, layer_norm)


index_terms = dsa.index_terms


def latent_attention_part(prefix: str = "", rope: Callable = rope_tables,
                          window: Optional[str] = None, gate: bool = False,
                          rescale: bool = False, index: bool = False
                          ) -> Part:
    """Latent attention as a layer's mixer, from the config's
    ``num_heads``, ``heads_of``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim`` under
    ``prefix``, with ``rope(cfg, tokens) -> (cos, sin)`` made once a
    forward; ``window`` names the config's field of a band's keys;
    ``gate``, ``rescale`` and ``index`` are the module's docstring's."""
    def leaves(cfg):
        h, sz = cfg.hidden_size, sizes(cfg, prefix)
        H, rq, rkv = sz.heads, sz.q_rank, sz.kv_rank
        dn, dr, dv = sz.d_n, sz.d_r, sz.d_v
        # no query latent (``q_lora_rank`` None): one ``wq`` from the
        # layer's normed input
        queries = {"wq": Leaf((h, H * (dn + dr)), h, ("embed", "qkv"))
                   } if rq is None else {
            "wq_a": Leaf((h, rq), h, ("embed", None)),
            "q_a_norm": Leaf((rq,), "ones", (None,)),
            "wq_b": Leaf((rq, H * (dn + dr)), h if rescale else rq,
                         (None, "qkv"))}
        out = {"attn_norm": Leaf((h,), "ones", ("embed",)), **queries,
               "wkv_a": Leaf((h, rkv + dr), h, ("embed", None)),
               "kv_a_norm": Leaf((rkv,), "ones", (None,)),
               "wkv_b": Leaf((rkv, H * (dn + dv)), h if rescale else rkv,
                             (None, "qkv")),
               "wo": Leaf((H * dv, h), sz.heads_of * dv, ("qkv", "embed"))}
        if gate:
            out["wg"] = Leaf((h, H), h, ("embed", None))
        if index:
            out.update(dsa.index_leaves(cfg, rq or h))
        return out

    def body(cfg, x, p, ctx):
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        b, s, _ = x.shape
        sz = sizes(cfg, prefix)
        H, rkv = sz.heads, sz.kv_rank
        dn, dr, dv = sz.d_n, sz.d_r, sz.d_v
        scale = softmax_scale(cfg, prefix)
        seen = getattr(cfg, window) if window else None
        more = {}
        if window:
            more["window"] = seen
        if gate or rescale or index:
            more.update(gate=gate, rescale=rescale, index=index)
        with tracing.span("rtpu.mla.shapes", keep=True, heads=H,
                          heads_of=sz.heads_of,
                          q_lora_rank=sz.q_rank,
                          kv_lora_rank=rkv, qk_nope_head_dim=dn,
                          qk_rope_head_dim=dr, v_head_dim=dv,
                          softmax_scale=scale, **more):
            pass

        def dot(a, w):
            return jnp.dot(a, w.astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)

        cos, sin = ctx.once[rope]
        with jax.named_scope("mla_q"):
            u = rms_norm(x, p["attn_norm"], eps)
            # the two latents before their norms are what the ladder's
            # first rung keeps of this layer (models/llama.py REMAT_LADDER):
            # the backward then runs neither down-projection again
            if sz.q_rank is None:
                c_qn, q = u, dot(u, p["wq"]).reshape(b, s, H, dn + dr)
            else:
                c_q = checkpoint_name(dot(u, p["wq_a"]), "q_latent")
                c_qn = rms_norm(c_q, p["q_a_norm"], eps)
                q = dot(c_qn, p["wq_b"]).reshape(b, s, H, dn + dr)
            if rescale and sz.q_rank is not None:
                q = q * jnp.asarray(rescale_factor(cfg, sz.q_rank), dt)
        with jax.named_scope("mla_kv"):
            c_kv = checkpoint_name(dot(u, p["wkv_a"]), "kv_latent")
            c_kvn = rms_norm(c_kv[..., :rkv], p["kv_a_norm"], eps)
            if rescale:
                c_kvn = c_kvn * jnp.asarray(rescale_factor(cfg, rkv), dt)
            kv = dot(c_kvn, p["wkv_b"]).reshape(b, s, H, dn + dv)
        with jax.named_scope("mla_rope"):
            q = jnp.concatenate(
                [q[..., :dn], _rotate(q[..., dn:], cos, sin)], axis=-1)
            k_r = _rotate(c_kv[:, :, None, rkv:], cos, sin)[:, :, 0]
        q = checkpoint_name(q, "q_rope")
        k_r = checkpoint_name(k_r, "k_rope")
        k_n = checkpoint_name(kv[..., :dn], "k_rope")
        v = checkpoint_name(kv[..., dn:], "v_proj")
        if gate:
            with jax.named_scope("attn_gate"):
                g = head_gate(cfg, u, p["wg"])
        said = {}
        if index:
            with jax.named_scope("dsa_proj"):
                q_i, k_i, w_i = index_inputs(cfg, u, c_qn, p, cos, sin)
            keep = ctx.keep_index_choice
            attn, kl, pairs, *choice = dsa.sparse_attention(
                q, k_n, v, k_r, q_i, k_i, w_i, scale=scale,
                topk=cfg.index_topk, block=cfg.index_block,
                tiers=cfg.index_tiers, mesh=ctx.mesh, keep_choice=keep)
            said = dsa.index_report(b, s, kl, pairs, {
                "choice": choice[0], "q_i": q_i, "k_i": k_i,
                "w": w_i} if keep else None)
        else:
            with jax.named_scope("flash"):
                if window:
                    with jax.named_scope("flash_window"):
                        attn = _attend(cfg, q, k_n, v, k_r, scale, ctx.mesh,
                                       seen)
                else:
                    attn = _attend(cfg, q, k_n, v, k_r, scale, ctx.mesh)
        with jax.named_scope("mla_out"):
            if gate:
                with jax.named_scope("attn_gate"):
                    attn = (attn.astype(jnp.float32) * g[..., None]
                            ).astype(dt)
            out = dot(attn.reshape(b, s, H * dv), p["wo"])
            return checkpoint_name(x + out, "attn_resid"), said

    def keeps(cfg, shape, tokens, mesh):
        sz = sizes(cfg, prefix)
        dv, dr = sz.d_v, sz.d_r
        H = shape["wo"][0] // dv
        act = jnp.dtype(cfg.dtype).itemsize
        # (without a query latent the queries are an expansion alone)
        latents = (shape["wq_a"][-1] if "wq_a" in shape else 0
                   ) + shape["wkv_a"][-1]
        expanded = shape["wq_b" if "wq_b" in shape else "wq"][-1] \
            + shape["wkv_b"][-1]
        # the gate a head, and the index's queries, key and head weights:
        # recomputed at every level, held by the layer's backward
        beside = ((shape["wg"][-1] if gate else 0)
                  + (shape["wi_q"][-1] + shape["wi_k"][-1]
                     + 2 * shape["wi_w"][-1] if index else 0))
        rows = dsa.walk_rows(cfg, tokens, dsa.Widths(
            H, sz.d_n, dr, dv, shape["wi_w"][-1], shape["wi_k"][-1],
            cfg.dtype)) if index else 0
        return kept(
            first=tokens * (shape["wo"][0] * act + H * 4 + latents * act)
            if not index else tokens * latents * act,
            qkv=tokens * (expanded + dr) * act,
            resid=tokens * cfg.hidden_size * act,
            width=2 * (latents + expanded) + 2 * beside, rows=rows)

    return Part(leaves, body, keeps, once=rope,
                **({"reports": "dsa", "terms": index_terms} if index else {}))


def _attend(cfg, q, k_n, v, k_r, scale: float, mesh,
            window: Optional[int] = None):
    """Causal attention of q [b, s, H, d_n + d_r] over keys [k_n | k_r]
    (k_n [b, s, H, d_n], k_r [b, s, d_r] shared by the heads) and values v
    [b, s, H, d_v], under a ``window`` the keys that end at the query's
    position alone: the flash kernels on a TPU, each chip its own rows of
    the batch under a mesh, and the reference elsewhere."""
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return attention_reference(q, with_shared_key(k_n, k_r), v,
                                   causal=True, sm_scale=scale,
                                   window=window)
    if impl != "flash":
        raise ValueError(f"attn_impl={impl!r}: latent attention runs "
                         "\"flash\" or \"reference\"")

    def flash(q_, k_, v_, kr_):
        return flash_attention(q_, k_, v_, causal=True, sm_scale=scale,
                               k_shared=kr_, window=window)

    if mesh is None:
        return flash(q, k_n, v, k_r)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import resolve_axis

    rows = resolve_axis("batch", mesh)
    spec = P(rows, None, None, None)
    return jax.shard_map(flash, mesh=mesh,
                         in_specs=(spec, spec, spec, P(rows, None, None)),
                         out_specs=spec, check_vma=False)(q, k_n, v, k_r)
