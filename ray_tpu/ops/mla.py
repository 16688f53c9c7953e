"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) as a layer's
mixer, for training.

Queries and keys/values each pass through a low-rank latent with an
RMSNorm of its own:

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

Named scopes: ``mla_q`` (the layer's norm, both query projections and the
latent's norm), ``mla_kv`` (the two key/value projections and the latent's
norm), ``mla_rope``, ``flash``, ``mla_out``. One kept span as the part is
traced, ``rtpu.mla.shapes``. Training only: a latent cache and the absorbed
decode path are the serving engines' to come.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   with_shared_key)
from ray_tpu.ops.layers import (Leaf, Part, apply_rope, kept, rms_norm,
                                rope_frequencies)
from ray_tpu.util import tracing


def softmax_scale(cfg) -> float:
    """``(d_n + d_r) ** -0.5``, times ``m ** 2`` where the config's rope
    scaling states an ``mscale_all_dim`` (the published code's
    ``yarn_get_mscale``)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
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


def latent_attention_part() -> Part:
    """Latent attention as a layer's mixer, from the config's
    ``num_heads``, ``heads_of``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim`` (the
    module's docstring)."""
    def leaves(cfg):
        h, H = cfg.hidden_size, cfg.num_heads
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        return {"attn_norm": Leaf((h,), "ones", ("embed",)),
                "wq_a": Leaf((h, rq), h, ("embed", None)),
                "q_a_norm": Leaf((rq,), "ones", (None,)),
                "wq_b": Leaf((rq, H * (dn + dr)), rq, (None, "qkv")),
                "wkv_a": Leaf((h, rkv + dr), h, ("embed", None)),
                "kv_a_norm": Leaf((rkv,), "ones", (None,)),
                "wkv_b": Leaf((rkv, H * (dn + dv)), rkv, (None, "qkv")),
                "wo": Leaf((H * dv, h), (cfg.heads_of or H) * dv,
                           ("qkv", "embed"))}

    def body(cfg, x, p, ctx):
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        b, s, _ = x.shape
        H, rkv = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        scale = softmax_scale(cfg)
        with tracing.span("rtpu.mla.shapes", keep=True, heads=H,
                          heads_of=cfg.heads_of or H,
                          q_lora_rank=cfg.q_lora_rank,
                          kv_lora_rank=rkv, qk_nope_head_dim=dn,
                          qk_rope_head_dim=dr, v_head_dim=dv,
                          softmax_scale=scale):
            pass

        def dot(a, w):
            return jnp.dot(a, w.astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)

        cos, sin = ctx.once[rope_tables]
        with jax.named_scope("mla_q"):
            u = rms_norm(x, p["attn_norm"], eps)
            # the two latents before their norms are what the ladder's
            # first rung keeps of this layer (models/llama.py REMAT_LADDER):
            # the backward then runs neither down-projection again
            c_q = checkpoint_name(dot(u, p["wq_a"]), "q_latent")
            q = dot(rms_norm(c_q, p["q_a_norm"], eps),
                    p["wq_b"]).reshape(b, s, H, dn + dr)
        with jax.named_scope("mla_kv"):
            c_kv = checkpoint_name(dot(u, p["wkv_a"]), "kv_latent")
            kv = dot(rms_norm(c_kv[..., :rkv], p["kv_a_norm"], eps),
                     p["wkv_b"]).reshape(b, s, H, dn + dv)
        with jax.named_scope("mla_rope"):
            q = jnp.concatenate(
                [q[..., :dn], _rotate(q[..., dn:], cos, sin)], axis=-1)
            k_r = _rotate(c_kv[:, :, None, rkv:], cos, sin)[:, :, 0]
        q = checkpoint_name(q, "q_rope")
        k_r = checkpoint_name(k_r, "k_rope")
        k_n = checkpoint_name(kv[..., :dn], "k_rope")
        v = checkpoint_name(kv[..., dn:], "v_proj")
        with jax.named_scope("flash"):
            attn = _attend(cfg, q, k_n, v, k_r, scale, ctx.mesh)
        with jax.named_scope("mla_out"):
            out = dot(attn.reshape(b, s, H * dv), p["wo"])
            return checkpoint_name(x + out, "attn_resid"), {}

    def keeps(cfg, shape, tokens, mesh):
        H = shape["wo"][0] // cfg.v_head_dim
        act = jnp.dtype(cfg.dtype).itemsize
        latents = shape["wq_a"][-1] + shape["wkv_a"][-1]
        expanded = shape["wq_b"][-1] + shape["wkv_b"][-1]
        return kept(
            flash=tokens * (shape["wo"][0] * act + H * 4 + latents * act),
            qkv=tokens * (expanded + cfg.qk_rope_head_dim) * act,
            resid=tokens * cfg.hidden_size * act,
            width=2 * (latents + expanded))

    return Part(leaves, body, keeps, once=rope_tables)


def _attend(cfg, q, k_n, v, k_r, scale: float, mesh):
    """Causal attention of q [b, s, H, d_n + d_r] over keys [k_n | k_r]
    (k_n [b, s, H, d_n], k_r [b, s, d_r] shared by the heads) and values v
    [b, s, H, d_v]: the flash kernels on a TPU, each chip its own rows of
    the batch under a mesh, and the reference elsewhere."""
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return attention_reference(q, with_shared_key(k_n, k_r), v,
                                   causal=True, sm_scale=scale)
    if impl != "flash":
        raise ValueError(f"attn_impl={impl!r}: latent attention runs "
                         "\"flash\" or \"reference\"")

    def flash(q_, k_, v_, kr_):
        return flash_attention(q_, k_, v_, causal=True, sm_scale=scale,
                               k_shared=kr_)

    if mesh is None:
        return flash(q, k_n, v, k_r)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import resolve_axis

    rows = resolve_axis("batch", mesh)
    spec = P(rows, None, None, None)
    return jax.shard_map(flash, mesh=mesh,
                         in_specs=(spec, spec, spec, P(rows, None, None)),
                         out_specs=spec, check_vma=False)(q, k_n, v, k_r)
