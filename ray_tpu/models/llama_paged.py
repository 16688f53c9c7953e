"""Paged-KV inference path for the Llama family: chunked prefill +
block-table decode over a shared page pool.

Extends the static-slot design (models/llama_decode.py) the way vLLM's
PagedAttention extends dense slot caches on GPU — re-thought for TPU
static shapes:

- The cache is a POOL: ``[L, P, KVH, page, hd]`` (layers, num_pages,
  kv_heads, page_size, head_dim — (page, hd) minor so the Pallas
  kernel's page blocks satisfy TPU tiling).
  A sequence owns an ordered page list (its block table, host-side).
  HBM cost tracks ACTUAL tokens in flight, not slots × max_len, so one
  chip holds far longer contexts; identical prompt prefixes share pages
  (serve/paged_engine.py's prefix cache).
- Prefill is CHUNKED: the prompt runs through ``prefill_chunk`` in
  bucket-sized pieces, each attending to the pages written so far plus
  itself causally. Prompt length is bounded by max context, not by the
  prefill bucket; a long prompt never stalls the decode batch for more
  than one chunk.
- Decode gathers each slot's pages: the Pallas page-gather kernel
  (ops/paged_attention.py) on a bare TPU, the XLA gather path under
  GSPMD/tensor-parallel or on CPU. The in-flight token's K/V merges via
  an explicit self-term (exact online-softmax merge), and lands in the
  pool with one in-place scatter — the same HBM discipline as the dense
  decode_step.

All programs keep static shapes: block tables are [S, MAXP] with MAXP =
ceil(max_context / page_size); trailing entries are clamped/masked.
Reference analogue: the reference ships no paging at all (it serves via
torch); the public analogue is vLLM's PagedAttention, rebuilt TPU-first.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.llama_decode import _mlp, _project_qkv, _w, sample_tokens
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies

_NEG_INF = -1e30


def init_paged_cache(cfg: LlamaConfig, num_pages: int, page_size: int,
                     mesh=None) -> Dict[str, jax.Array]:
    """Pool layout [L, P, KVH, page, hd]: (page, hd) stay the minor dims
    so the Pallas kernel's page blocks satisfy TPU tiling (÷8, ÷128)."""
    hd = cfg.head_dim_
    shape = (cfg.num_layers, num_pages, cfg.num_kv_heads, page_size, hd)
    cache = {"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
    if mesh is not None:
        cache = jax.device_put(cache, paged_cache_shardings(cfg, mesh))
    return cache


def paged_cache_shardings(cfg: LlamaConfig, mesh):
    """Page-pool shardings under tensor parallelism: the KV-head axis
    shards over ``tp`` (same rule as the dense cache — each chip owns
    its heads' pages); replicate when tp does not divide KVH."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = dict(getattr(mesh, "shape", {})).get("tp", 1)
    if tp > 1 and cfg.num_kv_heads % tp == 0:
        sh = NamedSharding(mesh, P(None, None, "tp", None, None))
    else:
        sh = NamedSharding(mesh, P())
    return {"k": sh, "v": sh}


def prefill_chunk(cfg: LlamaConfig, params, cache: Dict[str, jax.Array],
                  tokens: jax.Array, block_table: jax.Array,
                  ctx0: jax.Array, n_valid: jax.Array
                  ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """One prompt chunk for ONE sequence: tokens [1, C] (padded), at
    global positions ctx0..ctx0+n_valid-1; block_table [MAXP] covers the
    pages allocated so far (history AND this chunk's span).

    Attends to the pages written by previous chunks (positions < ctx0)
    plus itself causally, writes its K/V into the pool (pad positions
    dropped), and returns (cache, logits [1, vocab] at the chunk's last
    valid token) — the final chunk's logits seed the first generated
    token.
    """
    C = tokens.shape[1]
    hd = cfg.head_dim_
    page = cache["k"].shape[3]
    num_pages = cache["k"].shape[1]
    MAXP = block_table.shape[0]
    T_hist = MAXP * page
    rep = cfg.num_heads // cfg.num_kv_heads

    x = params["embed"].astype(cfg.dtype)[tokens]          # [1, C, h]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    # rope table over the full context window; chunk rows use absolute
    # positions ctx0+i
    Tmax = T_hist
    cos, sin = rope_frequencies(hd, Tmax, cfg.rope_theta, dtype=cfg.dtype,
                                scaling=cfg.rope_scaling_dict)
    pos_c = ctx0 + jnp.arange(C, dtype=jnp.int32)          # [C]
    ci = jnp.arange(C, dtype=jnp.int32)

    # masks are position-only — shared across layers
    hist_mask = (jnp.arange(T_hist)[None] < ctx0)          # [1, T_hist]
    self_mask = ci[:, None] >= ci[None, :]                 # [C, C] causal
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def layer(x, inp):
        p, kp, vp = inp                                    # pages [P,KVH,pg,hd]
        with jax.named_scope("attn"):
            x, k, v = attend(x, p, kp, vp)
        with jax.named_scope("mlp"):
            x = x + _mlp(cfg, p, x)
        return x, (k[0], v[0])                             # [C, KVH, hd]

    def attend(x, p, kp, vp):
        q, k, v, _ = _project_qkv(cfg, p, x)               # [1,C,H,hd]
        q = apply_rope(q, cos, sin, positions=pos_c[None])
        k = apply_rope(k, cos, sin, positions=pos_c[None])
        # [MAXP, KVH, page, hd] -> [KVH, T_hist, hd]
        ks = jnp.moveaxis(kp[block_table], 1, 0).reshape(
            cfg.num_kv_heads, T_hist, hd)
        vs = jnp.moveaxis(vp[block_table], 1, 0).reshape(
            cfg.num_kv_heads, T_hist, hd)
        q2 = q[0].reshape(C, cfg.num_kv_heads, rep, hd)
        s_hist = jnp.einsum("ckgd,ktd->ckgt", q2, ks,
                            preferred_element_type=jnp.float32) * scale
        s_hist = jnp.where(hist_mask[0][None, None, None], s_hist,
                           _NEG_INF)
        s_self = jnp.einsum("ckgd,ukd->ckgu", q2, k[0],
                            preferred_element_type=jnp.float32) * scale
        s_self = jnp.where(self_mask[:, None, None], s_self, _NEG_INF)
        scores = jnp.concatenate([s_hist, s_self], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        attn = (jnp.einsum("ckgt,ktd->ckgd", probs[..., :T_hist], vs)
                + jnp.einsum("ckgu,ukd->ckgd", probs[..., T_hist:], v[0]))
        attn = attn.reshape(1, C, cfg.num_heads * hd)
        x = x + jnp.dot(attn, _w(p, "wo", cfg.dtype),
                        preferred_element_type=jnp.float32).astype(cfg.dtype)
        return x, k, v

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], cache["k"], cache["v"]))
    # one scatter of the whole chunk into the pool: position ctx0+i goes
    # to page block_table[(ctx0+i)//page] at offset (ctx0+i)%page; pad
    # rows (i >= n_valid) redirect out of bounds and drop. Non-adjacent
    # advanced indices (dims 1 and 3) put the index dim FIRST in the
    # update: [C, L, KVH, hd].
    with jax.named_scope("pool_copy"):
        pidx = block_table[jnp.clip(pos_c // page, 0, MAXP - 1)]
        pidx = jnp.where(ci < n_valid, pidx, num_pages)
        poff = pos_c % page
        upd_k = jnp.moveaxis(new_k, 1, 0)                  # [C, L, KVH, hd]
        upd_v = jnp.moveaxis(new_v, 1, 0)
        ck = cache["k"].at[:, pidx, :, poff].set(upd_k, mode="drop",
                                                 unique_indices=True)
        cv = cache["v"].at[:, pidx, :, poff].set(upd_v, mode="drop",
                                                 unique_indices=True)
    with jax.named_scope("sample"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        x_last = x[0, jnp.maximum(n_valid - 1, 0)]         # [h]
        head = (params["embed"].astype(cfg.dtype).T if cfg.tie_embeddings
                else _w(params, "lm_head", cfg.dtype))
        logits = jnp.dot(x_last[None], head,
                         preferred_element_type=jnp.float32)  # [1, vocab]
    return {"k": ck, "v": cv}, logits


def paged_decode_step(cfg: LlamaConfig, params, cache: Dict[str, jax.Array],
                      tokens: jax.Array, positions: jax.Array,
                      active: jax.Array, block_table: jax.Array,
                      use_kernel: bool = False, interpret: bool = False
                      ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """One token for every slot over paged KV.

    tokens/positions/active [S] as dense decode_step; block_table
    [S, MAXP] int32. History attention streams pages (Pallas kernel when
    ``use_kernel``); the in-flight token merges via an exact
    online-softmax self-term; new K/V lands in one in-place scatter.
    """
    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    S = tokens.shape[0]
    page = cache["k"].shape[3]
    num_pages = cache["k"].shape[1]
    MAXP = block_table.shape[1]
    hd = cfg.head_dim_
    rep = cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    x = params["embed"].astype(cfg.dtype)[tokens][:, None]  # [S, 1, h]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    cos, sin = rope_frequencies(hd, MAXP * page, cfg.rope_theta,
                                dtype=cfg.dtype,
                                scaling=cfg.rope_scaling_dict)
    pos2 = positions[:, None]

    def layer(carry, inp):
        x = carry
        p, kp, vp = inp
        with jax.named_scope("attn"):
            x, k1, v1 = attend(x, p, kp, vp)
        with jax.named_scope("mlp"):
            x = x + _mlp(cfg, p, x)
        return x, (k1, v1)

    def attend(x, p, kp, vp):
        q, k, v, _ = _project_qkv(cfg, p, x)
        q = apply_rope(q, cos, sin, positions=pos2)
        k = apply_rope(k, cos, sin, positions=pos2)
        k1, v1 = k[:, 0], v[:, 0]                          # [S, KVH, hd]
        q2 = q[:, 0].reshape(S, cfg.num_kv_heads, rep, hd)
        if use_kernel:
            acc, m, l = paged_attention(q2, kp, vp, block_table,
                                        positions, interpret=interpret)
        else:
            acc, m, l = paged_attention_reference(q2, kp, vp, block_table,
                                                  positions)
        # exact merge of the in-flight token's self term into the
        # flash-style (acc, m, l) triple
        s_self = jnp.einsum("skgd,skd->skg", q2, k1,
                            preferred_element_type=jnp.float32) * scale
        m_tot = jnp.maximum(m, s_self)
        alpha = jnp.exp(m - m_tot)
        p_self = jnp.exp(s_self - m_tot)
        num = (acc * alpha[..., None]
               + p_self[..., None] * v1[:, :, None, :].astype(jnp.float32))
        den = l * alpha + p_self
        attn = (num / jnp.maximum(den, 1e-30)[..., None]).astype(cfg.dtype)
        attn = attn.reshape(S, 1, cfg.num_heads * hd)
        x = x + jnp.dot(attn, _w(p, "wo", cfg.dtype),
                        preferred_element_type=jnp.float32).astype(cfg.dtype)
        return x, k1, v1

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], cache["k"], cache["v"]))
    with jax.named_scope("pool_copy"):
        pidx = jnp.take_along_axis(
            block_table, jnp.clip(positions // page, 0, MAXP - 1)[:, None],
            axis=1)[:, 0]
        pidx = jnp.where(active, pidx, num_pages)          # drop inactive
        poff = positions % page
        # non-adjacent advanced indices (dims 1, 3): update is
        # [S, L, KVH, hd]
        ck = cache["k"].at[:, pidx, :, poff].set(
            jnp.moveaxis(new_k, 1, 0), mode="drop")
        cv = cache["v"].at[:, pidx, :, poff].set(
            jnp.moveaxis(new_v, 1, 0), mode="drop")
    with jax.named_scope("sample"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = (params["embed"].astype(cfg.dtype).T if cfg.tie_embeddings
                else _w(params, "lm_head", cfg.dtype))
        logits = jnp.dot(x[:, 0], head,
                         preferred_element_type=jnp.float32)
    return {"k": ck, "v": cv}, logits


def paged_decode_chunk(cfg: LlamaConfig, params,
                       cache: Dict[str, jax.Array], tokens: jax.Array,
                       positions: jax.Array, active: jax.Array,
                       block_table: jax.Array, num_steps: int,
                       rng: Optional[jax.Array] = None,
                       temperature: Optional[jax.Array] = None,
                       top_k: int = 0, sample: bool = True,
                       use_kernel: bool = False, interpret: bool = False
                       ) -> Tuple[Dict[str, jax.Array], jax.Array,
                                  jax.Array, jax.Array]:
    """``num_steps`` paged decode steps in one program, chaining tokens
    on device exactly like the dense decode_chunk (same return contract:
    cache, out [k, S], next_tokens [S], next_positions [S]). The block
    table must already cover positions+num_steps tokens per active slot
    (the engine's allocator grows tables before dispatch)."""
    S = tokens.shape[0]
    if temperature is None:
        temperature = jnp.zeros((S,), jnp.float32)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def step(carry, _):
        cache, toks, pos, key = carry
        cache, logits = paged_decode_step(
            cfg, params, cache, toks, pos, active, block_table,
            use_kernel=use_kernel, interpret=interpret)
        with jax.named_scope("sample"):
            if sample:
                key, sub = jax.random.split(key)
                nxt = sample_tokens(logits, sub, temperature, top_k)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, toks)
        return (cache, nxt, pos + active.astype(jnp.int32), key), nxt

    (cache, nxt, pos, _), out = jax.lax.scan(
        step, (cache, tokens, positions, rng), None, length=num_steps)
    return cache, out, nxt, pos


def make_paged_engine_fns(cfg: LlamaConfig, params, mesh=None,
                          use_kernel: Optional[bool] = None):
    """Jitted paged-engine programs (params as jit ARGUMENTS — a closure
    would bake the weights into the HLO as literals; see
    llama_decode.make_engine_fns). Pool geometry (num_pages, page_size,
    slot count) lives in the cache/block-table ARRAYS the returned
    programs take, not here — the jitted programs specialize on those
    shapes at first call.

    use_kernel: None → Pallas page-gather on a bare TPU, XLA gather under
    a mesh (GSPMD cannot shard a Pallas call) or off-TPU.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and mesh is None
    if mesh is not None:
        from ray_tpu.models import llama as _llama

        params = jax.device_put(params, _llama.param_shardings(cfg, mesh))
    prefill_j = jax.jit(prefill_chunk, static_argnums=(0,),
                        donate_argnums=(2,))
    chunk_j = jax.jit(paged_decode_chunk,
                      static_argnums=(0, 7, 10, 11, 12, 13),
                      donate_argnums=(2,))

    def pre(cache, tokens, block_table, ctx0, n_valid):
        return prefill_j(cfg, params, cache, tokens, block_table, ctx0,
                         n_valid)

    def dec_chunk(cache, tokens, positions, active, block_table,
                  num_steps, rng=None, temperature=None, top_k=0,
                  sample=True):
        return chunk_j(cfg, params, cache, tokens, positions, active,
                       block_table, num_steps, rng, temperature, top_k,
                       sample, use_kernel, False)

    # same signatures, lowered instead of run (see llama_decode)
    pre.lower = lambda cache, tokens, block_table, ctx0, n_valid: \
        prefill_j.lower(cfg, params, cache, tokens, block_table, ctx0,
                        n_valid)
    dec_chunk.lower = lambda cache, tokens, positions, active, \
        block_table, num_steps, rng, temperature, top_k, sample: \
        chunk_j.lower(cfg, params, cache, tokens, positions, active,
                      block_table, num_steps, rng, temperature, top_k,
                      sample, use_kernel, False)
    return pre, dec_chunk
