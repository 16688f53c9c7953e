"""KV-cached inference path for the Llama model: prefill + single-token
decode over a STATIC slot cache.

TPU-first design (none of this is in the reference — it serves via torch):
the serving cache is a fixed tensor ``[layers, slots, max_len, kv_heads,
head_dim]``. Every shape is static, so XLA compiles a handful of programs —
one prefill per bucket size, one decode chunk per size — and reuses them
for the lifetime of the server. Slot admission/eviction is pure
bookkeeping on the host. This dense path is the fastest at short
contexts (contiguous cache reads); models/llama_paged.py adds the paged
variant (page pool + block tables + prefix cache) for long/ragged
contexts and shared prompts.

Used by serve/llm_engine.py (continuous batching: new sequences join the
decode batch between steps by prefilling into a free slot).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu


def init_cache(cfg: LlamaConfig, num_slots: int, max_len: int,
               mesh=None) -> Dict[str, jax.Array]:
    hd = cfg.head_dim_
    shape = (cfg.num_layers, num_slots, max_len, cfg.num_kv_heads, hd)
    cache = {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }
    if mesh is not None:
        cache = jax.device_put(cache, cache_shardings(cfg, mesh))
    return cache


def cache_shardings(cfg: LlamaConfig, mesh):
    """Slot-cache shardings for tensor-parallel decode: the KV-head axis
    of [L, S, T, KVH, hd] shards over ``tp`` (each chip owns its heads'
    cache — the per-chip HBM saving is the point of TP serving). When
    tp does not divide KVH (GQA with few KV heads), the cache replicates —
    the standard fallback; Q heads still split."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = dict(getattr(mesh, "shape", {})).get("tp", 1)
    if tp > 1 and cfg.num_kv_heads % tp == 0:
        sh = NamedSharding(mesh, P(None, None, None, "tp", None))
    else:
        sh = NamedSharding(mesh, P())
    return {"k": sh, "v": sh}


def _project_qkv(cfg: LlamaConfig, p, x):
    """x [b, s, h] -> q [b,s,H,hd], k/v [b,s,KVH,hd] with rope NOT applied."""
    b, s, _ = x.shape
    hd = cfg.head_dim_
    h1 = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    q = jnp.dot(h1, _w(p, "wq", cfg.dtype),
                preferred_element_type=jnp.float32).astype(cfg.dtype)
    k = jnp.dot(h1, _w(p, "wk", cfg.dtype),
                preferred_element_type=jnp.float32).astype(cfg.dtype)
    v = jnp.dot(h1, _w(p, "wv", cfg.dtype),
                preferred_element_type=jnp.float32).astype(cfg.dtype)
    if "bq" in p:  # Qwen2-style qkv biases
        q = q + p["bq"].astype(cfg.dtype)
        k = k + p["bk"].astype(cfg.dtype)
        v = v + p["bv"].astype(cfg.dtype)
    return (q.reshape(b, s, cfg.num_heads, hd),
            k.reshape(b, s, cfg.num_kv_heads, hd),
            v.reshape(b, s, cfg.num_kv_heads, hd), h1)


def _mlp(cfg: LlamaConfig, p, x):
    h2 = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    return swiglu(h2, _w(p, "w_gate", cfg.dtype),
                  _w(p, "w_up", cfg.dtype), _w(p, "w_down", cfg.dtype),
                  act=cfg.mlp_act)


def _w(p, name: str, dtype):
    """Weight-leaf access: a plain array, or an int8 weight-only
    quantized leaf {"q": int8 [..., in, out], "s": f32 [..., 1, out]}
    dequantized on the fly. Decode is HBM-bandwidth-bound on weight
    reads; int8 halves that traffic and XLA fuses the convert+scale
    into the consuming dot's operand load."""
    v = p[name]
    if isinstance(v, dict):
        return v["q"].astype(dtype) * v["s"].astype(dtype)
    return v.astype(dtype)


# matmul weights eligible for weight-only quantization (biases, norms
# and the embedding gather stay in their original dtypes)
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_decode_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Per-output-channel symmetric int8 weight-only quantization of the
    decode params (serving only — training keeps full precision). Each
    [..., in, out] matmul weight becomes {"q": int8, "s": f32} with
    s = max|w| / 127 per output column. Quality: ~1e-2 relative logit
    error at 1B scale (see tests); throughput: weight HBM reads halve,
    which is the decode bottleneck."""

    def qz(w):
        w32 = w.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True),
                        1e-8) / 127.0
        q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
        return {"q": q, "s": s}

    out = dict(params)
    layers = dict(params["layers"])
    for k in _QUANT_KEYS:
        if k in layers:
            layers[k] = qz(layers[k])
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = qz(params["lm_head"])
    return out


def _prefill_attention(cfg: LlamaConfig, q, k, v):
    """Causal prefill attention: the Pallas flash kernel on TPU (GQA
    handled in-kernel, no repeated-KV materialization, no [b,H,P,P]
    score tensor), the fp32 reference path elsewhere. The kernel needs
    the sequence divisible by its block size, which holds for the
    power-of-two buckets but NOT the engine's max_len-1 overflow
    bucket — that one (and any other ragged length) takes the reference
    path instead of crashing at trace time; ``LLMEngine.report()``
    shows per bucket which one was compiled in."""
    from ray_tpu.ops.attention import attention_reference, flash_attention

    use_flash = cfg.prefill_flash
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash and q.shape[1] % 128 == 0:
        return flash_attention(q, k, v, causal=True)
    return attention_reference(q, k, v, causal=True)


def prefill(cfg: LlamaConfig, params, tokens: jax.Array
            ) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """Run the prompt through the model capturing per-layer K/V.

    tokens: [1, P] (P = padded bucket length).
    Returns (logits_last [vocab], kv {"k","v": [L, P, KVH, hd]},
    hidden-unused) — the engine inserts kv into a cache slot and samples
    the first generated token from logits_last at the true prompt length.
    """
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    P = tokens.shape[1]
    cos, sin = rope_frequencies(cfg.head_dim_, P, cfg.rope_theta,
                                dtype=cfg.dtype,
                                scaling=cfg.rope_scaling_dict)

    def layer(x, p):
        b, s, _ = x.shape
        q, k, v, _ = _project_qkv(cfg, p, x)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = _prefill_attention(cfg, q, k, v)
        attn = attn.reshape(b, s, cfg.num_heads * cfg.head_dim_)
        x = x + jnp.dot(attn, _w(p, "wo", cfg.dtype),
                        preferred_element_type=jnp.float32).astype(cfg.dtype)
        x = x + _mlp(cfg, p, x)
        return x, (k[0], v[0])  # [P, KVH, hd]

    x, kv = jax.lax.scan(lambda x_, p_: layer(x_, p_), x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = (params["embed"].astype(cfg.dtype).T if cfg.tie_embeddings
            else _w(params, "lm_head", cfg.dtype))
    logits = jnp.dot(x[0], head,
                     preferred_element_type=jnp.float32)  # [P, vocab]
    return logits, {"k": kv[0], "v": kv[1]}, x


def prefill_batch(cfg: LlamaConfig, params, tokens: jax.Array,
                  last_idx: jax.Array
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Batched prompt prefill: B prompts in one program.

    tokens: [B, P] (rows padded to the bucket length), last_idx: [B] (index
    of each row's true last prompt token). Returns (logits_last [B, vocab],
    kv {"k","v": [L, B, P, KVH, hd]}). One batched call replaces B
    sequential prefills — under burst admission this divides the
    prefill-phase host↔device round-trips by B.
    """
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    P = tokens.shape[1]
    cos, sin = rope_frequencies(cfg.head_dim_, P, cfg.rope_theta,
                                dtype=cfg.dtype,
                                scaling=cfg.rope_scaling_dict)

    def layer(x, p):
        b, s, _ = x.shape
        q, k, v, _ = _project_qkv(cfg, p, x)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = _prefill_attention(cfg, q, k, v)
        attn = attn.reshape(b, s, cfg.num_heads * cfg.head_dim_)
        x = x + jnp.dot(attn, _w(p, "wo", cfg.dtype),
                        preferred_element_type=jnp.float32).astype(cfg.dtype)
        x = x + _mlp(cfg, p, x)
        return x, (k, v)  # [B, P, KVH, hd]

    x, kv = jax.lax.scan(lambda x_, p_: layer(x_, p_), x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # gather each row's last true prompt position, then ONE [B, vocab]
    # head matmul (a full [B, P, vocab] logits tensor would be ~P times
    # the transfer and FLOPs for the same information)
    B = tokens.shape[0]
    x_last = x[jnp.arange(B), last_idx]  # [B, h]
    head = (params["embed"].astype(cfg.dtype).T if cfg.tie_embeddings
            else _w(params, "lm_head", cfg.dtype))
    logits = jnp.dot(x_last, head,
                     preferred_element_type=jnp.float32)  # [B, vocab]
    return logits, {"k": kv[0], "v": kv[1]}


def insert_many(cache: Dict[str, jax.Array], kv: Dict[str, jax.Array],
                slots: jax.Array, valid: jax.Array
                ) -> Dict[str, jax.Array]:
    """Write B prefilled sequences into their cache slots in one program.

    kv: [L, B, P, KVH, hd]; slots [B] int32; valid [B] bool (padding rows
    of a partially-filled admission batch leave the cache untouched).
    """
    def body(cache, xs):
        k_row, v_row, slot, ok = xs   # k/v row: [L, P, KVH, hd]

        def write(c):
            k = jax.lax.dynamic_update_slice(
                c["k"], k_row[:, None], (0, slot, 0, 0, 0))
            v = jax.lax.dynamic_update_slice(
                c["v"], v_row[:, None], (0, slot, 0, 0, 0))
            return {"k": k, "v": v}

        return jax.lax.cond(ok, write, lambda c: c, cache), None

    cache, _ = jax.lax.scan(
        body, cache,
        (jnp.moveaxis(kv["k"], 1, 0), jnp.moveaxis(kv["v"], 1, 0),
         slots, valid))
    return cache


def insert_sequence(cache: Dict[str, jax.Array], kv: Dict[str, jax.Array],
                    slot: jax.Array) -> Dict[str, jax.Array]:
    """Write a prefilled sequence's K/V into cache slot ``slot``.
    kv arrays: [L, P, KVH, hd]; cache: [L, S, T, KVH, hd]. P <= T."""
    def write(c, s):
        # dynamic_update_slice at [0, slot, 0, 0, 0]
        return jax.lax.dynamic_update_slice(
            c, s[:, None], (0, slot, 0, 0, 0))
    return {"k": write(cache["k"], kv["k"]),
            "v": write(cache["v"], kv["v"])}


def decode_step(cfg: LlamaConfig, params, cache: Dict[str, jax.Array],
                tokens: jax.Array, positions: jax.Array,
                active: jax.Array
                ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """One token for every slot.

    tokens [S] int32 (last sampled token per slot), positions [S] int32
    (index the new token is written at), active [S] bool.
    Returns (cache, logits [S, vocab]).

    HBM discipline (the decode step is bandwidth-bound): attention runs
    over the OLD cache plus an explicit self-attention term for the
    in-flight token, so the big cache tensors are never rewritten by the
    attention path; the new K/V rows (L*S*KVH*hd elements, ~1 MB) land
    in ONE batched scatter at the end, which XLA performs in place on
    the donated cache. The previous design (scatter-then-attend via a
    full-width select inside the layer scan) rewrote the entire cache
    every step and measured 6.4 ms/step on v5e at 1B; this form measures
    ~3 ms — against a 2.3 ms weight-read floor.
    """
    S = tokens.shape[0]
    T = cache["k"].shape[2]
    hd = cfg.head_dim_
    x = params["embed"].astype(cfg.dtype)[tokens][:, None]  # [S, 1, h]
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
    cos_t, sin_t = rope_frequencies(hd, T, cfg.rope_theta,
                                    dtype=cfg.dtype,
                                    scaling=cfg.rope_scaling_dict)
    pos2 = positions[:, None]  # [S, 1] — per-slot rope positions

    # STRICT mask: history only; the current token's contribution enters
    # via the concatenated self-score below, not via the cache
    hist_mask = (jnp.arange(T)[None] < positions[:, None])  # [S, T]
    rep = cfg.num_heads // cfg.num_kv_heads

    def layer(carry, inp):
        x = carry
        p, ck, cv = inp
        q, k, v, _ = _project_qkv(cfg, p, x)     # q [S,1,H,hd], k/v [S,1,KVH,hd]
        q = apply_rope(q, cos_t, sin_t, positions=pos2)
        k = apply_rope(k, cos_t, sin_t, positions=pos2)
        k1, v1 = k[:, 0], v[:, 0]                # [S, KVH, hd]
        # GQA as a GROUPED einsum — no repeated-KV materialization (the
        # decode step is HBM-bound; repeating kv doubles cache traffic)
        q2 = q[:, 0].reshape(S, cfg.num_kv_heads, rep, hd)
        scores = jnp.einsum("skrd,stkd->skrt", q2, ck,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(hist_mask[:, None, None], scores, -1e30)
        self_s = jnp.einsum("skrd,skd->skr", q2, k1,
                            preferred_element_type=jnp.float32
                            ) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.concatenate([scores, self_s[..., None]], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        attn = (jnp.einsum("skrt,stkd->skrd", probs[..., :T], cv)
                + probs[..., T][..., None] * v1[:, :, None, :])
        attn = attn.reshape(S, 1, cfg.num_heads * hd)
        x = x + jnp.dot(attn, _w(p, "wo", cfg.dtype),
                        preferred_element_type=jnp.float32).astype(cfg.dtype)
        x = x + _mlp(cfg, p, x)
        return x, (k1, v1)

    x, (new_k, new_v) = jax.lax.scan(
        layer, x, (params["layers"], cache["k"], cache["v"]))
    # new_k/new_v: [L, S, KVH, hd] — one scatter into the donated cache.
    # Inactive slots redirect to index T, dropped by mode="drop", so
    # their cache lines are untouched.
    scat = jnp.where(active, positions, T)
    ck = cache["k"].at[:, jnp.arange(S), scat].set(
        new_k, mode="drop", unique_indices=True)
    cv = cache["v"].at[:, jnp.arange(S), scat].set(
        new_v, mode="drop", unique_indices=True)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = (params["embed"].astype(cfg.dtype).T if cfg.tie_embeddings
            else _w(params, "lm_head", cfg.dtype))
    logits = jnp.dot(x[:, 0], head,
                     preferred_element_type=jnp.float32)  # [S, vocab]
    return {"k": ck, "v": cv}, logits


def sample_tokens(logits: jax.Array, key: jax.Array,
                  temperature: jax.Array, top_k: int = 0) -> jax.Array:
    """Per-slot sampling: temperature 0 means greedy; ``top_k`` (static,
    0 = off) masks everything below the k-th logit. logits [S, vocab],
    temperature [S]. Mixed batches work — each slot applies its own
    temperature, so greedy and sampled requests share one program."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / temp,
                                     axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def decode_chunk(cfg: LlamaConfig, params, cache: Dict[str, jax.Array],
                 tokens: jax.Array, positions: jax.Array, active: jax.Array,
                 num_steps: int, rng: Optional[jax.Array] = None,
                 temperature: Optional[jax.Array] = None, top_k: int = 0,
                 sample: bool = True
                 ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array,
                            jax.Array]:
    """``num_steps`` decode steps in ONE device program.

    Amortizes host<->device dispatch latency across many tokens: the
    sampled (or greedy) token feeds back on-device via lax.scan. Returns
    (cache, out_tokens [num_steps, S], next_tokens [S], next_positions
    [S]) — next_tokens/next_positions are PROGRAM OUTPUTS precisely so
    the engine can chain chunk N+1's inputs to chunk N's outputs as
    device arrays with no host round-trip (an eager ``out[-1]`` slice
    costs a dispatch of its own). Slots keep
    generating past EOS inside a chunk; the engine truncates host-side
    (bounded waste of num_steps-1 tokens per finished slot). With
    ``rng``/``temperature`` given, each slot samples at its own
    temperature (0 = greedy) with optional static top_k.
    """
    S = tokens.shape[0]
    if temperature is None:
        temperature = jnp.zeros((S,), jnp.float32)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def step(carry, _):
        cache, toks, pos, key = carry
        cache, logits = decode_step(cfg, params, cache, toks, pos, active)
        if sample:
            key, sub = jax.random.split(key)
            nxt = sample_tokens(logits, sub, temperature, top_k)
        else:
            # static greedy variant: no categorical, no top-k sort
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, toks)
        return (cache, nxt, pos + active.astype(jnp.int32), key), nxt

    (cache, nxt, pos, _), out = jax.lax.scan(
        step, (cache, tokens, positions, rng), None, length=num_steps)
    return cache, out, nxt, pos


def make_engine_fns(cfg: LlamaConfig, params, num_slots: int, max_len: int,
                    mesh=None):
    """Jitted (prefill_fn(tokens), insert_fn(cache, kv, slot),
    decode_fn(cache, tokens, positions, active)).

    params are passed as jit ARGUMENTS, never closed over: a closure would
    bake the full weight tensors into the HLO as literal constants and
    compilation explodes (GBs of literals). cfg is static (frozen
    dataclass).

    mesh: optional tensor-parallel mesh (axis "tp"). Weights shard the
    Megatron way — wq/wk/wv/w_gate/w_up column-wise, wo/w_down row-wise
    (the training logical-axis rules already say exactly this) — and XLA
    emits one all-reduce after attention and one after the MLP per layer,
    riding ICI on a real v5e-N slice. The KV cache shards over the KV-head
    axis (cache_shardings), so per-chip HBM holds 1/tp of the cache: the
    reason to serve an 8B model on a v5e-4 host instead of one chip.
    Reference analogue (role, not design): torch_tensor_nccl_channel.py:191
    moving activations between TP shards; here the mesh IS the engine."""
    if mesh is not None:
        from ray_tpu.models import llama as _llama

        params = jax.device_put(params, _llama.param_shardings(cfg, mesh))
    prefill_b_j = jax.jit(prefill_batch, static_argnums=(0,))
    insert_many_j = jax.jit(insert_many, donate_argnums=(0,))
    decode_j = jax.jit(decode_step, static_argnums=(0,),
                       donate_argnums=(2,))
    chunk_j = jax.jit(decode_chunk, static_argnums=(0, 6, 9, 10),
                      donate_argnums=(2,))

    def pre_batch(tokens, last_idx):
        return prefill_b_j(cfg, params, tokens, last_idx)

    def dec(cache, tokens, positions, active):
        return decode_j(cfg, params, cache, tokens, positions, active)

    def dec_chunk(cache, tokens, positions, active, num_steps,
                  rng=None, temperature=None, top_k=0, sample=True):
        return chunk_j(cfg, params, cache, tokens, positions, active,
                       num_steps, rng, temperature, top_k, sample)

    # same signatures, lowered instead of run (LLMEngine.report reads the
    # compiled text to see which attention path each program took)
    pre_batch.lower = lambda tokens, last_idx: prefill_b_j.lower(
        cfg, params, tokens, last_idx)
    dec_chunk.lower = lambda cache, tokens, positions, active, num_steps, \
        rng, temperature, top_k, sample: chunk_j.lower(
            cfg, params, cache, tokens, positions, active, num_steps, rng,
            temperature, top_k, sample)
    return pre_batch, insert_many_j, dec, dec_chunk
