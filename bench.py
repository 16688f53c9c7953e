"""Benchmark driver: prints ONE JSON line.

Headline metric (BASELINE.json's own north star, which the reference never
published — we establish it): **Train tokens/sec/chip + MFU** for the
flagship Llama model, fwd+bwd+adamw on the real TPU chip, bf16, flash
attention (Pallas fwd+bwd kernels), remat, lax.scan over stacked layers.

Secondary rows mirror the reference's microbenchmark driver
(python/ray/_private/ray_perf.py; numbers from
release/perf_metrics/microbenchmark.json on m5.16xlarge, see BASELINE.md):
task/actor call rates, put/get ops + GiB/s on the shm store, wait-1k-refs,
placement-group create/remove.

Output: one JSON line with the headline metric plus a "rows" array of
{metric, value, unit, vs_baseline} entries.
"""

from __future__ import annotations

import json
import os
import time

# --- reference baselines (BASELINE.md / release/perf_metrics) ----------------
BASE = {
    "single_client_tasks_async": 8011.5,
    "single_client_tasks_sync": 986.6,
    "1_1_actor_calls_sync": 2055.7,
    "1_1_actor_calls_async": 9060.7,
    "1_1_actor_calls_concurrent": 5168.0,
    "1_n_actor_calls_async": 8786.2,
    "n_n_actor_calls_async": 26545.9,
    "n_n_actor_calls_with_arg_async": 2699.1,
    "1_1_async_actor_calls_sync": 1486.2,
    "1_1_async_actor_calls_async": 4456.6,
    "1_1_async_actor_calls_with_args_async": 3038.9,
    "1_n_async_actor_calls_async": 7805.0,
    "n_n_async_actor_calls_async": 22710.0,
    "single_client_put_calls": 5241.2,
    "single_client_get_calls": 10303.5,
    "single_client_put_gigabytes": 20.18,
    "multi_client_put_calls": 12455.5,
    "multi_client_tasks_async": 23311.9,
    "multi_client_put_gigabytes": 38.47,
    "single_client_tasks_and_get_batch": 7.90,
    "single_client_get_object_containing_10k_refs": 13.68,
    "single_client_wait_1k_refs": 5.49,
    "placement_group_create_removal": 824.4,
}

# TPU bf16 peak FLOP/s per chip (for MFU).  v5e (aka "v5 lite") = 197e12,
# v5p = 459e12, v4 = 275e12.
_PEAK_BF16 = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,
    "v4": 275e12,
    "v6": 918e12,
    "v6e": 918e12,
}


# HBM bandwidth per chip (bytes/s): v5e 819 GB/s, v5p 2765, v4 1228,
# v6e 1640 — the decode-bound resource (weights stream once per step).
_PEAK_HBM = {
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v5": 2765e9,
    "v4": 1228e9,
    "v6": 1640e9,
    "v6e": 1640e9,
}


def _match_device_kind(table: dict, default: float) -> float:
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for k, v in table.items():
        if k in kind:
            return v
    return default


def _chip_peak_flops() -> float:
    return _match_device_kind(_PEAK_BF16, 197e12)  # conservative default


def _chip_peak_hbm() -> float:
    return _match_device_kind(_PEAK_HBM, 819e9)


def _row(metric: str, value: float, unit: str, baseline=None) -> dict:
    r = {"metric": metric, "value": round(value, 3), "unit": unit}
    if baseline:
        r["vs_baseline"] = round(value / baseline, 3)
    return r


# --- headline: train step on the real chip -----------------------------------

def _train_flops_per_step(cfg, n_params: int, batch: int, seq: int) -> float:
    """Model FLOPs for one fwd+bwd step (standard MFU accounting: 6N per
    token for matmuls + causal attention term; remat recompute NOT counted)."""
    tok = batch * seq
    matmul = 6.0 * n_params * tok
    # attention: QK^T and AV, 2 matmuls x 2 FLOPs x S x qdim per token per
    # layer, halved (causal), x3 for fwd+bwd
    qdim = cfg.num_heads * cfg.head_dim_
    attn = 3.0 * 2.0 * 2.0 * 0.5 * cfg.num_layers * seq * tok * qdim
    return matmul + attn


def bench_train_step(attn_impl: str, batch: int = 8, seq: int = 2048,
                     steps: int = 20):
    """Tokens/sec/chip + MFU for the flagship model on the default backend."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:  # CI fallback: tiny config so the bench always runs
        cfg = llama.LlamaConfig.tiny(attn_impl="reference")
        batch, seq, steps = 2, 128, 3
    else:
        # scan_layers=False: the unrolled layer loop avoids the scan
        # backward's stacked-gradient buffer re-copies; save_qkv remat
        # keeps the post-rope projections so backward skips their
        # recompute. Together: 855→782 ms at 1B (round 5, old machine).
        cfg = llama.LlamaConfig.llama3_1b_proxy(
            param_dtype=jnp.bfloat16, attn_impl=attn_impl,
            scan_layers=False, remat_policy="save_qkv")

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_params = llama.num_params(params)
    # bf16 first moment frees ~1.75 GB of optimizer HBM (funds the
    # save_qkv activations) and is speed- and loss-neutral (r4 notes)
    tx = optax.adamw(3e-4, weight_decay=0.01,
                     mu_dtype=jnp.bfloat16 if on_tpu else None)
    opt_state = tx.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)

    def _step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, {"tokens": tokens}))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # Donation keeps params+opt single-buffered in HBM; the timing barrier
    # is float(loss) — a device->host transfer of the step's own result,
    # which cannot complete before the step has (on the v5e host
    # block_until_ready is just as good: chip_smoke.py's train phase
    # reads the loss back after it in well under a millisecond).
    step = jax.jit(_step, donate_argnums=(0, 1))

    params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)  # compile + warmup barrier
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    loss = float(loss)
    dt = (time.perf_counter() - t0) / steps

    tok_s = batch * seq / dt
    mfu = _train_flops_per_step(cfg, n_params, batch, seq) / dt / _chip_peak_flops()
    return tok_s, mfu, loss, n_params, dt


def bench_layer_8b(seq: int, batch: int = 4, steps: int = 16):
    """One Llama-3-8B-DIM transformer layer, fwd+bwd on the chip.

    A single v5e chip (16 GiB) cannot hold the full 8B model, so the
    8B-shaped claim is validated where it can be: the per-layer compute
    (h=4096, ffn=14336, 32 heads / 8 KV heads — exactly the 8B block) at
    real sequence lengths. vocab is shrunk to 256 so the embed/head cost
    is negligible and the measurement is the LAYER. Returns (ms, mfu)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.llama3_8b(
        num_layers=1, vocab_size=256, param_dtype=jnp.bfloat16,
        attn_impl="flash", scan_layers=False, remat_policy="save_qkv")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_params = llama.num_params(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(cfg, p, {"tokens": tokens})))
    loss, grads = grad_fn(params)
    float(loss)  # compile barrier
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, grads = grad_fn(params)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    # fwd+bwd only: 6N per token matmul + causal attention term
    flops = _train_flops_per_step(cfg, n_params, batch, seq)
    return dt * 1e3, flops / dt / _chip_peak_flops()


def bench_flash_numerics():
    """On-chip fwd+grad agreement: Pallas flash attention vs XLA reference."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import attention_reference, flash_attention

    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    b, s, h, d = 2, 512, 4, 64
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, h, d), jnp.bfloat16)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).astype(jnp.float32).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32))))
        for a, b_ in zip(gf, gr))
    return err


def bench_moe_train(batch: int = 8, seq: int = 1024, steps: int = 8):
    """MoE (Mixtral-style) train step on the chip: tokens/sec/chip for the
    ~620M-param moe_proxy (8 experts, top-2). BASELINE config #3 names
    expert-parallel MoE; single-chip establishes the per-chip number."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import mixtral

    on_tpu = jax.default_backend() == "tpu"
    cfg = (mixtral.MixtralConfig.moe_proxy(param_dtype=jnp.bfloat16)
           if on_tpu else mixtral.MixtralConfig.tiny())
    if not on_tpu:
        batch, seq, steps = 2, 64, 2
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = tx.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)

    def _step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: mixtral.loss_fn(cfg, p, {"tokens": tokens}))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(_step, donate_argnums=(0, 1))
    params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens)
    float(loss)
    dt = (time.perf_counter() - t0) / steps
    return batch * seq / dt


def bench_serve_ttft(n_requests: int = 16, quantize=None):
    """Serve LLM engine on the chip: p50 TTFT + decode throughput.

    Drives the continuous-batching engine directly (the TPU lives in this
    process; Serve's router/replica layers add only IPC, measured by the
    actor-call rows). BASELINE.json names 'Serve p50 TTFT' as a north-star
    metric with no published reference number — this establishes it."""
    import jax

    from ray_tpu.serve.llm_engine import LLMEngine

    on_tpu = jax.default_backend() == "tpu"
    mc = ({"preset": "llama3_1b_proxy", "param_dtype": "bfloat16"}
          if on_tpu else {"preset": "tiny"})
    if quantize:
        mc["quantize"] = quantize
    engine = LLMEngine(
        model_config=mc,
        # 16 slots so the 16-request burst admits without queueing for a
        # slot (KV for 16x512 at 1B scale is a few hundred MB of HBM);
        # batched prefill admits the burst in 2 program calls
        num_slots=16, max_len=512 if on_tpu else 64,
        prefill_buckets=[128] if on_tpu else [16],
        max_new_tokens=64 if on_tpu else 8,
        chunk_steps=32)
    import random as _r

    rng = _r.Random(0)
    prompts = [[rng.randrange(1000) for _ in range(100)]
               for _ in range(n_requests)]
    # warmup: pay prefill+decode jit compilation outside the timed window
    engine.submit("warmup", prompts[0], 2)
    deadline = time.monotonic() + 600
    while not engine.collect() and time.monotonic() < deadline:
        time.sleep(0.01)
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        engine.submit(f"q{i}", p)
    done = {}
    deadline = time.monotonic() + 600
    while len(done) < n_requests and time.monotonic() < deadline:
        done.update(engine.collect())
        time.sleep(0.005)
    wall = time.perf_counter() - t0
    try:
        import jax

        weight_bytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(engine._params))
        return (*_serve_rows_from(engine, prompts, done, n_requests, wall),
                weight_bytes)
    finally:
        engine.shutdown()


def _serve_rows_from(engine, prompts, done, n_requests, wall):
    if len(done) < n_requests:
        raise RuntimeError(f"engine finished {len(done)}/{n_requests}")
    ttfts = sorted(r["ttft_s"] for r in done.values())
    total_tokens = sum(len(r["tokens"]) for r in done.values())
    # median TTFT over ALL requests under load (jit compilation was paid by
    # the warmup request, outside the timed window)
    p50 = ttfts[len(ttfts) // 2]
    # per-stream view: inter-token latency and tokens/s within ONE request
    # under full load (weak point of aggregate-only numbers: they hide a
    # thin per-stream experience)
    itls = sorted((r["latency_s"] - r["ttft_s"]) / max(1, len(r["tokens"]) - 1)
                  for r in done.values())
    itl_p50_ms = itls[len(itls) // 2] * 1e3
    per_stream = sorted(
        len(r["tokens"]) / max(1e-9, r["latency_s"] - r["ttft_s"])
        for r in done.values())
    per_stream_p50 = per_stream[len(per_stream) // 2]
    # unbatched upper bound: ONE request alone on the engine — the gap to
    # per_stream_p50 is the price each stream pays for batching. Failure
    # here must not void the measurements above.
    solo_tok_s = -1.0
    engine.submit("solo", prompts[0])
    solo = {}
    deadline = time.monotonic() + 600
    while "solo" not in solo and time.monotonic() < deadline:
        solo.update(engine.collect())
        time.sleep(0.005)
    r = solo.get("solo")
    if isinstance(r, dict):
        solo_tok_s = (len(r["tokens"])
                      / max(1e-9, r["latency_s"] - r["ttft_s"]))
    return (p50 * 1e3, total_tokens / wall, itl_p50_ms, per_stream_p50,
            solo_tok_s)


def bench_serve_paged():
    """Paged-KV engine rows: decode ITL with the Pallas page-gather
    kernel, and the prefix-cache TTFT speedup on a 4k shared prefix
    (round-5 VERDICT item 2's acceptance metric). Runs on TPU only."""
    import time as _t

    import random as _r

    from ray_tpu.serve.paged_engine import PagedLLMEngine

    rng = _r.Random(0)
    eng = PagedLLMEngine(
        model_config={"preset": "llama3_1b_proxy",
                      "param_dtype": "bfloat16"},
        num_slots=16, max_len=512, prefill_buckets=[128],
        max_new_tokens=64, chunk_steps=32, page_size=64)
    prompts = [[rng.randrange(1000) for _ in range(100)]
               for _ in range(16)]
    eng.submit("warmup", prompts[0], 2)
    t_end = _t.monotonic() + 600
    while not eng.collect() and _t.monotonic() < t_end:
        _t.sleep(0.01)
    for i, p in enumerate(prompts):
        eng.submit(f"q{i}", p)
    done = {}
    t_end = _t.monotonic() + 600
    while len(done) < 16 and _t.monotonic() < t_end:
        done.update(eng.collect())
        _t.sleep(0.005)
    eng.shutdown()
    if len(done) < 16 or any(not isinstance(v, dict)
                             for v in done.values()):
        raise RuntimeError(f"paged burst incomplete: {done}")
    itls = sorted((r["latency_s"] - r["ttft_s"])
                  / max(1, len(r["tokens"]) - 1) for r in done.values())
    itl_ms = itls[len(itls) // 2] * 1e3

    # prefix-cache speedup at 4k context
    eng = PagedLLMEngine(
        model_config={"preset": "llama3_1b_proxy",
                      "param_dtype": "bfloat16"},
        num_slots=4, max_len=4096, prefill_buckets=[512],
        max_new_tokens=16, chunk_steps=8, page_size=64)

    def ttft(rid, prompt):
        eng.submit(rid, prompt, 8)
        got = {}
        tend = _t.monotonic() + 600
        while rid not in got and _t.monotonic() < tend:
            got.update(eng.collect())
            _t.sleep(0.005)
        r = got[rid]
        if not isinstance(r, dict):
            raise RuntimeError(f"paged prefix req failed: {r!r}")
        return r["ttft_s"], r["tokens"]

    ttft("warmup2", [rng.randrange(1000) for _ in range(600)])
    shared = [rng.randrange(1000) for _ in range(3968)]
    cold, tc = ttft("cold", shared + [7, 8, 9])
    warm, tw = ttft("warm", shared + [7, 8, 9])
    eng.shutdown()
    if tc != tw:
        raise RuntimeError("prefix-cached generation diverged")
    return itl_ms, cold * 1e3, warm * 1e3, cold / warm


def bench_serve_affinity(model_config=None, page_size=64,
                         num_pages=None, sessions=8, turns=4):
    """serve_prefix_hit_ratio_multireplica: prefix-cache hit ratio of a
    session-heavy workload over TWO engine replicas, routed blind
    (seed power-of-two) vs cache-affinity (score_replicas over live
    residency digests). The pools are sized so ONE replica cannot hold
    every session's prefix: blind routing spreads each session across
    both replicas and LRU-thrashes, affinity pins each session to its
    digest holder. Returns (hit_affinity, hit_blind). Acceptance
    (ISSUE 18): affinity >= 2x blind at 2+ replicas."""
    import random as _r
    import time as _t

    from ray_tpu.serve.affinity import ResidencyDigest, score_replicas
    from ray_tpu.serve.paged_engine import PagedLLMEngine

    if model_config is None:
        model_config = {"preset": "llama3_1b_proxy",
                        "param_dtype": "bfloat16"}
    prefix_pages = 4
    # headroom for HALF the sessions' prefixes + one in-flight request
    if num_pages is None:
        num_pages = prefix_pages * (sessions // 2 + 2)
    rng = _r.Random(0)
    prefixes = [[rng.randrange(1000) for _ in range(
        prefix_pages * page_size)] for _ in range(sessions)]
    # session turn order interleaved round-robin: every session revisits
    # while the others churn the pool, the worst case for blind routing
    sched = [(s, t) for t in range(turns) for s in range(sessions)]

    def run(affine: bool) -> float:
        engines = [
            PagedLLMEngine(model_config=model_config, num_slots=4,
                           max_len=(prefix_pages + 2) * page_size,
                           prefill_buckets=[page_size],
                           max_new_tokens=4, chunk_steps=2,
                           page_size=page_size, num_pages=num_pages)
            for _ in range(2)]
        pick_rng = _r.Random(1)
        replicas = [("r0", None), ("r1", None)]
        try:
            for s, t in sched:
                prompt = prefixes[s] + [rng.randrange(1000)
                                        for _ in range(3)]
                choice = None
                if affine:
                    digests = {
                        f"r{i}": ResidencyDigest.from_report(
                            e.residency_digest())
                        for i, e in enumerate(engines)}
                    choice = score_replicas(
                        prompt, replicas,
                        {k: v for k, v in digests.items()
                         if v is not None},
                        {}, min_prefix_tokens=page_size,
                        load_penalty=64.0)
                if choice is None:  # seed pow-2 (idle: first of the pair)
                    choice = pick_rng.sample(replicas, 2)[0][0]
                eng = engines[int(choice[1:])]
                eng.submit(f"s{s}t{t}", prompt)
                t_end = _t.monotonic() + 600
                while not eng.collect() and _t.monotonic() < t_end:
                    _t.sleep(0.005)
            hits = sum(e._prefix_hit_tokens for e in engines)
            computed = sum(e._prefill_tokens_computed for e in engines)
            return hits / max(1, hits + computed)
        finally:
            for e in engines:
                e.shutdown()

    return run(affine=True), run(affine=False)


def bench_serve_disagg(model_config=None, page_size=64,
                       long_tokens=448, n_short=8, n_long=4):
    """Disaggregation rows: p99 TTFT and p99 decode ITL of a mixed
    stream — short decode-heavy requests with long prompts landing
    mid-decode — on the plain paged engine (disagg off) vs the
    disaggregated engine (dedicated prefill workers + device-channel KV
    handoff). Off the decode loop, long-prompt prefill chunks stop
    stealing decode ticks, so the short requests' ITL tail flattens.
    Returns {"off": (ttft_p99_ms, itl_p99_ms), "on": ...}. Acceptance
    (ISSUE 18): disagg-on p99 decode ITL <= disagg-off."""
    import random as _r
    import time as _t

    from ray_tpu.serve import qos
    from ray_tpu.serve.disagg import DisaggPagedEngine
    from ray_tpu.serve.paged_engine import PagedLLMEngine

    if model_config is None:
        model_config = {"preset": "llama3_1b_proxy",
                        "param_dtype": "bfloat16"}
    rng = _r.Random(2)
    max_len = long_tokens + 2 * page_size
    shorts = [[rng.randrange(1000) for _ in range(page_size // 2)]
              for _ in range(n_short)]
    longs = [[rng.randrange(1000) for _ in range(long_tokens)]
             for _ in range(n_long)]
    kw = dict(model_config=model_config, num_slots=8, max_len=max_len,
              prefill_buckets=[2 * page_size], max_new_tokens=48,
              chunk_steps=4, page_size=page_size)

    out = {}
    for mode in ("off", "on"):
        eng = (DisaggPagedEngine(prefill_workers=1,
                                 divert_min_tokens=2 * page_size, **kw)
               if mode == "on" else PagedLLMEngine(**kw))
        try:
            eng.submit("warmup", shorts[0], 2)
            t_end = _t.monotonic() + 600
            while not eng.collect() and _t.monotonic() < t_end:
                _t.sleep(0.01)
            for i, p in enumerate(shorts):
                eng.submit(f"short{i}", p)
            _t.sleep(0.05)  # shorts reach steady decode, then the burst
            for i, p in enumerate(longs):
                eng.submit(f"long{i}", p, 8)
            done = {}
            t_end = _t.monotonic() + 600
            while (len(done) < n_short + n_long
                   and _t.monotonic() < t_end):
                done.update(eng.collect())
                _t.sleep(0.005)
        finally:
            eng.shutdown()
        if len(done) < n_short + n_long:
            raise RuntimeError(f"disagg bench incomplete ({mode}): "
                               f"{sorted(done)}")
        ttfts = [done[f"long{i}"]["ttft_s"] * 1e3
                 for i in range(n_long)]
        itls = [(r["latency_s"] - r["ttft_s"])
                / max(1, len(r["tokens"]) - 1) * 1e3
                for k, r in done.items() if k.startswith("short")]
        out[mode] = (qos.percentile(ttfts, 99),
                     qos.percentile(itls, 99))
    return out


# --- ray_perf-style microbenchmarks ------------------------------------------

def _timeit(fn, n: int, warm: int = 1) -> float:
    """ops/sec for fn() executing n logical ops."""
    for _ in range(warm):
        fn()
    t0 = time.perf_counter()
    fn()
    return n / (time.perf_counter() - t0)


def bench_core(rows: list):
    import numpy as np

    import ray_tpu

    nw = 2 if (os.cpu_count() or 1) <= 2 else 4
    ray_tpu.init(num_workers=nw, object_store_memory=2048 << 20)

    # Pre-fault the store arena so put throughput measures memcpy, not
    # first-touch page faults (plasma baselines likewise run on warm stores).
    from ray_tpu.core import runtime_context
    runtime_context.get_core().store.prefault()

    @ray_tpu.remote
    def noop():
        return None

    @ray_tpu.remote
    class A:
        def f(self):
            return None

        def f_arg(self, x):
            return None

    # tasks async: submit batch, then resolve
    def tasks_async(n=6000):
        ray_tpu.get([noop.remote() for _ in range(n)])
    rate = _timeit(lambda: tasks_async(), 6000, warm=1)
    rows.append(_row("single_client_tasks_async", rate, "tasks/s",
                     BASE["single_client_tasks_async"]))

    # tasks sync: one at a time
    def tasks_sync(n=300):
        for _ in range(n):
            ray_tpu.get(noop.remote())
    rate = _timeit(lambda: tasks_sync(), 300, warm=1)
    rows.append(_row("single_client_tasks_sync", rate, "tasks/s",
                     BASE["single_client_tasks_sync"]))

    a = A.remote()
    def actor_sync(n=300):
        for _ in range(n):
            ray_tpu.get(a.f.remote())
    rate = _timeit(lambda: actor_sync(), 300, warm=1)
    rows.append(_row("1_1_actor_calls_sync", rate, "calls/s",
                     BASE["1_1_actor_calls_sync"]))

    def actor_async(n=4000):
        ray_tpu.get([a.f.remote() for _ in range(n)])
    rate = _timeit(lambda: actor_async(), 4000, warm=1)
    rows.append(_row("1_1_actor_calls_async", rate, "calls/s",
                     BASE["1_1_actor_calls_async"]))

    actors = [A.remote() for _ in range(nw)]
    def one_n(n=4000):
        ray_tpu.get([actors[i % nw].f.remote() for i in range(n)])
    rate = _timeit(lambda: one_n(), 4000, warm=1)
    rows.append(_row("1_n_actor_calls_async", rate, "calls/s",
                     BASE["1_n_actor_calls_async"]))

    # n:n — ray_perf methodology (ray_perf.py:225-232): the n "clients"
    # are m REMOTE TASKS, each driving every actor round-robin, so the
    # whole exchange crosses real process boundaries on both sides.
    # NOTE the hardware asymmetry: the reference number aggregates across
    # 64 vCPUs; this VM has ONE core, so the aggregate can never exceed
    # the single-pair rate — see the aggregate_msgs_per_core row.
    @ray_tpu.remote
    def drive_actors(acts, per):
        ray_tpu.get([acts[i % len(acts)].f.remote() for i in range(per)])
        return 0

    m = 4
    def n_n(per=500):
        ray_tpu.get([drive_actors.remote(actors, per) for _ in range(m)])
    rate = _timeit(lambda: n_n(), 500 * m, warm=1)
    rows.append(_row("n_n_actor_calls_async", rate, "calls/s",
                     BASE["n_n_actor_calls_async"]))

    # n:n with arg (ray_perf.py:235-243): m client actors, each driving
    # its own server actor with a put-ref argument per call
    @ray_tpu.remote
    class ArgClient:
        def __init__(self, server):
            self.server = server

        def batch(self, n):
            x = ray_tpu.put(0)
            ray_tpu.get([self.server.f_arg.remote(x) for _ in range(n)])
            return 0

    clients = [ArgClient.remote(a_) for a_ in actors]
    def n_n_arg(per=250):
        ray_tpu.get([c.batch.remote(per) for c in clients])
    rate = _timeit(lambda: n_n_arg(), 250 * nw, warm=1)
    rows.append(_row("n_n_actor_calls_with_arg_async", rate, "calls/s",
                     BASE["n_n_actor_calls_with_arg_async"]))

    # 1:1 concurrent (thread-pooled actor, ray_perf.py:205-210)
    conc = A.options(max_concurrency=16).remote()
    ray_tpu.get(conc.f.remote())
    def actor_concurrent(n=2000):
        ray_tpu.get([conc.f.remote() for _ in range(n)])
    rate = _timeit(lambda: actor_concurrent(), 2000, warm=1)
    rows.append(_row("1_1_actor_calls_concurrent", rate, "calls/s",
                     BASE["1_1_actor_calls_concurrent"]))

    # actor restart recovery: SIGKILL the worker, time until the first
    # call against the NEW incarnation returns (restart fork + __init__ +
    # replayed dispatch). Median of 3 kills; no reference number — the
    # conservative bar lives in BASELINE.json.published.
    import signal as _signal

    @ray_tpu.remote(max_restarts=10, max_task_retries=10)
    class Restartable:
        def pid(self):
            return os.getpid()

        def f(self):
            return b"ok"

    ra = Restartable.remote()
    recov = []
    for _ in range(3):
        pid = ray_tpu.get(ra.pid.remote())
        os.kill(pid, _signal.SIGKILL)
        t0 = time.perf_counter()
        ray_tpu.get(ra.f.remote())
        recov.append((time.perf_counter() - t0) * 1e3)
    rows.append(_row("actor_restart_recovery_ms", sorted(recov)[1], "ms"))

    # async actors (asyncio event-loop per actor, ray_perf.py:26-35)
    @ray_tpu.remote
    class AsyncA:
        async def f(self):
            return b"ok"

        async def f_arg(self, x):
            return b"ok"

    aa = AsyncA.remote()
    ray_tpu.get(aa.f.remote())
    def async_sync(n=300):
        for _ in range(n):
            ray_tpu.get(aa.f.remote())
    rate = _timeit(lambda: async_sync(), 300, warm=1)
    rows.append(_row("1_1_async_actor_calls_sync", rate, "calls/s",
                     BASE["1_1_async_actor_calls_sync"]))

    def async_async(n=2000):
        ray_tpu.get([aa.f.remote() for _ in range(n)])
    rate = _timeit(lambda: async_async(), 2000, warm=1)
    rows.append(_row("1_1_async_actor_calls_async", rate, "calls/s",
                     BASE["1_1_async_actor_calls_async"]))

    ref_arg = ray_tpu.put(0)
    def async_args(n=2000):
        ray_tpu.get([aa.f_arg.remote(ref_arg) for _ in range(n)])
    rate = _timeit(lambda: async_args(), 2000, warm=1)
    rows.append(_row("1_1_async_actor_calls_with_args_async", rate,
                     "calls/s",
                     BASE["1_1_async_actor_calls_with_args_async"]))

    async_actors = [AsyncA.remote() for _ in range(nw)]
    for x in async_actors:
        ray_tpu.get(x.f.remote())
    def one_n_async(n=2000):
        ray_tpu.get([async_actors[i % nw].f.remote() for i in range(n)])
    rate = _timeit(lambda: one_n_async(), 2000, warm=1)
    rows.append(_row("1_n_async_actor_calls_async", rate, "calls/s",
                     BASE["1_n_async_actor_calls_async"]))

    def n_n_async(per=500):
        ray_tpu.get([drive_actors.remote(async_actors, per)
                     for _ in range(m)])
    rate = _timeit(lambda: n_n_async(), 500 * m, warm=1)
    rows.append(_row("n_n_async_actor_calls_async", rate, "calls/s",
                     BASE["n_n_async_actor_calls_async"]))

    # put/get small objects
    def puts(n=3000):
        for _ in range(n):
            ray_tpu.put(b"x" * 100)
    rate = _timeit(lambda: puts(), 3000, warm=1)
    rows.append(_row("single_client_put_calls", rate, "puts/s",
                     BASE["single_client_put_calls"]))

    # multi-client puts: m remote tasks each putting small objects
    @ray_tpu.remote
    def put_batch(n):
        for _ in range(n):
            ray_tpu.put(b"x" * 100)
        return 0

    def multi_puts(per=750):
        ray_tpu.get([put_batch.remote(per) for _ in range(m)])
    rate = _timeit(lambda: multi_puts(), 750 * m, warm=1)
    rows.append(_row("multi_client_put_calls", rate, "puts/s",
                     BASE["multi_client_put_calls"]))

    # multi-client task submission: m remote tasks each submitting nested
    # noop tasks (ray_perf.py:65-67 small_value_batch)
    @ray_tpu.remote
    def submit_batch(n):
        ray_tpu.get([noop.remote() for _ in range(n)])
        return 0

    def multi_tasks(per=1000):
        ray_tpu.get([submit_batch.remote(per) for _ in range(m)])
    rate = _timeit(lambda: multi_tasks(), 1000 * m, warm=1)
    rows.append(_row("multi_client_tasks_async", rate, "tasks/s",
                     BASE["multi_client_tasks_async"]))

    # tasks-and-get batch: 1k-task submit+get cycles per second
    def tasks_and_get(n=1000):
        ray_tpu.get([noop.remote() for _ in range(n)])
    tasks_and_get()
    t0 = time.perf_counter()
    reps = 6
    for _ in range(reps):
        tasks_and_get()
    rate = reps / (time.perf_counter() - t0)
    rows.append(_row("single_client_tasks_and_get_batch", rate,
                     "1k-batches/s",
                     BASE["single_client_tasks_and_get_batch"]))

    small = ray_tpu.put(b"y" * 100)
    def gets(n=6000):
        for _ in range(n):
            ray_tpu.get(small)
    rate = _timeit(lambda: gets(), 6000, warm=1)
    rows.append(_row("single_client_get_calls", rate, "gets/s",
                     BASE["single_client_get_calls"]))

    # put GiB/s: zero-copy numpy into the shm store
    arr = np.random.default_rng(0).random((64 << 20) // 8)  # 64 MiB
    def put_gb(reps=8):
        for _ in range(reps):
            ray_tpu.put(arr)
    for _ in range(2):
        put_gb(2)
    t0 = time.perf_counter()
    put_gb(8)
    gibs = (8 * arr.nbytes / (1 << 30)) / (time.perf_counter() - t0)
    rows.append(_row("single_client_put_gigabytes", gibs, "GiB/s",
                     BASE["single_client_put_gigabytes"]))

    # Hardware ceiling for the row above: raw streaming memcpy into a
    # ring of distinct 64 MiB destinations (exactly what put does). The
    # reference's 20.18 GiB/s runs on a 64-vCPU m5.16xlarge with far more
    # memory bandwidth; on THIS machine put is at ~the memcpy ceiling, so
    # the remaining vs_baseline gap is hardware, not the store.
    ring = [np.empty_like(arr) for _ in range(8)]
    for d in ring:
        np.copyto(d, arr)
    t0 = time.perf_counter()
    for i in range(16):
        np.copyto(ring[i % 8], arr)
    ceiling = (16 * arr.nbytes / (1 << 30)) / (time.perf_counter() - t0)
    del ring
    rows.append(_row("host_memcpy_gigabytes", ceiling, "GiB/s"))
    rows.append(_row("put_bandwidth_vs_host_memcpy", gibs / ceiling, "x"))

    # multi-client put GiB/s: m worker processes copying into the SAME
    # shm arena concurrently
    @ray_tpu.remote
    def put_gb_worker(nbytes, reps):
        import numpy as _np

        from ray_tpu.core import runtime_context

        # warm-store methodology, same as the single-client row (plasma
        # baselines also run warm): first-touch faults on the worker's
        # own arena mapping otherwise dominate (1.5 vs 5.3 GiB/s)
        core = runtime_context.get_core()
        if getattr(core, "store", None) is not None:
            core.store.prefault()
        a = _np.ones(nbytes // 8)
        for _ in range(reps):
            ray_tpu.put(a)
        return 0

    mb32 = 32 << 20
    ray_tpu.get([put_gb_worker.remote(mb32, 1) for _ in range(m)])  # warm
    t0 = time.perf_counter()
    ray_tpu.get([put_gb_worker.remote(mb32, 4) for _ in range(m)])
    gibs_m = (m * 4 * mb32 / (1 << 30)) / (time.perf_counter() - t0)
    rows.append(_row("multi_client_put_gigabytes", gibs_m, "GiB/s",
                     BASE["multi_client_put_gigabytes"]))

    # get of one object containing 10k refs
    refs_10k = [noop.remote() for _ in range(10_000)]
    ray_tpu.get(refs_10k)
    big_ref = ray_tpu.put(refs_10k)
    ray_tpu.get(big_ref)
    t0 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        ray_tpu.get(big_ref)
    rate = reps / (time.perf_counter() - t0)
    rows.append(_row("single_client_get_object_containing_10k_refs", rate,
                     "gets/s",
                     BASE["single_client_get_object_containing_10k_refs"]))
    del refs_10k, big_ref

    # wait over 1k already-resolved refs (ray_perf pre-resolves before the
    # timed region, so this measures wait() cost, not task completion)
    refs_1k = [noop.remote() for _ in range(1000)]
    ray_tpu.get(refs_1k)
    def wait_1k(reps):
        for _ in range(reps):
            ray_tpu.wait(refs_1k, num_returns=len(refs_1k), timeout=30)
    wait_1k(2)
    t0 = time.perf_counter()
    wait_1k(20)
    rate = 20 / (time.perf_counter() - t0)
    rows.append(_row("single_client_wait_1k_refs", rate, "waits/s",
                     BASE["single_client_wait_1k_refs"]))

    # compiled-DAG lane. dag_pipeline_latency_us stays the historical
    # 3-stage BLOCK-mode row (spin_us=0, so the spin default can't move
    # it); the spin-vs-block A/B runs on a 1-stage echo (one roundtrip =
    # 2 channel hops) and is INTERLEAVED in-process — across process
    # restarts this box drifts more than the spin effect, so only an
    # interleaved comparison is honest. Per-hop = roundtrip / 2.
    from ray_tpu.core.config import config as _dag_config
    from ray_tpu.dag import compile_pipeline

    @ray_tpu.remote
    class Id:
        def step(self, x):
            return x

    stages = [Id.remote() for _ in range(3)]
    for a_ in stages:
        ray_tpu.get(a_.step.remote(0))
    n = 300
    t0 = time.perf_counter()
    for i in range(n):
        v = i
        for a_ in stages:
            v = ray_tpu.get(a_.step.remote(v))
    actor_lat = (time.perf_counter() - t0) / n

    def _dag_lat(d, reps):
        t0 = time.perf_counter()
        for i in range(reps):
            d.execute(i)
        return (time.perf_counter() - t0) / reps

    dag = compile_pipeline([(a_, "step") for a_ in stages], spin_us=0)
    dag.execute(0)
    dag_lat = min(_dag_lat(dag, n), _dag_lat(dag, n))
    dag.teardown()
    rows.append(_row("dag_pipeline_latency_us", dag_lat * 1e6, "us"))
    rows.append(_row("dag_vs_actor_call_speedup", actor_lat / dag_lat, "x"))

    spin_us = _dag_config.dag_spin_us or 200
    d_block = compile_pipeline([(stages[0], "step")], spin_us=0)
    d_spin = compile_pipeline([(stages[0], "step")], spin_us=spin_us)
    d_block.execute(0)
    d_spin.execute(0)
    block_rt, spin_rt = [], []
    for _ in range(5):
        block_rt.append(_dag_lat(d_block, 200))
        spin_rt.append(_dag_lat(d_spin, 200))
    d_block.teardown()
    d_spin.teardown()
    block_us, spin_us_rt = min(block_rt) * 1e6, min(spin_rt) * 1e6
    rows.append(_row("dag_compiled_roundtrip_us", spin_us_rt, "us"))
    rows.append(_row("dag_compiled_roundtrip_block_us", block_us, "us"))
    rows.append(_row("dag_compiled_per_hop_us", spin_us_rt / 2, "us"))
    rows.append(_row("dag_spin_vs_block_speedup",
                     block_us / spin_us_rt, "x"))

    # streaming returns: time-to-first-ref of a 100-yield generator task
    # vs the whole task's completion — the number the subsystem exists to
    # shrink (a non-streaming task returns nothing until it finishes)
    @ray_tpu.remote
    def gen100():
        for i in range(100):
            time.sleep(0.002)
            yield i

    def stream_first_and_total():
        t0 = time.perf_counter()
        g = gen100.options(num_returns="streaming").remote()
        ray_tpu.get(g.next_ref(timeout=60))
        first = time.perf_counter() - t0
        last = None
        for r in g:
            last = r
        ray_tpu.get(last)
        return first, time.perf_counter() - t0

    stream_first_and_total()  # warm
    samples = [stream_first_and_total() for _ in range(5)]
    first_ms = sorted(s[0] for s in samples)[2] * 1e3
    total_ms = sorted(s[1] for s in samples)[2] * 1e3
    rows.append(_row("streaming_first_output_latency_ms", first_ms, "ms"))
    rows.append(_row("streaming_task_total_ms", total_ms, "ms"))

    # placement group create/remove
    from ray_tpu.util import placement_group, remove_placement_group

    def pg_cycle(n=200):
        for _ in range(n):
            pg = placement_group([{"CPU": 0.01}], strategy="PACK")
            pg.wait(timeout_seconds=10)
            remove_placement_group(pg)
    rate = _timeit(lambda: pg_cycle(), 200, warm=0)
    rows.append(_row("placement_group_create_removal", rate, "PG/s",
                     BASE["placement_group_create_removal"]))

    ray_tpu.shutdown()


def bench_scalability(rows: list):
    """The reference's single-node scalability envelope
    (release/benchmarks/single_node.py; BASELINE.md durations measured
    on m4.16xlarge): 10k-object-arg task, 3k-return task, ray.get over
    10k store objects, and 1M tasks queued on one node. Durations —
    vs_baseline is baseline/ours (>1 = faster). These are exactly where
    queue and refcount data structures break; the regression guard pins
    them via BASELINE.json."""
    import ray_tpu

    nw = 2 if (os.cpu_count() or 1) <= 2 else 4
    ray_tpu.init(num_workers=nw, object_store_memory=2048 << 20)
    try:
        @ray_tpu.remote
        def noop(*a):
            return None

        @ray_tpu.remote
        def ret_n(n):
            return tuple(range(n))

        def dur_row(metric, dt, base):
            rows.append({"metric": metric, "value": round(dt, 3),
                         "unit": "s (lower is better)",
                         "vs_baseline": round(base / dt, 3)})

        args = [ray_tpu.put(1) for _ in range(10_000)]
        t0 = time.perf_counter()
        ray_tpu.get(noop.remote(*args), timeout=600)
        dur_row("single_node_task_with_10k_args_s",
                time.perf_counter() - t0, 18.38)
        del args

        t0 = time.perf_counter()
        refs = ret_n.options(num_returns=3000).remote(3000)
        ray_tpu.get(list(refs), timeout=600)
        dur_row("single_node_task_returning_3k_objects_s",
                time.perf_counter() - t0, 5.74)

        objs = [ray_tpu.put(b"x" * 100) for _ in range(10_000)]
        t0 = time.perf_counter()
        ray_tpu.get(objs, timeout=600)
        dur_row("single_node_get_10k_objects_s",
                time.perf_counter() - t0, 23.41)
        del objs

        t0 = time.perf_counter()
        refs = [noop.remote() for _ in range(1_000_000)]
        # resolve in slabs: one get over 1M refs would build a single
        # million-entry wait set; the reference resolves in batches too
        for i in range(0, 1_000_000, 100_000):
            ray_tpu.get(refs[i:i + 100_000], timeout=1200)
        dur_row("single_node_1m_queued_tasks_s",
                time.perf_counter() - t0, 186.3)
    finally:
        ray_tpu.shutdown()


def bench_many_nodes(rows: list):
    """Scale rows on a 16-node local cluster of REAL node-server
    processes: scheduling throughput for a 10k-task wave, actor-fleet
    creation, and PG churn (reference: release/benchmarks many_nodes
    342.8 tasks/s on 64 real nodes / many_actors 627/s — those aggregate
    64x64 cores; this VM has one)."""
    import ray_tpu
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=16, num_workers_per_node=1,
                object_store_memory=64 << 20)
    try:
        assert c.wait_for_nodes(16, timeout=180)
        c.connect()

        @ray_tpu.remote
        def f(x):
            return x + 1

        ray_tpu.get([f.remote(i) for i in range(200)], timeout=120)  # warm
        t0 = time.perf_counter()
        ray_tpu.get([f.remote(i) for i in range(10_000)], timeout=600)
        rows.append(_row("many_nodes_tasks_per_sec",
                         10_000 / (time.perf_counter() - t0), "tasks/s",
                         342.8))

        from ray_tpu.util import placement_group, remove_placement_group
        t0 = time.perf_counter()
        for _ in range(50):
            pg = placement_group([{"CPU": 0.01}] * 2, strategy="SPREAD")
            pg.wait(timeout_seconds=60)
            remove_placement_group(pg)
        rows.append(_row("many_nodes_pgs_per_sec",
                         50 / (time.perf_counter() - t0), "PG/s", 22.2))
    finally:
        c.shutdown()
        runtime_context.set_core(prev)


def _locality_wave(locality_on: bool, mb: int = 100, tasks: int = 8):
    """One measurement: a fresh 2-node cluster, a ``mb``-MB object pinned
    to the src node, then a timed wave of ``tasks`` unconstrained
    consumers sharing it. Returns (wall_s, summed node fetch stats)."""
    import ray_tpu
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster
    from ray_tpu.core.config import config as cfg

    runtime_context.set_core(None)
    os.environ["RTPU_LOCALITY_AWARE_SCHEDULING"] = (
        "1" if locality_on else "0")
    cfg.reload()
    c = Cluster(num_nodes=2, num_workers_per_node=2,
                object_store_memory=512 << 20,
                node_resources=[{"src": 2}, {"dst": 2}])
    try:
        assert c.wait_for_nodes(2, timeout=120)
        core = c.connect()

        @ray_tpu.remote
        def produce(n):
            import numpy as _np

            return _np.ones(n // 8)

        @ray_tpu.remote
        def warm():
            import numpy as _np  # noqa: F401 — pay the import cost now

            return 0

        @ray_tpu.remote
        def consume(a):
            return a.nbytes

        # every worker pays its numpy import before the timed window, so
        # the on/off comparison measures data movement, not cold starts
        ray_tpu.get([warm.options(resources={r: 1}).remote()
                     for r in ("src", "dst") for _ in range(2)],
                    timeout=120)
        ref = produce.options(resources={"src": 1}).remote(mb << 20)
        ray_tpu.get(ref, timeout=300)
        time.sleep(0.2)  # batched loc_add flush
        t0 = time.perf_counter()
        ray_tpu.get([consume.remote(ref) for _ in range(tasks)],
                    timeout=600)
        dt = time.perf_counter() - t0
        fetch = {"bytes": 0, "seconds": 0.0}
        for node in c.nodes:
            st = core._nodes.get(node.address).call(("state",))
            fetch["bytes"] += st["fetch"]["bytes"]
            fetch["seconds"] += st["fetch"]["seconds"]
        return dt, fetch
    finally:
        c.shutdown()


def bench_cross_node(rows: list):
    """Locality-scheduling rows: wall-clock speedup of a task wave over a
    100 MB shared argument with locality-aware placement on vs off, and
    the effective cross-node pull throughput observed in the off run
    (which is forced to move the bytes; the zero-copy ranged path)."""
    from ray_tpu.core import runtime_context
    from ray_tpu.core.config import config as cfg

    prev = runtime_context.get_core_or_none()
    old = os.environ.get("RTPU_LOCALITY_AWARE_SCHEDULING")
    try:
        t_off, fetch = _locality_wave(False)
        t_on, _ = _locality_wave(True)
        if fetch["seconds"] > 0:
            rows.append(_row("cross_node_fetch_gbps",
                             fetch["bytes"] * 8 / fetch["seconds"] / 1e9,
                             "Gbit/s"))
        rows.append(_row("locality_scheduling_speedup",
                         t_off / max(t_on, 1e-9), "x"))
    finally:
        if old is None:
            os.environ.pop("RTPU_LOCALITY_AWARE_SCHEDULING", None)
        else:
            os.environ["RTPU_LOCALITY_AWARE_SCHEDULING"] = old
        cfg.reload()
        runtime_context.set_core(prev)


def bench_gcs_failover(rows: list):
    """gcs_failover_recovery_ms: SIGKILL the head of a live 2-node
    cluster (WAL persistence on), restart it on the same port, and time
    until the control plane fully answers again — both nodes ALIVE, a KV
    write accepted, and an actor call served. Median of 3 rounds; no
    reference number — the conservative bar lives in
    BASELINE.json.published."""
    import tempfile

    import ray_tpu
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    with tempfile.TemporaryDirectory() as pdir:
        c = Cluster(num_nodes=2, num_workers_per_node=1,
                    object_store_memory=64 << 20, gcs_persist_dir=pdir,
                    env={"RTPU_GCS_RECONNECT_TIMEOUT_S": "60"})
        try:
            assert c.wait_for_nodes(2, timeout=120)
            core = c.connect()

            @ray_tpu.remote(max_restarts=2, max_task_retries=2)
            class P:
                def ping(self):
                    return 1

            a = P.remote()
            assert ray_tpu.get(a.ping.remote(), timeout=60) == 1

            times = []
            for _ in range(3):
                c.kill_gcs()
                t0 = time.perf_counter()
                c.restart_gcs()
                assert c.wait_for_nodes(2, timeout=60)
                core.gcs.call(("kv", "put", "bench-ha", 1))
                assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
                times.append((time.perf_counter() - t0) * 1e3)
            rows.append(_row("gcs_failover_recovery_ms",
                             sorted(times)[1], "ms"))
        finally:
            c.shutdown()
            runtime_context.set_core(prev)


def bench_partition_heal(rows: list):
    """partition_heal_recovery_ms: sever the driver<->GCS edge of a live
    2-node cluster with a netem partition (no process dies — the wire
    does), poke the control plane so every pooled connection poisons,
    then heal and time until the cluster fully answers again — a KV
    write accepted AND an actor call served. This prices the reconnect
    path (pool teardown + redial + retry weave) that a real switch flap
    exercises, as opposed to bench_gcs_failover's process-death path.
    Median of 3 rounds; the partition is held well under the 3 s
    heartbeat death timeout so no node is declared dead. No reference
    number — the conservative bar lives in BASELINE.json.published."""
    import ray_tpu
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=2, num_workers_per_node=1,
                object_store_memory=64 << 20)
    try:
        assert c.wait_for_nodes(2, timeout=120)
        core = c.connect()

        @ray_tpu.remote(max_restarts=2, max_task_retries=2)
        class P:
            def ping(self):
                return 1

        a = P.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=60) == 1

        times = []
        for _ in range(3):
            c.partition("driver", "gcs")
            hold = time.perf_counter()
            while time.perf_counter() - hold < 0.5:
                # poison the pooled GCS connections so the healed round
                # has to pay the full redial, not ride a warm socket
                core.gcs.try_call(("kv", "put", "bench-chaos", 0))
                time.sleep(0.05)
            c.heal()
            t0 = time.perf_counter()
            core.gcs.call(("kv", "put", "bench-chaos", 1))
            assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
            times.append((time.perf_counter() - t0) * 1e3)
        rows.append(_row("partition_heal_recovery_ms",
                         sorted(times)[1], "ms"))
    finally:
        c.shutdown()
        runtime_context.set_core(prev)


def bench_elastic(rows: list):
    """elastic_resume_s: a 4-worker elastic training gang loses its
    highest rank to SIGKILL mid-run (gang_resize fault site) and rides
    through — abort the in-flight collective generation, drain the
    survivors, re-form at world 3, resume from the last consistent
    checkpoint. The row is the shrink event's resume_s (death detected
    -> training live again at the new world size), i.e. the cost of a
    warm resize instead of a cold gang restart. No reference number —
    the conservative bar lives in BASELINE.json.published."""
    import tempfile

    import ray_tpu
    from ray_tpu import train as train_mod
    from ray_tpu.core import fault_injection, runtime_context
    from ray_tpu.train import JaxConfig, RunConfig, ScalingConfig

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    ray_tpu.init(num_workers=6, object_store_memory=128 << 20)
    try:
        fault_injection.clear()
        fault_injection.inject("gang_resize", "kill", target="3")

        def loop(config):
            import json as _json
            import os as _os
            import tempfile as _tf

            import numpy as np

            from ray_tpu import train
            from ray_tpu.parallel import collective

            ctx = train.get_context()
            world = ctx.get_world_size()
            w = np.zeros(4)
            start = 0
            ckpt = train.get_checkpoint()
            if ckpt is not None:
                with ckpt.as_directory() as d:
                    state = _json.load(
                        open(_os.path.join(d, "state.json")))
                start = state["step"] + 1
                w = np.asarray(state["w"])
            for step in range(start, 12):
                rng = np.random.default_rng(step)
                X = rng.normal(size=(16, 4))
                g = X.T @ (X @ w - X.sum(axis=1))
                if world > 1:
                    g = np.asarray(
                        collective.allreduce(g, group_name="train"))
                w = w - 0.01 * g / 16
                with _tf.TemporaryDirectory() as d:
                    with open(_os.path.join(d, "state.json"), "w") as f:
                        _json.dump({"step": step, "w": w.tolist()}, f)
                    train.report(
                        {"step": step},
                        checkpoint=train.Checkpoint.from_directory(d))

        with tempfile.TemporaryDirectory() as sdir:
            trainer = train_mod.DataParallelTrainer(
                loop,
                backend_config=JaxConfig(platform=None,
                                         host_collectives=True),
                scaling_config=ScalingConfig(num_workers=4, min_workers=2),
                run_config=RunConfig(storage_path=sdir, name="bench"),
            )
            res = trainer.fit()
        assert res.error is None, res.error
        shrinks = [e for e in res.elastic_stats if e["event"] == "shrink"]
        assert shrinks, "the gang never shrank"
        rows.append(_row("elastic_resume_s", shrinks[0]["resume_s"], "s"))
    finally:
        fault_injection.clear()
        ray_tpu.shutdown()
        runtime_context.set_core(prev)


def bench_serve_overload(rows: list):
    """serve_p99_ttft_overload_ms: p99 completion latency of the HIGH
    priority class through the serve plane under sustained mixed-priority
    overload (arrival ~an order of magnitude over capacity; admission
    control on: 2 replicas, max_queue_depth=8, heavy-tail service times),
    plus the fraction of offered load shed with typed BackpressureError.
    The row pins the overload contract: admitted high-priority work rides
    a bounded queue, so its tail stays flat instead of growing with the
    offered load. No reference number — the conservative bar lives in
    BASELINE.json.published."""
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import runtime_context
    from ray_tpu.exceptions import BackpressureError
    from ray_tpu.serve import qos

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    ray_tpu.init(num_workers=4, object_store_memory=128 << 20)
    try:
        @serve.deployment(name="overload_bench", num_replicas=2,
                          max_queue_depth=8)
        def work(dt):
            time.sleep(dt)
            return dt

        handle = serve.run(work)

        def service_time(i):  # heavy tail: mostly fast, thick slow tail
            if i % 13 == 0:
                return 0.3
            if i % 5 == 0:
                return 0.12
            return 0.02

        lat = {"low": [], "normal": [], "high": []}
        shed = {"low": 0, "normal": 0, "high": 0}
        lock = threading.Lock()
        threads = []
        rounds = 60
        for i in range(rounds):
            for prio in ("low", "normal", "high"):
                t0 = time.perf_counter()
                try:
                    fut = handle.options(priority=prio).remote(
                        service_time(i))
                except BackpressureError:
                    with lock:
                        shed[prio] += 1
                    continue

                def reap(fut=fut, prio=prio, t0=t0):
                    try:
                        fut.result(timeout=120)
                        with lock:
                            lat[prio].append(
                                (time.perf_counter() - t0) * 1e3)
                    except BackpressureError:
                        with lock:
                            shed[prio] += 1

                t = threading.Thread(target=reap, daemon=True)
                t.start()
                threads.append(t)
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=180)
        if not lat["high"]:
            raise RuntimeError("no high-priority request completed")
        rows.append(_row("serve_p99_ttft_overload_ms",
                         qos.percentile(lat["high"], 99), "ms"))
        rows.append(_row("serve_overload_shed_fraction",
                         sum(shed.values()) / (rounds * 3), "fraction"))
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
        runtime_context.set_core(prev)


def bench_serve_replay(rows: list):
    """Request fault tolerance rows (ISSUE 20).

    serve_replica_kill_recovery_ms: worst request latency in a
    sequential unary stream over 2 replicas when one replica is
    SIGKILLed mid-flight with ``serve_request_replay`` on — the killed
    request's latency covers death detection, the re-pick (which skips
    the corpse), and the replay. Healthy requests price the floor.

    serve_stream_resume_added_ttft_ms: extra inter-chunk gap at the
    resume boundary of a token stream whose replica "dies" after the
    first delivered chunk (injected ``stream_resume``), vs the steady
    median gap of an uninterrupted stream on the same engine — the
    price of the resubmit + prompt-and-watermark re-prefill. No
    reference numbers — the conservative bars live in
    BASELINE.json.published."""
    import os as _os
    import signal as _signal
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import fault_injection, runtime_context
    from ray_tpu.core.config import config

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    _os.environ["RTPU_SERVE_REQUEST_REPLAY"] = "1"
    config.reload()
    ray_tpu.init(num_workers=4, object_store_memory=128 << 20)
    try:
        @serve.deployment(name="replay_bench", num_replicas=2)
        class Work:
            def __call__(self, x):
                time.sleep(0.02)
                return x

            def pid(self):
                return _os.getpid()

        handle = serve.run(Work.bind())
        pids = set()
        deadline = time.monotonic() + 60
        while len(pids) < 2 and time.monotonic() < deadline:
            pids.add(handle.pid.remote().result(timeout=30))
        if len(pids) < 2:
            raise RuntimeError("replay bench never saw 2 replicas")
        victim = sorted(pids)[0]
        lats = []
        for i in range(30):
            if i == 5:
                # land the kill inside the request's service window
                threading.Timer(0.01, _os.kill,
                                (victim, _signal.SIGKILL)).start()
            t0 = time.perf_counter()
            handle.remote(i).result(timeout=120)
            lats.append((time.perf_counter() - t0) * 1e3)
        rows.append(_row("serve_replica_kill_recovery_ms", max(lats),
                         "ms"))

        from ray_tpu.serve.llm_engine import LLMEngine

        # num_cpus=0.1 lands the replica on a CPU pool worker, so the
        # model is the CPU-sized one whatever this host holds — and this
        # process must not open the chip to find out (a parent that
        # touches jax takes the chip from every TPU actor after it)
        dep = serve.deployment(
            name="replay_stream_bench", engine=True, num_cpus=0.1,
        )(LLMEngine).bind(
            model_config={"preset": "tiny"}, num_slots=4,
            max_len=64, prefill_buckets=[16],
            max_new_tokens=24, chunk_steps=1)
        sh = serve.run(dep, timeout=600)
        prompt = [5, 11, 2]

        def chunk_gaps_ms(inject: bool):
            if inject:
                fault_injection.inject("stream_resume", "drop",
                                       "replay_stream_bench", times=1)
            try:
                ts = [time.perf_counter()]
                for _ in sh.stream(prompt, 24):
                    ts.append(time.perf_counter())
            finally:
                fault_injection.clear()
            # drop the TTFT gap: the rows price steady-state + resume
            return [(b - a) * 1e3 for a, b in zip(ts[1:], ts[2:])]

        chunk_gaps_ms(False)  # warm the stream path
        steady = sorted(chunk_gaps_ms(False))
        median_gap = steady[len(steady) // 2]
        resume_gap = max(chunk_gaps_ms(True))
        rows.append(_row("serve_stream_resume_added_ttft_ms",
                         max(0.1, resume_gap - median_gap), "ms"))
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
        runtime_context.set_core(prev)
        del _os.environ["RTPU_SERVE_REQUEST_REPLAY"]
        config.reload()


def bench_node_drain(rows: list):
    """node_drain_ms: cordon-to-DRAINED wall time for a 2-node cluster
    whose draining node hosts a restartable actor — the window covers
    the cordon, the actor's quiesce-then-reap migration to the healthy
    node, and the node's own idle self-report. Median of 3 rounds (a
    fresh cluster per round: drain is terminal for the node). No
    reference number — the conservative bar lives in
    BASELINE.json.published."""
    import ray_tpu
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster

    prev = runtime_context.get_core_or_none()
    times = []
    for _ in range(3):
        runtime_context.set_core(None)
        c = Cluster(num_nodes=2, num_workers_per_node=1,
                    object_store_memory=64 << 20)
        try:
            assert c.wait_for_nodes(2, timeout=120)
            c.connect()

            @ray_tpu.remote(max_restarts=1)
            class P:
                def where(self):
                    return os.environ.get("RTPU_NODE_ID")

            a = P.remote()
            host = ray_tpu.get(a.where.remote(), timeout=60)
            target = next(n for n in c.nodes
                          if c._node_id_of(n).hex() == host)
            t0 = time.perf_counter()
            assert c.drain(target)
            assert c.wait_node_state(target, "DRAINED", timeout=60)
            times.append((time.perf_counter() - t0) * 1e3)
            # the migrated actor must still answer on the survivor
            assert ray_tpu.get(a.where.remote(), timeout=60) != host
        finally:
            c.shutdown()
            runtime_context.set_core(prev)
    rows.append(_row("node_drain_ms", sorted(times)[1], "ms"))


def bench_job_orphan(rows: list):
    """job_orphan_recovery_ms: SIGKILL a (subprocess) job agent mid-job
    and time from the kill to the job reaching a terminal SUCCEEDED via
    the lease-expiry orphan path — lease timeout + GCS re-queue +
    rescuer claim + payload re-run. Median of 3 rounds on one GCS. No
    reference number — the conservative bar lives in
    BASELINE.json.published."""
    import subprocess
    import sys
    import tempfile

    from ray_tpu.core.cluster.gcs import GcsServer
    from ray_tpu.core.cluster.rpc import RpcClient
    from ray_tpu.core.config import config
    from ray_tpu.job.agent import JobAgent
    from ray_tpu.job.client import JobStatus, JobSubmissionClient

    key = b"bench-job-key"
    old_ttl = os.environ.get("RTPU_JOB_LEASE_TTL_S")
    os.environ["RTPU_JOB_LEASE_TTL_S"] = "0.6"
    config.reload()
    times = []
    try:
        with tempfile.TemporaryDirectory() as logs:
            gcs = GcsServer(authkey=key)
            addr = f"{gcs.address[0]}:{gcs.address[1]}"
            client = JobSubmissionClient(addr, authkey=key)
            try:
                for i in range(3):
                    env = dict(os.environ,
                               RTPU_CLUSTER_AUTHKEY=key.hex())
                    proc = subprocess.Popen(
                        [sys.executable, "-m", "ray_tpu.job.agent",
                         "--gcs", addr, "--agent-id", f"doomed-{i}",
                         "--poll", "0.05", "--log-dir", logs],
                        stdout=subprocess.PIPE,
                        stderr=subprocess.DEVNULL, env=env)
                    assert proc.stdout.readline().decode().startswith(
                        "AGENT_READY")
                    jid = client.submit_job(
                        entrypoint="sleep 30", max_restarts=1,
                        backoff=0.05, submission_id=f"bench-orphan-{i}")
                    deadline = time.monotonic() + 60
                    while time.monotonic() < deadline:
                        info = client.get_job_info(jid)
                        if info["status"] == JobStatus.RUNNING.value \
                                and info.get("pid"):
                            break
                        time.sleep(0.02)
                    assert info.get("pid"), "agent never claimed"
                    proc.kill()
                    proc.wait()
                    t0 = time.perf_counter()
                    # the retry's entrypoint completes immediately: the
                    # timed window prices the ORPHAN MACHINERY (lease
                    # expiry + re-queue + claim), not the payload
                    client._gcs.call(("kv", "merge", f"job/{jid}",
                                      {"entrypoint": "true"}))
                    rescuer = JobAgent(
                        RpcClient(gcs.address, key), gcs.address,
                        agent_id=f"rescuer-{i}", log_dir=logs,
                        poll_s=0.05)
                    try:
                        deadline = time.monotonic() + 60
                        while time.monotonic() < deadline:
                            st = client.get_job_status(jid)
                            if st == JobStatus.SUCCEEDED:
                                break
                            time.sleep(0.02)
                        assert st == JobStatus.SUCCEEDED, st
                    finally:
                        rescuer.close()
                    times.append((time.perf_counter() - t0) * 1e3)
            finally:
                client.close()
                gcs.close()
    finally:
        if old_ttl is None:
            os.environ.pop("RTPU_JOB_LEASE_TTL_S", None)
        else:
            os.environ["RTPU_JOB_LEASE_TTL_S"] = old_ttl
        config.reload()
    rows.append(_row("job_orphan_recovery_ms", sorted(times)[1], "ms"))


def bench_many_nodes_actors() -> float:
    """The actor-fleet creation row ALONE on a fresh 16-node cluster.

    Run in its own interpreter (``bench.py --many-nodes-actors-row``):
    the row is fork-bound, so page-cache/allocator churn left behind by
    whatever ran before moved it 3x with test ordering (VERDICT r5 weak
    #6). A fresh process + fresh cluster pins the preconditions."""
    import ray_tpu
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster

    runtime_context.set_core(None)
    c = Cluster(num_nodes=16, num_workers_per_node=1,
                object_store_memory=64 << 20)
    try:
        assert c.wait_for_nodes(16, timeout=180)
        c.connect()

        # same warmup shape as the combined bench had before isolation:
        # a task wave wakes every node's worker before the timed window
        @ray_tpu.remote
        def f(x):
            return x + 1

        ray_tpu.get([f.remote(i) for i in range(200)], timeout=120)

        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        t0 = time.perf_counter()
        actors = [A.remote() for _ in range(100)]
        ray_tpu.get([a.ping.remote() for a in actors], timeout=600)
        return 100 / (time.perf_counter() - t0)
    finally:
        c.shutdown()


def bench_many_nodes_actors_isolated(rows: list, cooldown_s: float = 5.0):
    """Run the actor-creation row in a fresh subprocess after a cooldown
    so the parent's cluster teardown (16 node processes exiting) has
    settled before the fork-heavy measurement starts."""
    import subprocess
    import sys

    time.sleep(cooldown_s)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--many-nodes-actors-row"],
        capture_output=True, text=True, timeout=900, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    rate = float(json.loads(out.stdout.strip().splitlines()[-1])
                 ["actors_per_sec"])
    rows.append(_row("many_nodes_actors_per_sec", rate, "actors/s",
                     627.3))


def main():
    rows: list = []

    # 0) ray_perf-style core microbenchmarks FIRST, before jax loads: the
    # TPU sections leave runtime threads behind that steal CPU from the
    # host path and depress memcpy/dispatch rates
    try:
        bench_core(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "core_microbench", "value": -1,
                     "unit": f"error: {e}"})

    try:
        bench_many_nodes(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "many_nodes_tasks_per_sec", "value": -1,
                     "unit": f"error: {e}"})

    # actor-fleet creation in a FRESH subprocess + cooldown: isolated
    # from test ordering (fork-bound row, VERDICT r5 weak #6)
    try:
        bench_many_nodes_actors_isolated(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "many_nodes_actors_per_sec", "value": -1,
                     "unit": f"error: {e}"})

    # locality rows on a fresh 2-node cluster (ISSUE 4 acceptance:
    # locality_scheduling_speedup >= 1.5x on the shared-arg wave)
    try:
        bench_cross_node(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "locality_scheduling_speedup", "value": -1,
                     "unit": f"error: {e}"})

    # head-node failover recovery on a fresh 2-node cluster (ISSUE 6:
    # GCS SIGKILL + same-port restart with WAL persistence)
    try:
        bench_gcs_failover(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "gcs_failover_recovery_ms", "value": -1,
                     "unit": f"error: {e}"})

    # wire-level chaos recovery on a fresh 2-node cluster (ISSUE 15:
    # netem partition + heal, nothing dies — prices the reconnect path)
    try:
        bench_partition_heal(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "partition_heal_recovery_ms", "value": -1,
                     "unit": f"error: {e}"})

    # elastic gang shrink ride-through (ISSUE 7: SIGKILL a gang worker,
    # resume warm at the smaller world size from the last consistent
    # checkpoint)
    try:
        bench_elastic(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "elastic_resume_s", "value": -1,
                     "unit": f"error: {e}"})

    # serve-plane overload contract: bounded high-priority tail + typed
    # shedding under sustained mixed-priority overload (ISSUE 10)
    try:
        bench_serve_overload(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "serve_p99_ttft_overload_ms", "value": -1,
                     "unit": f"error: {e}"})

    # serving-plane request fault tolerance: mid-flight replica kill
    # recovery + mid-stream resume cost (ISSUE 20)
    try:
        bench_serve_replay(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "serve_replica_kill_recovery_ms",
                     "value": -1, "unit": f"error: {e}"})

    # planned-removal lifecycle: cordon -> actor migration -> DRAINED
    # (ISSUE 16: drain must move work, not kill it)
    try:
        bench_node_drain(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "node_drain_ms", "value": -1,
                     "unit": f"error: {e}"})

    # supervised-job orphan path: agent SIGKILL -> lease expiry ->
    # re-queue -> rescuer completes (ISSUE 16)
    try:
        bench_job_orphan(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "job_orphan_recovery_ms", "value": -1,
                     "unit": f"error: {e}"})

    # scalability AFTER many_nodes: the 1M-task slab leaves the single
    # core hot (allocator/page-cache churn) and measurably depresses the
    # fork-bound actor-launch row when run before it (28.7 -> 9.2/s)
    try:
        bench_scalability(rows)
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "scalability_bench", "value": -1,
                     "unit": f"error: {e}"})

    # 1) headline: flagship train step on the chip
    import jax

    backend = jax.default_backend()
    tok_s, mfu, loss, n_params, dt = bench_train_step("flash")
    rows.append(_row("train_tokens_per_sec_per_chip", tok_s, "tokens/s/chip"))
    rows.append(_row("train_mfu", mfu, "fraction"))
    rows.append(_row("train_step_ms", dt * 1e3, "ms"))
    if backend == "tpu":
        tok_ref, mfu_ref, *_ = bench_train_step("reference")
        rows.append(_row("train_tokens_per_sec_reference_attn", tok_ref,
                         "tokens/s/chip"))
        rows.append(_row("flash_attention_step_speedup",
                         tok_s / max(tok_ref, 1e-9), "x"))
        try:
            err = bench_flash_numerics()
            # bf16 tolerance bound asserted ON-CHIP (CI asserts 2e-5 in
            # fp32 interpret mode; this is the hardware-kernel check)
            assert err < 0.1, f"flash bwd grads diverged on-chip: {err}"
            rows.append(_row("flash_bwd_grad_max_err_vs_ref", err,
                             "abs (bound 0.1)"))
        except Exception as e:  # pragma: no cover
            rows.append({"metric": "flash_bwd_grad_max_err_vs_ref",
                         "value": -1, "unit": f"error: {e}"})
        # 8B-dim per-layer rows: the "Llama-3-8B" shape measured for real
        for seq_len in (2048, 4096):
            try:
                ms, mfu8 = bench_layer_8b(seq_len)
                rows.append(_row(f"layer8b_step_ms_seq{seq_len}", ms, "ms"))
                rows.append(_row(f"layer8b_mfu_seq{seq_len}", mfu8,
                                 "fraction"))
            except Exception as e:  # pragma: no cover
                rows.append({"metric": f"layer8b_step_ms_seq{seq_len}",
                             "value": -1, "unit": f"error: {e}"})

    # 2) MoE train step on the chip
    try:
        moe_tok_s = bench_moe_train()
        rows.append(_row("moe_train_tokens_per_sec_per_chip", moe_tok_s,
                         "tokens/s/chip"))
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "moe_train_tokens_per_sec_per_chip",
                     "value": -1, "unit": f"error: {e}"})

    # 3) serve: p50 TTFT + continuous-batched decode throughput on the chip
    try:
        (ttft_ms, dec_tok_s, itl_ms, stream_tok_s,
         solo_tok_s, weight_bytes) = bench_serve_ttft()
        rows.append(_row("serve_ttft_p50_ms", ttft_ms, "ms"))
        rows.append(_row("serve_decode_tokens_per_sec", dec_tok_s,
                         "tokens/s"))
        rows.append(_row("serve_itl_p50_ms", itl_ms, "ms"))
        rows.append(_row("serve_tokens_per_sec_per_stream_p50",
                         stream_tok_s, "tokens/s"))
        rows.append(_row("serve_tokens_per_sec_single_stream_unbatched",
                         solo_tok_s, "tokens/s"))
        rows.append(_row("serve_batching_per_stream_retention",
                         stream_tok_s / max(solo_tok_s, 1e-9), "x"))
        if backend == "tpu":
            # decode is HBM-bound on weight reads: one full pass of the
            # weights per decode step, so utilization = weight bytes /
            # measured per-step time / chip HBM bandwidth (VERDICT r4
            # item 1's accounting)
            step_s = itl_ms / 1e3
            rows.append(_row("decode_hbm_bw_utilization",
                             weight_bytes / max(step_s, 1e-9)
                             / _chip_peak_hbm(), "fraction"))
            # int8 weight-only decode: on the pipelined engine the
            # dequant fuses and the halved weight reads land (r5)
            try:
                (_, int8_tok_s, int8_itl, _, _, _) = bench_serve_ttft(
                    quantize="int8")
                rows.append(_row("serve_int8_itl_p50_ms", int8_itl,
                                 "ms"))
                rows.append(_row("serve_int8_decode_tokens_per_sec",
                                 int8_tok_s, "tokens/s"))
            except Exception as e:  # pragma: no cover
                rows.append({"metric": "serve_int8_itl_p50_ms",
                             "value": -1, "unit": f"error: {e}"})
    except Exception as e:  # pragma: no cover
        rows.append({"metric": "serve_ttft_p50_ms", "value": -1,
                     "unit": f"error: {e}"})

    # 3b) paged-KV engine: Pallas page-gather decode + prefix caching
    if backend == "tpu":
        try:
            (paged_itl, cold_ms, warm_ms,
             speedup) = bench_serve_paged()
            rows.append(_row("serve_paged_itl_p50_ms", paged_itl, "ms"))
            rows.append(_row("serve_prefix_cold_ttft_ms_4k", cold_ms,
                             "ms"))
            rows.append(_row("serve_prefix_warm_ttft_ms_4k", warm_ms,
                             "ms"))
            rows.append(_row("serve_prefix_cache_ttft_speedup", speedup,
                             "x"))
        except Exception as e:  # pragma: no cover
            rows.append({"metric": "serve_paged_itl_p50_ms", "value": -1,
                         "unit": f"error: {e}"})

    # 3c) disaggregated serving plane (ISSUE 18): cache-affinity routing
    # hit ratio over 2 replicas, and prefill/decode split tail latency
    if backend == "tpu":
        try:
            hit_aff, hit_blind = bench_serve_affinity()
            rows.append(_row("serve_prefix_hit_ratio_multireplica",
                             hit_aff, "fraction"))
            rows.append(_row("serve_prefix_hit_ratio_blind", hit_blind,
                             "fraction"))
            rows.append(_row("serve_affinity_hit_ratio_speedup",
                             hit_aff / max(hit_blind, 1e-9), "x"))
        except Exception as e:  # pragma: no cover
            rows.append({"metric": "serve_prefix_hit_ratio_multireplica",
                         "value": -1, "unit": f"error: {e}"})
        try:
            dis = bench_serve_disagg()
            rows.append(_row("serve_disagg_off_p99_ttft_ms",
                             dis["off"][0], "ms"))
            rows.append(_row("serve_disagg_on_p99_ttft_ms",
                             dis["on"][0], "ms"))
            rows.append(_row("serve_disagg_off_p99_itl_ms",
                             dis["off"][1], "ms"))
            rows.append(_row("serve_disagg_on_p99_itl_ms",
                             dis["on"][1], "ms"))
            # acceptance: moving prefill off the decode loop must not
            # inflate the decode ITL tail (>= 1.0 means on wins)
            rows.append(_row("serve_disagg_itl_tail_ratio",
                             dis["off"][1] / max(dis["on"][1], 1e-9),
                             "x"))
        except Exception as e:  # pragma: no cover
            rows.append({"metric": "serve_disagg_on_p99_itl_ms",
                         "value": -1, "unit": f"error: {e}"})

    # BASELINE.json.published was empty until this repo established it
    # (round 2); once present, report the honest ratio against it.
    published = {}
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE.json")) as f:
            published = json.load(f).get("published") or {}
    except (OSError, ValueError):
        pass
    base_tok = published.get("train_tokens_per_sec_per_chip")
    ncores = os.cpu_count() or 1
    # the note's measured claim comes from THIS run's rows, not a baked
    # constant
    put_ratio = next((r["value"] for r in rows
                      if r["metric"] == "put_bandwidth_vs_host_memcpy"),
                     None)
    note = (f"{ncores}-core host; the reference microbenchmark baselines "
            f"ran on a 64-vCPU m5.16xlarge, so aggregate-parallelism "
            f"rows (n_n/multi_client/many_nodes) are bounded by "
            f"{ncores} core(s) here — compare per core")
    if put_ratio is not None:
        note += (f"; this run's put bandwidth was {put_ratio}x the "
                 f"host's measured streaming-memcpy ceiling")
    out = {
        "hardware_note": note,
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_s / base_tok, 3) if base_tok else 1.0,
        "mfu": round(mfu, 4),
        "model_params": n_params,
        "backend": backend,
        "loss": round(loss, 4),
        "rows": rows,
    }

    # Regression guard (round-3 verdict: north-star drift must not land
    # silently): on the real chip, fail LOUDLY when a published headline
    # regresses >10%. "Published" values were measured on quiet hardware;
    # direction-aware comparison (latency metrics regress UP).
    guard = []
    if backend == "tpu" and published:
        by_name = {r["metric"]: r["value"] for r in rows
                   if isinstance(r.get("value"), (int, float))
                   and r["value"] > 0}
        by_name["train_tokens_per_sec_per_chip"] = tok_s
        checks = [  # (published key, row key, higher_is_better)
            ("train_tokens_per_sec_per_chip",
             "train_tokens_per_sec_per_chip", True),
            ("train_mfu", "train_mfu", True),
            ("moe_train_tokens_per_sec_per_chip",
             "moe_train_tokens_per_sec_per_chip", True),
            ("serve_decode_tokens_per_sec",
             "serve_decode_tokens_per_sec", True),
            ("serve_ttft_p50_ms_loaded", "serve_ttft_p50_ms", False),
            ("serve_itl_p50_ms", "serve_itl_p50_ms", False),
            ("single_node_task_with_10k_args_s",
             "single_node_task_with_10k_args_s", False),
            ("single_node_task_returning_3k_objects_s",
             "single_node_task_returning_3k_objects_s", False),
            ("single_node_get_10k_objects_s",
             "single_node_get_10k_objects_s", False),
            ("single_node_1m_queued_tasks_s",
             "single_node_1m_queued_tasks_s", False),
            ("many_nodes_actors_per_sec",
             "many_nodes_actors_per_sec", True),
            ("streaming_first_output_latency_ms",
             "streaming_first_output_latency_ms", False),
            ("actor_restart_recovery_ms",
             "actor_restart_recovery_ms", False),
            ("serve_int8_itl_p50_ms", "serve_int8_itl_p50_ms", False),
            ("serve_int8_decode_tokens_per_sec",
             "serve_int8_decode_tokens_per_sec", True),
            ("locality_scheduling_speedup",
             "locality_scheduling_speedup", True),
            ("cross_node_fetch_gbps", "cross_node_fetch_gbps", True),
            ("gcs_failover_recovery_ms", "gcs_failover_recovery_ms",
             False),
            ("partition_heal_recovery_ms", "partition_heal_recovery_ms",
             False),
            ("elastic_resume_s", "elastic_resume_s", False),
            ("serve_p99_ttft_overload_ms",
             "serve_p99_ttft_overload_ms", False),
            ("dag_pipeline_latency_us", "dag_pipeline_latency_us",
             False),
            ("dag_compiled_roundtrip_us", "dag_compiled_roundtrip_us",
             False),
            ("dag_compiled_roundtrip_block_us",
             "dag_compiled_roundtrip_block_us", False),
            ("node_drain_ms", "node_drain_ms", False),
            ("job_orphan_recovery_ms", "job_orphan_recovery_ms",
             False),
            ("serve_affinity_hit_ratio_speedup",
             "serve_affinity_hit_ratio_speedup", True),
            ("serve_prefix_hit_ratio_multireplica",
             "serve_prefix_hit_ratio_multireplica", True),
            ("serve_disagg_on_p99_ttft_ms",
             "serve_disagg_on_p99_ttft_ms", False),
            ("serve_disagg_on_p99_itl_ms",
             "serve_disagg_on_p99_itl_ms", False),
            ("serve_disagg_itl_tail_ratio",
             "serve_disagg_itl_tail_ratio", True),
            ("serve_replica_kill_recovery_ms",
             "serve_replica_kill_recovery_ms", False),
            ("serve_stream_resume_added_ttft_ms",
             "serve_stream_resume_added_ttft_ms", False),
        ]
        for pub_key, row_key, hib in checks:
            pub, got = published.get(pub_key), by_name.get(row_key)
            if not pub or not got:
                continue
            ratio = got / pub if hib else pub / got
            if ratio < 0.90:
                guard.append(f"{row_key}: {got:.1f} vs published "
                             f"{pub:.1f} ({ratio:.2f}x)")
        out["regression_guard"] = ("FAILED: " + "; ".join(guard)
                                   if guard else "ok")
    print(json.dumps(out))
    if guard:
        import sys

        print(f"REGRESSION GUARD FAILED: {'; '.join(guard)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    import sys

    if "--many-nodes-actors-row" in sys.argv:
        print(json.dumps({"actors_per_sec": bench_many_nodes_actors()}))
        sys.exit(0)
    main()
