#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at the published widths of Llama-3.2-1B with seeded random weights:

  kernels      a ``num_tpus=1`` actor compares both Pallas kernels with
               their references at this model's head shape
  serve-paged  ``serve.run`` of ``PagedLLMEngine`` with ``num_tpus=1``;
               requests through the handle, one of them streamed
  serve-dense  the same for ``LLMEngine``, started only after the first
               replica's process has exited (the chip changes hands)
  train        ``JaxTrainer(ScalingConfig(use_tpu=True),
               JaxConfig(platform="tpu"))``: adamw steps on one batch

and, where the runtime finds four chips, ``serve-4x1`` (four one-chip
replicas) and ``train-1x4`` (one worker, fsdp over four chips).

This process never imports jax: every device belongs to a worker the
runtime spawned for it. Any failed check raises, so the script exits
non-zero and prints no result. It takes no arguments and never looks at
the backend to pick a size; ``tests/test_zz_chip_smoke.py`` runs the same
phase functions at a tiny size on CPU workers.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import json
import os
import signal
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

# Llama-3.2-1B as published (config.json of meta-llama/Llama-3.2-1B),
# spelled as overrides of the repo's Llama-3 preset.
LLAMA_3_2_1B = {
    "preset": "llama3_8b",
    "vocab_size": 128_256, "hidden_size": 2048, "intermediate_size": 8192,
    "num_layers": 16, "num_heads": 32, "num_kv_heads": 8, "head_dim": 64,
    "tie_embeddings": True, "rms_norm_eps": 1e-5, "rope_theta": 500_000.0,
    "rope_scaling": (("rope_type", "llama3"), ("factor", 32.0),
                     ("low_freq_factor", 1.0), ("high_freq_factor", 4.0),
                     ("original_max_position_embeddings", 8192)),
    "dtype": "bfloat16", "param_dtype": "bfloat16",
}

# Serving shape: buckets are multiples of 128 so the flash prefill kernel
# runs; the long prompt spans two prefill chunks on the paged engine.
SERVE = {"num_slots": 8, "max_len": 1024, "prefill_buckets": [128, 512],
         "chunk_steps": 2}
PROMPT_LENS = [40, 100, 300, 500]
LONG_PROMPT_LEN = 900
NEW_TOKENS = 8

# Training shape: 4 x 2048 tokens fills ~12 of the chip's 15.75 GiB at
# full depth (compiler's estimate); four chips divide the same batch.
TRAIN = {"batch": 4, "seq": 2048, "steps": 4, "lr": 1e-3, "seed": 0}

# Stated tolerances. Kernel outputs are bf16 (eps 2^-8): errors are taken
# against an f32 "highest" reference on the same inputs, relative to the
# reference's largest magnitude — a wrong mask or scale gives O(1).
KERNEL_FWD_TOL = 2e-2
KERNEL_GRAD_TOL = 4e-2
# One chip vs four: same math per example, different reduction order in
# bf16, compounded by a few adamw steps, on a loss of ~11.8.
LOSS_TOL = 5e-2

TIME_LIMIT_S = 1150          # the contract allows 1200 for the whole script
PHASE_TIMEOUT_S = 600


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _check_device(phase: str, rep: Dict[str, Any], platform: str,
                  count: int) -> None:
    _require(rep["platform"] == platform,
             f"{phase}: the process the runtime gave the chip to reports "
             f"platform={rep['platform']!r} ({rep['device_kind']!r}), "
             f"expected {platform!r} — no accelerator behind this path")
    _require(rep["device_count"] == count,
             f"{phase}: process sees {rep['device_count']} devices, "
             f"expected {count} (TPU_VISIBLE_CHIPS="
             f"{rep.get('visible_chips')!r})")


def _check_mosaic(phase: str, calls: Dict[str, int],
                  expected: List[str]) -> None:
    """``calls``: Mosaic custom calls per compiled program. The programs
    named in ``expected`` must have the kernel, the others must not (a
    kernel where none was expected means this check is out of date)."""
    for prog, n in calls.items():
        _require((n >= 1) == (prog in expected),
                 f"{phase}: program {prog} has {n} Mosaic custom calls; "
                 f"the Pallas kernel was expected in {expected} only "
                 f"(all programs: {calls})")


def _device_line(phase: str, rep: Dict[str, Any], setup_s: float,
                 run_s: float, extra: str = "") -> None:
    """One line per phase, built from what the chip-holding process said
    about itself, plus the wall time this process measured around it."""
    print(f"[chip_smoke] phase={phase} pid={rep['pid']} "
          f"platform={rep['platform']} "
          f"device_kind={rep['device_kind']!r} "
          f"devices={rep['device_count']} "
          f"chips={rep.get('visible_chips')} "
          f"setup_s={setup_s:.1f} run_s={run_s:.1f}"
          + (f" {extra}" if extra else ""), flush=True)


def _pid_gone(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except (OSError, IndexError):
        return True


def _wait_pids_gone(phase: str, pids: List[int], timeout_s: float = 60.0):
    deadline = time.monotonic() + timeout_s
    while not all(_pid_gone(p) for p in pids):
        _require(time.monotonic() < deadline,
                 f"{phase}: replica process(es) {pids} still alive "
                 f"{timeout_s:g}s after the deployment was deleted — the "
                 f"chip was not given back")
        time.sleep(0.05)


def _prompt(vocab: int, n: int, seed: int) -> List[int]:
    import random

    rng = random.Random(seed)
    return [rng.randrange(1, vocab) for _ in range(n)]


# --------------------------------------------------------------- kernels


def _kernel_parity(heads: int, kv_heads: int, head_dim: int, seq: int,
                   page: int, interpret: bool) -> Dict[str, Any]:
    """Runs in the process that holds the chip: both Pallas kernels
    against their references at one head shape, outside any timing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import attention_reference, flash_attention
    from ray_tpu.ops.paged_attention import (paged_attention,
                                             paged_attention_reference)

    f32 = jnp.float32
    devs = jax.devices()
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    b = 2
    q = jax.random.normal(ks[0], (b, seq, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, seq, kv_heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, seq, kv_heads, head_dim), jnp.bfloat16)
    w = jax.random.normal(ks[3], q.shape, f32)   # cotangent

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, use_pallas=True,
                               interpret=interpret)

    def ref(q_, k_, v_):
        return attention_reference(q_.astype(f32), k_.astype(f32),
                                   v_.astype(f32), causal=True)

    def wsum(fn):
        return lambda *a: (fn(*a).astype(f32) * w).sum()

    def rel(a, r):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        return float(np.abs(a - r).max() / max(1.0, np.abs(r).max()))

    flash_j = jax.jit(flash)
    grad_j = jax.jit(jax.grad(wsum(flash), argnums=(0, 1, 2)))
    with jax.default_matmul_precision("highest"):
        out_r = jax.jit(ref)(q, k, v)
        g_r = jax.jit(jax.grad(wsum(ref), argnums=(0, 1, 2)))(q, k, v)
    fwd_err = rel(flash_j(q, k, v), out_r)
    grad_err = max(rel(a, r) for a, r in zip(grad_j(q, k, v), g_r))

    # paged: ragged contexts incl. empty, one token, a page edge, full
    S, G, maxp = 4, heads // kv_heads, 8
    n_pages = S * maxp
    q2 = jax.random.normal(ks[4], (S, kv_heads, G, head_dim), jnp.bfloat16)
    kp = jax.random.normal(ks[5], (n_pages, kv_heads, page, head_dim),
                           jnp.bfloat16)
    vp = jax.random.normal(ks[6], (n_pages, kv_heads, page, head_dim),
                           jnp.bfloat16)
    bt = jnp.asarray(np.random.default_rng(0).permutation(n_pages)
                     .reshape(S, maxp).astype(np.int32))
    ctx = jnp.asarray([0, 1, 3 * page, maxp * page - 5], jnp.int32)
    paged_j = jax.jit(lambda *a: paged_attention(*a, interpret=interpret))
    acc, m, l = paged_j(q2, kp, vp, bt, ctx)
    with jax.default_matmul_precision("highest"):
        acc_r, m_r, l_r = jax.jit(paged_attention_reference)(
            q2.astype(f32), kp.astype(f32), vp.astype(f32), bt, ctx)
    live = np.asarray(ctx) > 0

    def normed(acc_, l_):
        return (np.asarray(acc_, np.float32)
                / np.maximum(np.asarray(l_, np.float32), 1e-30)[..., None])

    paged_err = max(rel(normed(acc, l)[live], normed(acc_r, l_r)[live]),
                    rel(np.asarray(m)[live], np.asarray(m_r)[live]))
    empty_ok = bool(np.all(np.asarray(acc)[~live] == 0))

    def mosaic(jitted, *a):
        return jitted.lower(*a).compile().as_text().count("tpu_custom_call")

    return {
        "pid": os.getpid(), "platform": devs[0].platform,
        "device_kind": devs[0].device_kind, "device_count": len(devs),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "flash_fwd_err": fwd_err, "flash_grad_err": grad_err,
        "paged_err": paged_err, "paged_empty_ok": empty_ok,
        "mosaic_calls": {"flash_fwd": mosaic(flash_j, q, k, v),
                         "flash_grad": mosaic(grad_j, q, k, v),
                         "paged": mosaic(paged_j, q2, kp, vp, bt, ctx)},
    }


class _KernelProbe:
    def run(self, *args):
        return _kernel_parity(*args)


def phase_kernels(model_config: Dict[str, Any], resources: Dict[str, Any],
                  platform: str, interpret: bool, seq: int = 1024,
                  page: int = 64, device_count: int = 1) -> Dict[str, Any]:
    """Both Pallas kernels vs their references, in an actor of the
    runtime that holds ``resources``. ``interpret`` runs the kernels in
    Pallas interpret mode (CPU tests); otherwise each must have compiled
    to a Mosaic custom call."""
    import ray_tpu

    t0 = time.monotonic()
    actor = ray_tpu.remote(_KernelProbe).options(**resources).remote()
    rep = ray_tpu.get(actor.run.remote(
        model_config["num_heads"], model_config["num_kv_heads"],
        model_config["head_dim"], seq, page, interpret),
        timeout=PHASE_TIMEOUT_S)
    ray_tpu.kill(actor)
    _wait_pids_gone("kernels", [rep["pid"]])
    _check_device("kernels", rep, platform, device_count)
    _require(rep["flash_fwd_err"] <= KERNEL_FWD_TOL,
             f"flash forward disagrees with attention_reference: "
             f"{rep['flash_fwd_err']:.3g} > {KERNEL_FWD_TOL}")
    _require(rep["flash_grad_err"] <= KERNEL_GRAD_TOL,
             f"flash gradient disagrees with attention_reference: "
             f"{rep['flash_grad_err']:.3g} > {KERNEL_GRAD_TOL}")
    _require(rep["paged_err"] <= KERNEL_FWD_TOL and rep["paged_empty_ok"],
             f"paged attention disagrees with paged_attention_reference: "
             f"{rep['paged_err']:.3g} > {KERNEL_FWD_TOL} "
             f"(empty slots zero: {rep['paged_empty_ok']})")
    _check_mosaic("kernels", rep["mosaic_calls"],
                  [] if interpret else list(rep["mosaic_calls"]))
    _device_line("kernels", rep, 0.0, time.monotonic() - t0,
                 f"flash_fwd_err={rep['flash_fwd_err']:.2e} "
                 f"flash_grad_err={rep['flash_grad_err']:.2e} "
                 f"paged_err={rep['paged_err']:.2e} "
                 f"mosaic={rep['mosaic_calls']}")
    return rep


# ----------------------------------------------------------------- serve


def _engine_cls(engine: str):
    if engine == "paged":
        from ray_tpu.serve.paged_engine import PagedLLMEngine

        return PagedLLMEngine
    from ray_tpu.serve.llm_engine import LLMEngine

    return LLMEngine


def _deploy(name: str, engine: str, model_config, serve_cfg, resources,
            num_replicas: int = 1):
    from ray_tpu import serve

    dep = serve.deployment(
        engine=True, name=name, num_replicas=num_replicas, **resources)(
            _engine_cls(engine)).bind(model_config=model_config, **serve_cfg)
    return serve.run(dep, timeout=PHASE_TIMEOUT_S)


def _replica_reports(name: str, n: int, platform: str,
                     device_count: int = 1) -> List[dict]:
    """report() of every replica, once each engine has compiled. Raises
    on the first start-up error instead of waiting it out."""
    import ray_tpu
    from ray_tpu.serve.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    deadline = time.monotonic() + PHASE_TIMEOUT_S
    while True:
        _, replicas = ray_tpu.get(controller.get_replicas.remote(name),
                                  timeout=30)
        reps = [ray_tpu.get(h.call_method.remote("report", (), {}),
                            timeout=PHASE_TIMEOUT_S)
                for _, h in replicas]
        for rep in reps:
            _require(rep["first_error"] is None,
                     f"{name}: engine start-up failed in pid "
                     f"{rep['pid']}:\n{rep['first_error']}")
        if len(reps) == n and all(r["mosaic_calls"] is not None
                                  for r in reps):
            for rep in reps:
                _check_device(name, rep, platform, device_count)
            return reps
        _require(time.monotonic() < deadline,
                 f"{name}: engines not ready within {PHASE_TIMEOUT_S}s")
        time.sleep(0.5)


def _check_reply(phase: str, reply: Any, n_new: int, vocab: int) -> List[int]:
    _require(isinstance(reply, dict),
             f"{phase}: reply is {type(reply).__name__}, not a dict: "
             f"{reply!r}")
    toks = reply["tokens"]
    _require(len(toks) == n_new and all(
        isinstance(t, int) and 0 <= t < vocab for t in toks),
        f"{phase}: expected {n_new} token ids in [0, {vocab}), got {toks}")
    return toks


def phase_serve(name: str, engine: str, model_config: Dict[str, Any],
                serve_cfg: Dict[str, Any], resources: Dict[str, Any],
                platform: str, mosaic_programs: List[str],
                prompt_lens: List[int], long_prompt_len: int,
                n_new: int, device_count: int = 1) -> Dict[str, Any]:
    """One engine replica behind ``serve.run``; requests go router →
    replica → engine submit/collect. ``mosaic_programs`` names the engine
    programs that must contain a Mosaic custom call (none on CPU).
    Returns once the replica's process has exited."""
    from ray_tpu import serve

    t0 = time.monotonic()
    handle = _deploy(name, engine, model_config, serve_cfg, resources)
    rep = _replica_reports(name, 1, platform, device_count)[0]
    setup_s = time.monotonic() - t0
    _check_mosaic(name, rep["mosaic_calls"], mosaic_programs)

    t1 = time.monotonic()
    vocab = model_config["vocab_size"]
    prompts = [_prompt(vocab, n, seed=i) for i, n in enumerate(prompt_lens)]
    pending = [handle.remote(p, max_new_tokens=n_new) for p in prompts]
    outs = [_check_reply(name, r.result(timeout=PHASE_TIMEOUT_S), n_new,
                         vocab) for r in pending]
    # greedy decoding is deterministic: same prompt, same tokens — unary
    # again, and streamed through the peek mailbox
    again = _check_reply(name, handle.remote(
        prompts[0], max_new_tokens=n_new).result(timeout=PHASE_TIMEOUT_S),
        n_new, vocab)
    _require(again == outs[0],
             f"{name}: greedy repeat differs: {outs[0]} then {again}")
    streamed = [t for chunk in handle.stream(
        prompts[-1], max_new_tokens=n_new) for t in chunk]
    _require(streamed == outs[-1],
             f"{name}: streamed tokens {streamed} differ from the unary "
             f"reply {outs[-1]}")
    extra = ""
    if engine == "paged":
        # a long prompt (several prefill chunks) asked twice: the second
        # must reuse the first's cached prefix pages
        long_p = _prompt(vocab, long_prompt_len, seed=99)
        a = _check_reply(name, handle.remote(
            long_p, max_new_tokens=n_new).result(timeout=PHASE_TIMEOUT_S),
            n_new, vocab)
        b = _check_reply(name, handle.remote(
            long_p, max_new_tokens=n_new).result(timeout=PHASE_TIMEOUT_S),
            n_new, vocab)
        _require(a == b, f"{name}: long prompt repeat differs: {a} / {b}")
        hits = handle.stats.remote().result(
            timeout=60)["prefix_hit_tokens"]
        _require(hits > 0, f"{name}: repeated {long_prompt_len}-token "
                           f"prompt reported prefix_hit_tokens={hits}")
        extra = f" prefix_hit_tokens={hits}"
    run_s = time.monotonic() - t1

    end = _replica_reports(name, 1, platform, device_count)[0]
    _require(end["pid"] == rep["pid"],
             f"{name}: replica was replaced mid-phase "
             f"({rep['pid']} -> {end['pid']})")
    serve.delete(name)
    _wait_pids_gone(name, [rep["pid"]])
    _device_line(name, rep, setup_s, run_s,
                 f"engine_setup_s={rep['setup_s']:.1f} "
                 f"mosaic={rep['mosaic_calls']} "
                 f"completed={end['stats']['completed']}{extra}")
    return rep


def phase_serve_replicas(name: str, engine: str, model_config, serve_cfg,
                         resources, platform: str, num_replicas: int,
                         n_new: int, device_count: int = 1,
                         prompt_len: int = 60) -> List[dict]:
    """``num_replicas`` one-chip replicas of the same deployment, alive
    together: each process must see exactly one device, the runtime must
    have granted each a different chip, and every one must answer."""
    import ray_tpu
    from ray_tpu import serve

    t0 = time.monotonic()
    handle = _deploy(name, engine, model_config, serve_cfg, resources,
                     num_replicas=num_replicas)
    reps = _replica_reports(name, num_replicas, platform, device_count)
    setup_s = time.monotonic() - t0
    pids = [r["pid"] for r in reps]
    chips = [r["visible_chips"] for r in reps]
    _require(len(set(pids)) == num_replicas,
             f"{name}: replicas share processes: {pids}")
    if platform == "tpu":
        # inside a confined process every chip is device 0, so identity
        # is the runtime's grant; two processes cannot hold one chip
        _require(None not in chips and len(set(chips)) == num_replicas,
                 f"{name}: replicas were not granted distinct chips: "
                 f"{chips}")
    t1 = time.monotonic()
    vocab = model_config["vocab_size"]
    for wave in range(8):
        pending = [handle.remote(
            _prompt(vocab, prompt_len, seed=1000 + 16 * wave + i),
            max_new_tokens=n_new) for i in range(4 * num_replicas)]
        for r in pending:
            _check_reply(name, r.result(timeout=PHASE_TIMEOUT_S), n_new,
                         vocab)
        done = [r["stats"]["completed"]
                for r in _replica_reports(name, num_replicas, platform,
                                          device_count)]
        if all(done):
            break
    _require(all(done), f"{name}: some replica answered nothing after "
                        f"{wave + 1} waves: completed per replica {done}")
    run_s = time.monotonic() - t1
    serve.delete(name)
    _wait_pids_gone(name, pids)
    for rep, n in zip(reps, done):
        _device_line(name, rep, setup_s, run_s,
                     f"engine_setup_s={rep['setup_s']:.1f} completed={n}")
    return reps


# ----------------------------------------------------------------- train


def _train_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: the model the engines serve, llama.loss_fn
    with attn_impl="auto", adamw on one seeded batch; reports each step.
    The state is BORN sharded (jit with out_shardings) — never built on
    one device and moved."""
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import batch_sharding

    kw = dict(config["model_config"])
    preset = kw.pop("preset")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = getattr(llama.LlamaConfig, preset)(**kw, attn_impl="auto")
    devs = jax.devices()
    mesh = psh = bsh = None
    if config["mesh_axes"]:
        mesh = build_mesh(MeshSpec(config["mesh_axes"]), devices=devs)
        psh = llama.param_shardings(cfg, mesh)
        bsh = batch_sharding(mesh)

    params = jax.jit(lambda k: llama.init_params(cfg, k),
                     out_shardings=psh)(jax.random.PRNGKey(config["seed"]))
    tx = optax.adamw(config["lr"])
    opt = tx.init(params)   # eager zeros_like keeps each leaf's sharding
    tokens = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1), np.int32)
    batch = {"tokens": jax.device_put(jnp.asarray(tokens), bsh)}
    jax.block_until_ready((params, opt, batch))

    def mem():
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]

    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))
    state_mem = mem()

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, batch, mesh=mesh))(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    text = compiled.as_text()
    # a pallas_call GSPMD could not partition shows up as an all-gather
    # of q/k/v or the output — rank >= 3, head_dim innermost, at least
    # the whole batch of K — feeding it; fsdp's weight gathers are rank 2
    import re

    act_elems = (config["batch"] * config["seq"] * cfg.num_kv_heads
                 * cfg.head_dim_)
    act_gathers = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* all-gather\(", text):
        dims = [int(x) for x in m.group(1).split(",")]
        if (len(dims) >= 3 and dims[-1] == cfg.head_dim_
                and np.prod(dims) >= act_elems):
            act_gathers.append(m.group(1))
    setup_s = time.monotonic() - t0
    for i in range(config["steps"]):
        t = time.monotonic()
        params, opt, loss = compiled(params, opt, batch)
        loss.block_until_ready()
        step_s = time.monotonic() - t
        t = time.monotonic()
        loss_f = float(loss)
        train.report({
            "step": i, "loss": loss_f, "step_s": step_s,
            "readback_after_barrier_s": time.monotonic() - t,
            "setup_s": setup_s, "pid": os.getpid(),
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "mosaic_calls": text.count("tpu_custom_call"),
            "activation_all_gathers": act_gathers,
            "state_bytes": state_bytes, "state_bytes_in_use": state_mem,
            "peak_bytes_in_use": [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devs],
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        })


def _check_train(name: str, hist: List[Dict[str, Any]], steps: int,
                 platform: str, device_count: int,
                 min_mosaic_calls: int) -> None:
    """What the per-step reports of ``_train_loop`` must show."""
    _require(len(hist) == steps,
             f"{name}: {len(hist)} reports for {steps} steps")
    rep = hist[-1]
    _check_device(name, rep, platform, device_count)
    _require(rep["mosaic_calls"] >= min_mosaic_calls,
             f"{name}: compiled train step has {rep['mosaic_calls']} "
             f"Mosaic custom calls, expected >= {min_mosaic_calls} — the "
             f"Pallas flash forward/backward did not run")
    _require(not rep["activation_all_gathers"],
             f"{name}: activations all-gathered around the attention "
             f"kernel: {rep['activation_all_gathers']}")
    losses = [h["loss"] for h in hist]
    _require(all(x == x and abs(x) != float("inf") for x in losses),
             f"{name}: non-finite loss: {losses}")
    _require(losses[-1] < losses[0],
             f"{name}: loss on the repeated batch did not fall: {losses}")
    if device_count > 1 and rep["state_bytes_in_use"][0] is not None:
        share = rep["state_bytes"] / device_count
        _require(all(0.5 * share <= b <= 1.5 * share
                     for b in rep["state_bytes_in_use"]),
                 f"{name}: params + optimizer state ({rep['state_bytes']} "
                 f"bytes) are not divided over the devices: in use after "
                 f"init {rep['state_bytes_in_use']}")


def phase_train(name: str, model_config: Dict[str, Any],
                train_cfg: Dict[str, Any], scaling: Dict[str, Any],
                jax_cfg: Dict[str, Any], platform: str, device_count: int,
                mesh_axes: Optional[Dict[str, int]],
                min_mosaic_calls: int) -> Dict[str, Any]:
    """A few adamw steps through JaxTrainer(ScalingConfig(**scaling),
    JaxConfig(**jax_cfg)). ``min_mosaic_calls``: Mosaic custom calls the
    compiled step must contain (flash forward, dQ, dK/dV: 3 on the chip,
    0 where attn_impl="auto" picks the reference)."""
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={"model_config": model_config,
                               "mesh_axes": mesh_axes, **train_cfg},
            scaling_config=ScalingConfig(**scaling),
            jax_config=JaxConfig(**jax_cfg),
            run_config=RunConfig(name=name, storage_path=storage),
        ).fit()
    wall_s = time.monotonic() - t0
    _require(result.error is None, f"{name}: training failed: "
                                   f"{result.error!r}")
    hist = result.metrics_history
    _check_train(name, hist, train_cfg["steps"], platform, device_count,
                 min_mosaic_calls)
    rep = hist[-1]
    losses = [h["loss"] for h in hist]
    run_s = sum(h["step_s"] for h in hist)
    _device_line(
        name, rep, wall_s - run_s, run_s,
        f"loop_setup_s={rep['setup_s']:.1f} "
        f"losses={[round(x, 4) for x in losses]} "
        f"step_s={[round(h['step_s'], 3) for h in hist]} "
        f"readback_after_barrier_s="
        f"{max(h['readback_after_barrier_s'] for h in hist):.4f} "
        f"mosaic={rep['mosaic_calls']} state_bytes={rep['state_bytes']} "
        f"state_in_use={rep['state_bytes_in_use']} "
        f"peak_in_use={rep['peak_bytes_in_use']}")
    return {"losses": losses, **rep}


# ------------------------------------------------------------------ main


def _on_alarm(signum, frame):
    raise SmokeFailure(f"chip_smoke exceeded its {TIME_LIMIT_S}s limit")


def main() -> None:
    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.core.resources import scan_tpu_chips

    signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(TIME_LIMIT_S)
    t0 = time.monotonic()
    ray_tpu.init(num_workers=2, object_store_memory=256 << 20)
    try:
        chips = int(state.cluster_resources().get("TPU", 0))
        _require(chips >= 1,
                 "no TPU chip: the runtime detected none on this machine "
                 f"({scan_tpu_chips()[1]}); JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}")
        tpu1 = {"num_tpus": 1}
        phase_kernels(LLAMA_3_2_1B, tpu1, "tpu", interpret=False)
        phase_serve("serve-paged", "paged", LLAMA_3_2_1B, SERVE, tpu1,
                    "tpu", ["decode"], PROMPT_LENS, LONG_PROMPT_LEN,
                    NEW_TOKENS)
        phase_serve("serve-dense", "dense", LLAMA_3_2_1B, SERVE, tpu1,
                    "tpu", [f"prefill[{b}]" for b in SERVE["prefill_buckets"]],
                    PROMPT_LENS, LONG_PROMPT_LEN, NEW_TOKENS)
        one = phase_train(
            "train", LLAMA_3_2_1B, TRAIN,
            {"num_workers": 1, "use_tpu": True}, {"platform": "tpu"}, "tpu",
            1, None, 3)
        device = one
        if chips >= 4:
            phase_serve_replicas("serve-4x1", "paged", LLAMA_3_2_1B, SERVE,
                                 tpu1, "tpu", 4, NEW_TOKENS)
            four = phase_train(
                "train-1x4", LLAMA_3_2_1B, TRAIN,
                {"num_workers": 1, "use_tpu": True, "chips_per_worker": 4},
                {"platform": "tpu"}, "tpu", 4, {"fsdp": 4}, 3)
            gaps = [abs(a - b) for a, b in zip(one["losses"],
                                               four["losses"])]
            _require(max(gaps) <= LOSS_TOL,
                     f"train-1x4 losses {four['losses']} differ from the "
                     f"one-chip {one['losses']} by {max(gaps):.3g} > "
                     f"{LOSS_TOL}")
            print(f"[chip_smoke] train-1x4 vs train: max |loss gap| "
                  f"{max(gaps):.2e} <= {LOSS_TOL}", flush=True)
            device = four
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        signal.alarm(0)
    _require("jax" not in sys.modules,
             "the parent process imported jax — it would hold the chip")
    print(f"[chip_smoke] all phases ok in {time.monotonic() - t0:.0f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
