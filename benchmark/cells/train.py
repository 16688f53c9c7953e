"""Runs one training cell: ``JaxTrainer`` through the runtime's TPU
actor, one worker that owns the cell's chips, adamw on a fresh seeded
batch each step. The loop below runs in that worker; this process never
imports jax.

The window holds whole steps: it opens before a step is sent and closes
when the first step ends at or after ``--seconds``; the rate is all its
tokens over all its time, and every step ends in ``block_until_ready``.
In a traced run the profiler starts and stops inside the window and
stalls the steps around it, so the worker also reports the steps and the
time of the window that lie outside the profiler's span (``untraced_*``);
``mfu`` reads its rate from those.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Dict

from benchmark.lib import procs, spec


def _train_loop(config: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.lib import compile_counter
    from benchmark.references import llama_ref
    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import batch_sharding

    t_loop_wall = time.time()     # the backend has opened the chips by now
    compile_counter.install()
    tr = config["traffic"]
    kw = dict(config["model_config"])
    preset = kw.pop("preset")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = getattr(llama.LlamaConfig, preset)(**kw, attn_impl="auto")
    devs = jax.devices()
    mesh = psh = bsh = None
    if tr["mesh_axes"]:
        mesh = build_mesh(MeshSpec(tr["mesh_axes"]), devices=devs)
        psh = llama.param_shardings(cfg, mesh)
        bsh = batch_sharding(mesh)
    seed = config["seed"]
    init = jax.jit(lambda k: llama.init_params(cfg, k), out_shardings=psh)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    params = init(key)
    tx = optax.adamw(tr["lr"])
    opt = tx.init(params)
    B, S = tr["batch"], tr["seq"]
    host = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (tr["host_batches"], B, S + 1), np.int32)

    def put(i: int):
        return {"tokens": jax.device_put(host[i % len(host)], bsh)}

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, batch, mesh=mesh))(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    batch = put(0)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    losses = []
    for i in range(tr["warmup_steps"]):          # step 0 runs batch 0
        params, opt, loss = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(loss)

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends = []
    traced = {"on": None, "off": None}
    t_open_wall = time.time()
    t_open = time.monotonic()
    i = tr["warmup_steps"]
    while True:
        n = len(ends)
        if trace_dir and traced["on"] is None and n == tr["trace_from_step"]:
            jax.profiler.start_trace(trace_dir)
            traced["on"] = n
        with ann("bench.send"):
            batch = put(i)
        with ann("bench.step"):
            params, opt, loss = compiled(params, opt, batch)
        with ann("bench.wait"):
            loss.block_until_ready()
        ends.append(time.monotonic())
        losses.append(loss)
        i += 1
        if (traced["on"] is not None and traced["off"] is None
                and len(ends) == traced["on"] + tr["trace_steps"]):
            jax.profiler.stop_trace()
            traced["off"] = len(ends)
        if ends[-1] - t_open >= config["seconds"]:
            break
    if trace_dir and traced["off"] is None:
        jax.profiler.stop_trace()
        raise RuntimeError("the window closed before the trace did")
    compiles = compile_counter.count() - compiles0
    # step j took from the end of step j-1 to its own end; start_trace
    # runs before step ``on`` is sent and stop_trace after step ``off-1``
    # has ended, so the stalls fall into steps on .. off
    took = [e - s for s, e in zip([t_open] + ends, ends)]
    clean = [d for j, d in enumerate(took) if traced["on"] is None
             or j < traced["on"] or j > traced["off"]]
    loss_values = [float(x) for x in losses]
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    # ---- correctness, after the window: the program's next-token loss
    # at every position of batch 0, on the weights the first step saw,
    # against the plain float32 reference (the step's own first loss is
    # compared with the reference's mean by the caller)
    del params, opt, batch, loss, losses

    def token_nll(p, tokens):
        lg = llama.forward(cfg, p, tokens[:, :-1], mesh=mesh)
        return jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, tokens[:, 1:, None], -1)[..., 0]

    params = init(key)
    got = np.asarray(jax.jit(token_nll)(params, put(0)["tokens"]))
    ref = llama_ref.token_nll(cfg, params, host[0])
    gap = np.abs(got - ref)

    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": ends[-1] - t_open,
        "steps": len(ends), "step_ends": [e - t_open for e in ends],
        "untraced_steps": len(clean), "untraced_s": sum(clean),
        "losses": loss_values, "ref_loss": float(ref.mean()),
        "token_nll_gap": {"rms": float(np.sqrt(np.mean(gap ** 2))),
                          "max": float(gap.max()),
                          "p99": float(np.quantile(gap, 0.99)),
                          "positions": int(gap.size),
                          "ref_std": float(ref.std())},
        "compiles": compiles, "mosaic_calls": mosaic_calls,
        "state_bytes": state_bytes,
        "traced_steps": (traced["off"] - traced["on"]
                         if traced["on"] is not None else 0),
    })


def _report_ended(ended: Dict[str, float], t_fit: float) -> None:
    print(f"[bench] after the runtime's shutdown {ended['reaped']:.0f} "
          f"child process(es) were waited for during {ended['seconds']:.2f}s"
          f" ({ended['killed']:.0f} had to be killed); the last ended "
          f"{time.monotonic() - t_fit:.2f}s after fit() returned; host "
          f"memory in use {100 * procs.host_memory_used_share():.1f}%",
          flush=True)


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import math

    import ray_tpu
    from ray_tpu import state
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    name = cell["name"]
    model = spec.model_sizes(config)
    trace_dir = os.path.join(ctx["tmp_dir"], f"trace-{name}")
    storage = os.path.join(ctx["tmp_dir"], f"train-{name}")
    for d in (trace_dir, storage):
        shutil.rmtree(d, ignore_errors=True)
    ray_tpu.init(num_workers=2, object_store_memory=256 << 20)
    try:
        if ctx["platform"] == "tpu":
            have = int(state.cluster_resources().get("TPU", 0))
            if have < cell["chips"]:
                raise RuntimeError(
                    f"the runtime found {have} TPU chip(s); the cell needs "
                    f"{cell['chips']}")
        result = JaxTrainer(
            _train_loop,
            train_loop_config={
                "model_config": ctx["model_config"], "traffic": traffic,
                "seed": ctx["seed"], "seconds": ctx["seconds"],
                "trace_dir": trace_dir if ctx["trace"] else None},
            scaling_config=ScalingConfig(**ctx["scaling"]),
            jax_config=JaxConfig(**ctx["jax_config"]),
            run_config=RunConfig(name=name, storage_path=storage),
        ).fit()
    finally:
        t_fit = time.monotonic()   # fit() has signalled the chips' owner
        ray_tpu.shutdown()
        _report_ended(procs.wait_for_children(), t_fit)
    if result.error is not None:
        raise RuntimeError(f"training failed: {result.error!r}")
    rep = result.metrics_history[-1]
    if rep["platform"] != ctx["platform"] or \
            rep["device_count"] != ctx["devices"]:
        raise RuntimeError(
            f"the worker ran on platform={rep['platform']!r} "
            f"({rep['device_kind']!r}) with {rep['device_count']} device(s); "
            f"the cell needs {ctx['devices']} x {ctx['platform']!r}")
    losses = rep["losses"]
    finite = all(math.isfinite(x) for x in losses)
    gap = abs(losses[0] - rep["ref_loss"])
    chk = traffic["check"]
    tol = chk["loss_tolerance"]
    tg = rep["token_nll_gap"]
    token_ok = (tg["rms"] <= chk["token_nll_rms_tolerance"]
                and tg["max"] <= chk["token_nll_max_tolerance"])
    print(f"[bench] first-step loss {losses[0]:.5f} reference "
          f"{rep['ref_loss']:.5f} gap {gap:.2e} (tolerance {tol}); losses "
          f"finite: {finite}; last loss {losses[-1]:.4f}; mosaic calls "
          f"{rep['mosaic_calls']}; state {rep['state_bytes'] / 1e9:.2f} GB",
          flush=True)
    print(f"[bench] per-token loss against the reference over "
          f"{tg['positions']} positions: rms gap {tg['rms']:.4f} "
          f"(tolerance {chk['token_nll_rms_tolerance']}), max "
          f"{tg['max']:.4f} (tolerance {chk['token_nll_max_tolerance']}), "
          f"p99 {tg['p99']:.4f}; the reference's own spread over "
          f"positions {tg['ref_std']:.3f}; ok={token_ok}", flush=True)
    print(f"[bench] the worker held its chips and entered the train loop "
          f"{rep['t_loop_wall'] - ctx['t_start_wall']:.1f}s after this "
          f"process started", flush=True)
    print(f"[bench] window {rep['window_s']:.3f}s (asked {ctx['seconds']}); "
          f"compilations inside the window: {rep['compiles']}; steps "
          f"{rep['steps']}, of them outside the profiler's span "
          f"{rep['untraced_steps']} in {rep['untraced_s']:.3f}s", flush=True)
    if rep["compiles"]:
        raise RuntimeError(f"{rep['compiles']} program(s) compiled inside "
                           f"the measured window")
    device = {"platform": rep["platform"], "device_kind": rep["device_kind"],
              "device_count": rep["device_count"],
              "memory_peak_bytes": rep["memory_peak_bytes"]}
    bad_steps = 0 if finite else sum(not math.isfinite(x) for x in losses)
    return {
        "correct": finite and gap <= tol and token_ok,
        "attempted": rep["steps"], "failed": bad_steps, "device": device,
        "setup_s": rep["t_open_wall"] - ctx["t_start_wall"],
        "obs": {"train": {"steps": rep["steps"], "window_s": rep["window_s"],
                          "tokens_per_step": traffic["batch"] * traffic["seq"],
                          "chips": rep["device_count"],
                          "traced_steps": rep["traced_steps"],
                          "untraced_steps": rep["untraced_steps"],
                          "untraced_s": rep["untraced_s"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
