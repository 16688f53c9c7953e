"""Runs one training cell of a model with gated delta-rule layers among
full-attention layers (Olmo-Hybrid: three to one): ``cells/train_scan.py``'s
window, tracing, compile count, report and comparison (its ``compare``,
``checks_of`` and ``first_step_left`` by import, and through it
``train_hybrid.py``'s ``load_model``, ``optimizer`` and ``model_parts``),
with a step that reports the rule's counter ``gdn_state_abs_max`` where
that file's reports the scan's: ``train_scan.make_step`` reads
``aux["ssm_state_abs_max"]``, so a model without a selective scan cannot
pass through it, and the loop below is that file's under the other name.

The comparison that decides ``correct`` is ``train_scan.py``'s (its
docstring), on the weights the first step saw and batch 0, through the
timed path's own forward at the timed sizes (``model.token_nll``: the
walked rule, the causal flash kernels without rope, the head and loss in
blocks), against ``references/<module>_ref.py`` (float32, highest
precision, the recurrence token by token): (a) the first step's loss; (b)
the per-position loss, root mean square and largest gap; (c) the linear
layers' states after the last position ``[30, 192, 96]`` a layer: the
largest ``|S|`` as a share of the reference's, and every head's whole
state, the worst head bounded; (d) the gradient of a seeded weighted loss
for every leaf of the first layer of each kind, the embedding, the last
norm and the head; (e) adamw's first moment and the parameters after the
timed program's own first step, against optax's adamw in float32 on the
reference's gradient.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Dict

from benchmark.cells.train import _report_ended
from benchmark.cells.train_hybrid import (load_model, model_parts,  # noqa: F401
                                          optimizer)
from benchmark.cells.train_scan import (checks_of, compare,  # noqa: F401
                                        first_step_left)
from benchmark.lib import procs, spec


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    the largest ``|S|`` a linear layer's state holds after the sequence)."""
    import jax
    import optax

    trainable, with_trainable = model_parts(model)

    def step(params, opt, batch):
        trained = trainable(params)
        (loss, aux), grads = jax.value_and_grad(
            lambda t: model.loss_terms(cfg, with_trainable(params, t), batch,
                                       mesh=mesh), has_aux=True)(trained)
        updates, opt = tx.update(grads, opt, trained)
        params = with_trainable(params, optax.apply_updates(trained, updates))
        return params, opt, loss, aux["gdn_state_abs_max"]

    return step


def _train_loop(config: Dict[str, Any]) -> None:
    import jax
    import numpy as np

    from benchmark.lib import compile_counter

    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import batch_sharding

    t_loop_wall = time.time()     # the backend has opened the chips by now
    compile_counter.install()
    tr = config["traffic"]
    model, reference, cfg = load_model(config["model_config"])
    devs = jax.devices()
    mesh = psh = bsh = None
    if tr["mesh_axes"]:
        mesh = build_mesh(MeshSpec(tr["mesh_axes"]), devices=devs)
        psh = model.param_shardings(cfg, mesh)
        bsh = batch_sharding(mesh)
    seed = config["seed"]
    init = jax.jit(lambda k: model.init_params(cfg, k), out_shardings=psh)
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    params = init(key)
    tx = optimizer(tr)
    opt = tx.init(model_parts(model)[0](params))
    B, S = tr["batch"], tr["seq"]
    host = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (tr["host_batches"], B, S + 1), np.int32)

    def put(i: int):
        return {"tokens": jax.device_put(host[i % len(host)], bsh)}

    batch = put(0)
    compiled = jax.jit(make_step(model, cfg, tx, mesh),
                       donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    losses = []
    for i in range(tr["warmup_steps"]):          # step 0 runs batch 0
        params, opt, loss, state_max = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends, state_maxes = [], []
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
            params, opt, loss, state_max = compiled(params, opt, batch)
        with ann("bench.wait"):
            loss.block_until_ready()
        ends.append(time.monotonic())
        # to the host at once (train_mixed.py says why)
        losses.append(float(loss))
        state_maxes.append(float(state_max))
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
    took = [e - s for s, e in zip([t_open] + ends, ends)]
    clean = [d for j, d in enumerate(took) if traced["on"] is None
             or j < traced["on"] or j > traced["off"]]
    loss_values = losses
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    # ---- correctness, after the window (the module's docstring)
    del params, opt, batch, loss, losses, state_max
    # (e): the timed executable once more on what its first call was given
    # (the seeded weights, a new optimizer state, batch 0), here and not in
    # the warm-up: copies taken there cost every step of the window 3 ms
    params = init(key)
    after, opt, *_ = compiled(params, tx.init(model_parts(model)[0](params)),
                              put(0))
    left = first_step_left(reference, after, opt)
    del after, opt
    params = init(key)
    gaps = compare(model, reference, cfg, params, put(0)["tokens"], host[0],
                   mesh=mesh, seed=seed, first_step=(tx, left))

    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": ends[-1] - t_open,
        "steps": len(ends), "step_ends": [e - t_open for e in ends],
        "untraced_steps": len(clean), "untraced_s": sum(clean),
        "losses": loss_values, "gaps": gaps,
        "compiles": compiles, "mosaic_calls": mosaic_calls,
        "state_bytes": state_bytes,
        "step_memory_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs_not_aliased": (mem.output_size_in_bytes
                                    - mem.alias_size_in_bytes)},
        "traced_steps": (traced["off"] - traced["on"]
                         if traced["on"] is not None else 0),
        # the program's own counter of the last step (rtpu_train_*)
        "gdn_state_abs_max": state_maxes[-1],
        "gdn_state_abs_max_first_most": [state_maxes[0], max(state_maxes)],
    })


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import math

    module = ctx["model_config"]["module"]
    if not os.path.exists(os.path.join(spec.ROOT, "ray_tpu", "models",
                                       module + ".py")):
        # a checkout from before the model: fail at once, not in a worker
        # (asked by path: importing ray_tpu.models here would import jax)
        raise RuntimeError(f"this checkout has no ray_tpu/models/{module}.py"
                           ": it cannot run this training cell")
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
    gaps = rep["gaps"]
    tg, sm = gaps["token_nll_gap"], gaps["state_abs_max"]
    checks = checks_of(traffic["check"], losses[0], gaps)
    worst = {kind: max(leaves, key=leaves.get)
             for kind, leaves in gaps["gradient_gap"].items()}
    print(f"[bench] first-step loss {losses[0]:.5f}; reference "
          f"{gaps['ref_terms']['loss']:.5f}; losses finite: {finite}; last "
          f"loss {losses[-1]:.4f}; mosaic calls {rep['mosaic_calls']}; state "
          f"{rep['state_bytes'] / 1e9:.2f} GB; step memory "
          f"{rep['step_memory_bytes']}; peak bytes in use "
          f"{rep['memory_peak_bytes']}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, the worst leaf of each "
          f"kind of layer: {worst}; every leaf: {gaps['gradient_gap']}",
          flush=True)
    print(f"[bench] compared: {tg['positions']} positions; the reference's "
          f"per-token loss spreads {tg['ref_std']:.3f} (p99 gap "
          f"{tg['p99']:.4f}); the largest |S| after the sequence: program "
          f"{sm['program']:.4f}, reference {sm['reference']:.4f}; in the "
          f"window's first step {rep['gdn_state_abs_max_first_most'][0]:.4f}"
          f", at most {rep['gdn_state_abs_max_first_most'][1]:.4f}, in the "
          f"last {rep['gdn_state_abs_max']:.4f}; a head's whole last state "
          f"against the reference's: {gaps['state_head_gap']}", flush=True)
    print("[bench] what the first step handed on against the reference's "
          f"adamw step, every leaf: {gaps['first_step']}", flush=True)
    print(f"[bench] the worker held its chips and entered the train loop "
          f"{rep['t_loop_wall'] - ctx['t_start_wall']:.1f}s after this "
          f"process started", flush=True)
    print(f"[bench] window {rep['window_s']:.3f}s (asked {ctx['seconds']}); "
          f"compilations inside the window: {rep['compiles']}; steps "
          f"{rep['steps']}, of them outside the profiler's span "
          f"{rep['untraced_steps']} in {rep['untraced_s']:.3f}s", flush=True)
    ends = rep["step_ends"]
    took = sorted((b - a, j) for j, (a, b) in enumerate(zip([0.0] + ends,
                                                            ends)))
    print(f"[bench] a step took {took[0][0]:.4f} / "
          f"{took[len(took) // 2][0]:.4f} / {took[-1][0]:.4f}s (least, "
          f"median, most); the three longest were steps "
          f"{[(j, round(d, 4)) for d, j in took[:-4:-1]]}", flush=True)

    if rep["compiles"]:
        raise RuntimeError(f"{rep['compiles']} program(s) compiled inside "
                           f"the measured window")
    device = {"platform": rep["platform"], "device_kind": rep["device_kind"],
              "device_count": rep["device_count"],
              "memory_peak_bytes": rep["memory_peak_bytes"]}
    bad_steps = 0 if finite else sum(not math.isfinite(x) for x in losses)
    return {
        "correct": finite and all(v <= t for v, t in checks.values()),
        "attempted": rep["steps"], "failed": bad_steps, "device": device,
        "setup_s": rep["t_open_wall"] - ctx["t_start_wall"],
        "obs": {"train": {"steps": rep["steps"], "window_s": rep["window_s"],
                          "tokens_per_step": traffic["batch"] * traffic["seq"],
                          "chips": rep["device_count"],
                          "traced_steps": rep["traced_steps"],
                          "untraced_steps": rep["untraced_steps"],
                          "untraced_s": rep["untraced_s"],
                          "gdn_state_abs_max": rep["gdn_state_abs_max"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
