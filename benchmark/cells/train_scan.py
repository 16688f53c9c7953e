"""Runs one training cell of a model with selective-scan layers and no
routed layer (Granite 4.0-H: nine Mamba-2 layers to one attention layer):
``cells/train_hybrid.py``'s window, tracing, compile count and report
(its ``load_model``, ``optimizer`` and ``model_parts`` by import), with a
step that has no expert counts to hand out and reports the scan's counter
``ssm_state_abs_max`` in their place. ``train_hybrid.make_step`` reads
``aux["expert_counts"]``, so a model without a router cannot pass through
it; the loop below is that file's, less everything about routing.

The comparison that decides ``correct``: the weights the first step saw
and batch 0, through the timed path's own forward at the timed sizes
(``model.token_nll``: the walked scan, the causal flash kernels without
rope at the stated scale, the head and loss in blocks), against
``references/<module>_ref.py`` (float32, highest precision, the recurrence
token by token). (a) The first step's loss against the reference's. (b)
The per-position next-token loss, root mean square and largest gap. (c)
The scan layers' states after the last position against the reference's:
the largest ``|S|`` (the counter) as a share of the reference's, and the
whole state a head at a time, the norm of the gap over the norm of the
reference's head, the worst head of the layers' bounded (a decay or a
running sum that loses its float32 shows here first). (d) The gradient of
a seeded scalar, ``sum(w * per-position loss)``, for every leaf of the
first Mamba layer, the attention layer, the embedding and the last norm:
the norm of the gap over the norm of the reference's leaf, the worst leaf
of a kind bounded. (e) What the timed program's own first step hands on
(the compiled step run once more, after the window, on what its first
call was given), for the same leaves: adamw's first moment and the
parameters after the step, against the reference's adamw step (optax in
float32) on the reference's gradient of the mean loss, as (d) measures a
gap. A step that hands on the state it was given reads 1 on the moment.
The schedule's rate at step 0 is 0 (the foot of a linear ramp), so the
parameters the reference's step hands on are the ones it was given, and
the program's have to be bit-equal: that limit is 0.
"""

from __future__ import annotations

import os
import shutil
import time
from functools import lru_cache
from typing import Any, Dict, Tuple

from benchmark.cells.train import _report_ended
from benchmark.cells.train_hybrid import (load_model, model_parts,  # noqa: F401
                                          optimizer)
from benchmark.cells.train_mixed import _gradient_gaps
from benchmark.lib import procs, spec


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    the largest ``|S|`` a scan layer's state holds after the sequence)."""
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
        return params, opt, loss, aux["ssm_state_abs_max"]

    return step


@lru_cache(maxsize=None)
def _program(model, reference, cfg, mesh):
    """The program's side of ``compare``, jitted once a configuration."""
    import jax

    def program(p, tokens, weights):
        def weighted(p_):
            nll, states = model.token_nll(cfg, p_, tokens, mesh=mesh)
            return (weights * nll).sum(), (nll, states)

        (_, (nll, states)), grads = jax.value_and_grad(
            weighted, has_aux=True)(p)
        return nll, states, reference.first_layers(grads)

    return jax.jit(program)


def first_step_left(reference, params, opt):
    """Host copies of what a train step handed on, for the leaves a
    gradient is asked for (``reference.first_layers``): the parameters and
    adamw's first moment."""
    import jax

    return jax.device_get({"params": reference.first_layers(params),
                           "mu": reference.first_layers(opt[0].mu)})


@lru_cache(maxsize=None)
def _first_step_gaps(tx):
    """Jitted: (what ``first_step_left`` gave, the leaves the step started
    from, the reference's gradient of the mean loss) -> (the first
    moment's gaps by kind and leaf, the parameters' gap as one number: the
    norm of all gaps over the norm of all the reference's leaves), against
    ``tx``'s own first step in float32."""
    import jax
    import jax.numpy as jnp
    import optax

    def gaps(left, start, grads):
        start = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), start)
        updates, opt = tx.update(grads, tx.init(start), start)
        want = optax.apply_updates(start, updates)
        leaves = jax.tree_util.tree_leaves
        gap = sum(jnp.square(a.astype(jnp.float32) - b).sum()
                  for a, b in zip(leaves(left["params"]), leaves(want)))
        return (_gradient_gaps()(left["mu"], opt[0].mu),
                jnp.sqrt(gap / sum(jnp.square(b).sum() for b in leaves(want))))

    return jax.jit(gaps)


def compare(model, reference, cfg, params, tokens, host_tokens, mesh=None,
            reference_params=None, program_cfg=None, reference_mantissa_bits=None,
            seed: int = 0, first_step=None) -> Dict[str, Any]:
    """The gaps between the program's own forward (on ``tokens``, the
    device's copy of ``host_tokens``) and the reference, and between their
    gradients of ``sum(weights * per-position loss)``, the weights drawn
    from ``seed``. ``first_step``: (the optimizer, what ``first_step_left``
    gave of a step on these weights and tokens) adds the gaps of what that
    step handed on (the module's docstring, (e)). ``reference_params`` (the
    reference's weights where the program's have a fault planted),
    ``program_cfg`` and ``reference_mantissa_bits`` are for
    ``benchmark/tests/scan_limits.py``, which shows that the tolerances
    refuse a reference in a lower precision and a program with a fault
    planted."""
    import numpy as np

    weights = (np.random.default_rng(seed + 1).uniform(
        0.5, 1.5, host_tokens[:, 1:].shape) / host_tokens[:, 1:].size
               ).astype(np.float32)

    def floats(by_kind):
        return {kind: {name: float(v) for name, v in leaves.items()}
                for kind, leaves in by_kind.items()}

    got_nll, got_states, got_grads = _program(
        model, reference, program_cfg or cfg, mesh)(params, tokens, weights)
    got_nll, got_states = np.asarray(got_nll), np.asarray(got_states)
    ref_params = params if reference_params is None else reference_params
    ref = reference.token_nll(cfg, ref_params, host_tokens,
                              grad_weights=weights,
                              mantissa_bits=reference_mantissa_bits)
    by_kind = floats(_gradient_gaps()(got_grads, ref.pop("grads")))
    del got_grads
    stepped = {}
    if first_step is not None:
        tx, left = first_step
        mean = reference.token_nll(
            cfg, ref_params, host_tokens,
            grad_weights=np.full_like(weights, 1.0 / weights.size),
            mantissa_bits=reference_mantissa_bits)
        moment, moved = _first_step_gaps(tx)(
            left, reference.first_layers(ref_params), mean.pop("grads"))
        stepped = {"first_step": {"moment_gap": floats(moment),
                                  "param_gap": float(moved)}}
    nll_gap = np.abs(got_nll - ref["nll"])
    # the last states [Lm, B, H, P, N], a head at a time
    ref_states = ref["last_states"]
    head_gap = (np.sqrt(np.square(got_states - ref_states).sum((-2, -1)))
                / np.sqrt(np.square(ref_states).sum((-2, -1))))
    return {
        **stepped,
        "ref_terms": ref["terms"],
        "program_loss": float(got_nll.mean()),
        "state_abs_max": {"program": float(np.abs(got_states).max()),
                          "reference": ref["state_abs_max"]},
        "state_head_gap": {
            "worst": float(head_gap.max()),
            "median": float(np.median(head_gap)),
            "layer_row_head": [int(i) for i in np.unravel_index(
                head_gap.argmax(), head_gap.shape)]},
        "token_nll_gap": {"rms": float(np.sqrt(np.mean(nll_gap ** 2))),
                          "max": float(nll_gap.max()),
                          "p99": float(np.quantile(nll_gap, 0.99)),
                          "positions": int(nll_gap.size),
                          "ref_std": float(ref["nll"].std())},
        "gradient_gap": by_kind}


def checks_of(chk: Dict[str, Any], first_loss: float, gaps: Dict[str, Any]
              ) -> Dict[str, Tuple[float, float]]:
    """what -> (reading, tolerance): the comparison that decides
    ``correct``, of ``compare()``'s ``gaps`` and the first step's loss,
    under the traffic file's ``check``."""
    tg, sm = gaps["token_nll_gap"], gaps["state_abs_max"]
    checks = {
        "first-step loss": (abs(first_loss - gaps["ref_terms"]["loss"]),
                            chk["loss_tolerance"]),
        "per-token loss, rms": (tg["rms"], chk["token_nll_rms_tolerance"]),
        "per-token loss, max": (tg["max"], chk["token_nll_max_tolerance"]),
        "scan state, largest |S|": (
            abs(sm["program"] - sm["reference"]) / sm["reference"],
            chk["state_abs_max_tolerance"]),
        "scan state, a head's whole": (gaps["state_head_gap"]["worst"],
                                       chk["state_head_gap_tolerance"]),
    }
    for kind, leaves in gaps["gradient_gap"].items():
        checks[f"gradient, {kind}"] = (
            max(leaves.values()), chk["gradient_gap_tolerance"][kind])
    if "first_step" in gaps:
        for kind, leaves in gaps["first_step"]["moment_gap"].items():
            checks[f"first step, moment, {kind}"] = (
                max(leaves.values()),
                chk["first_step_moment_tolerance"][kind])
        checks["first step, parameters"] = (
            gaps["first_step"]["param_gap"],
            chk["first_step_param_tolerance"])
    return checks


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
        "ssm_state_abs_max": state_maxes[-1],
        "ssm_state_abs_max_first_most": [state_maxes[0], max(state_maxes)],
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
          f"window's first step {rep['ssm_state_abs_max_first_most'][0]:.4f}"
          f", at most {rep['ssm_state_abs_max_first_most'][1]:.4f}, in the "
          f"last {rep['ssm_state_abs_max']:.4f}; a head's whole last state "
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
                          "ssm_state_abs_max": rep["ssm_state_abs_max"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
