"""Runs one training cell of a model with leaves no optimizer owns (LFM2:
convolution and attention layers, a sigmoid router whose selection bias
the program moves after each step): ``cells/train_mixed.py``'s window,
tracing, compile count and report, with a step that trains
``model.trainable(params)`` (every leaf where the module has no such
function), puts the result back with ``model.with_trainable`` and then
calls ``model.update_router_bias`` with the step's expert counts.

The comparison is ``train_mixed.py``'s (its docstring: router logits,
differing choices, per-position loss, first-step loss, the gradient of a
seeded scalar leaf by leaf in the first layer of each kind), with three
differences. The program's choices are ``route``'s own (``forward(...,
keep_router_logits=True)`` hands out its ``top_e``), and a differing
choice's regret is measured in the reference's selection scores
(``select_scores``, ``s + b``), which is what the choice is made on. (e)
The routers' biases after the first step are compared with the
reference's rule (``<module>_ref.updated_bias``) applied to the biases the
step started from and the program's own counts of that step: a sign of
integer differences, so the tolerance is 0. (f) The first step's biases
are 0, where a choice on ``s + b`` is a choice on ``s``; so the same
forward runs once more on those weights with the biases the window ended
with (the program's own, 0.001 a step), and ``route``'s choices are held
to the selection scores recomputed on the host from the program's own
logits and those biases (``own_regret``): a sigmoid's rounding for a
``route`` that adds the bias, the biases' size for one that does not.

The loop is ``train_mixed.py``'s: a step is sent, waited for and its small
outputs read before the next is sent. The module is named by the
configuration (``model_config["module"]``), as in ``train_mixed.py``; four
runners now repeat one loop (PERF.md 7).
"""

from __future__ import annotations

import os
import shutil
import time
from functools import lru_cache
from typing import Any, Dict, Tuple

from benchmark.cells.train import _report_ended
from benchmark.cells.train_mixed import _gradient_gaps
from benchmark.lib import procs, spec


def _same(params, trained=None):
    return params if trained is None else trained


def model_parts(model):
    """(trainable, with_trainable) of a model module: the identity where
    every leaf is the optimizer's."""
    return (getattr(model, "trainable", _same),
            getattr(model, "with_trainable", _same))


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    the routed layers' expert counts [Lr, E], the largest ``|b|``)."""
    import jax
    import jax.numpy as jnp
    import optax

    trainable, with_trainable = model_parts(model)

    def step(params, opt, batch):
        trained = trainable(params)
        (loss, aux), grads = jax.value_and_grad(
            lambda t: model.loss_terms(cfg, with_trainable(params, t), batch,
                                       mesh=mesh), has_aux=True)(trained)
        updates, opt = tx.update(grads, opt, trained)
        params = with_trainable(params, optax.apply_updates(trained, updates))
        counts = aux["expert_counts"]
        bias_max = jnp.zeros((), jnp.float32)
        if hasattr(model, "update_router_bias"):
            params = model.update_router_bias(cfg, params, counts)
            bias_max = model.router_bias_abs_max(params)
        return params, opt, loss, counts, bias_max

    return step


@lru_cache(maxsize=None)
def _program(model, reference, cfg, mesh):
    """The program's side of ``compare``, jitted once a configuration."""
    import jax
    import jax.numpy as jnp

    trainable, with_trainable = model_parts(model)

    def program(p, tokens, weights):
        def weighted(t):
            lg, router = model.forward(cfg, with_trainable(p, t),
                                       tokens[:, :-1], mesh=mesh,
                                       keep_router_logits=True)
            nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, tokens[:, 1:, None], -1)[..., 0]
            return (weights * nll).sum(), (nll, router["logits"],
                                           router["chosen"])

        (_, (nll, logits, chosen)), grads = jax.value_and_grad(
            weighted, has_aux=True)(trainable(p))
        return nll, logits, chosen, reference.first_layers(grads)

    return jax.jit(program)


def own_regret(reference, cfg, params, logits, chosen) -> float:
    """How far below the k-th largest of the program's own selection
    scores (the sigmoid of its ``logits [Lr, n, E]`` plus the routers'
    biases of ``params``, recomputed here in float32) the program holds a
    choice ``route`` made (``chosen [Lr, n, K]``), at most."""
    import numpy as np

    select = (1.0 / (1.0 + np.exp(-logits.astype(np.float32)))
              + reference.router_biases(cfg, params)[:, None, :])
    kth = np.sort(select, axis=-1)[..., -cfg.top_k]
    return float((kth[..., None]
                  - np.take_along_axis(select, chosen, -1)).max())


def choices_under_bias(model, reference, cfg, params, tokens, mesh=None
                       ) -> float:
    """(f) of the module's docstring: ``own_regret`` of the timed path's
    forward on ``params``, whose biases are not 0."""
    import numpy as np

    _, logits, chosen, _ = _program(model, reference, cfg, mesh)(
        params, tokens, np.zeros(tokens[:, 1:].shape, np.float32))
    return own_regret(reference, cfg, params, np.asarray(logits),
                      np.asarray(chosen))


def compare(model, reference, cfg, params, tokens, host_tokens, mesh=None,
            reference_params=None, seed: int = 0) -> Dict[str, Any]:
    """The gaps between the program's ``forward`` (on ``tokens``, the
    device's copy of ``host_tokens``) and the reference forced to the
    program's choices of experts, and between their gradients of
    ``sum(weights * per-position loss)``, the weights drawn from ``seed``.
    ``reference_params`` is for ``benchmark/tests/hybrid_limits.py``, which
    shows that the tolerances refuse a reference in a lower precision and
    a program with a fault planted."""
    import numpy as np

    weights = (np.random.default_rng(seed + 1).uniform(
        0.5, 1.5, host_tokens[:, 1:].shape) / host_tokens[:, 1:].size
               ).astype(np.float32)
    got_nll, got_logits, got_chosen, got_grads = _program(
        model, reference, cfg, mesh)(params, tokens, weights)
    got_nll, got_logits, got_chosen = (
        np.asarray(x) for x in (got_nll, got_logits, got_chosen))
    ref = reference.token_nll(
        cfg, params if reference_params is None else reference_params,
        host_tokens, forced_topk=got_chosen, grad_weights=weights)
    by_kind = {kind: {name: float(v) for name, v in leaves.items()}
               for kind, leaves in _gradient_gaps()(
                   got_grads, ref.pop("grads")).items()}
    del got_grads
    # how far below the reference's k-th largest selection score the
    # reference holds each expert the program chose
    select = ref["select_scores"]
    kth = np.sort(select, axis=-1)[..., -cfg.top_k]
    regret = kth[..., None] - np.take_along_axis(select, got_chosen, -1)
    differs = regret > 0
    logit_gap = np.abs(got_logits - ref["router_logits"])
    nll_gap = np.abs(got_nll - ref["nll"])
    return {
        "ref_terms": ref["terms"],
        "router_logit_gap": {"rms": float(np.sqrt(np.mean(logit_gap ** 2))),
                             "max": float(logit_gap.max()),
                             "ref_std": float(ref["router_logits"].std())},
        "choices": {"differing_share": float(differs.mean()),
                    "max_regret": float(regret.max()),
                    "own_regret": own_regret(reference, cfg, params,
                                             got_logits, got_chosen),
                    "count": int(differs.size)},
        "token_nll_gap": {"rms": float(np.sqrt(np.mean(nll_gap ** 2))),
                          "max": float(nll_gap.max()),
                          "p99": float(np.quantile(nll_gap, 0.99)),
                          "positions": int(nll_gap.size),
                          "ref_std": float(ref["nll"].std())},
        "gradient_gap": by_kind}


def checks_of(chk: Dict[str, Any], first_loss: float, gaps: Dict[str, Any],
              bias_gap: float, biased_regret: float
              ) -> Dict[str, Tuple[float, float]]:
    """what -> (reading, tolerance): the comparison that decides
    ``correct``, of ``compare()``'s ``gaps`` and the three readings taken
    beside it, under the traffic file's ``check``."""
    rl, ch, tg = (gaps["router_logit_gap"], gaps["choices"],
                  gaps["token_nll_gap"])
    checks = {
        "first-step loss": (abs(first_loss - gaps["ref_terms"]["loss"]),
                            chk["loss_tolerance"]),
        "router logits, rms": (rl["rms"], chk["router_logit_rms_tolerance"]),
        "router logits, max": (rl["max"], chk["router_logit_max_tolerance"]),
        "differing choices, share": (ch["differing_share"],
                                     chk["differing_choice_share_tolerance"]),
        "differing choices, regret": (ch["max_regret"],
                                      chk["choice_regret_tolerance"]),
        "choices under the routers' biases, regret": (
            biased_regret, chk["biased_choice_regret_tolerance"]),
        "per-token loss, rms": (tg["rms"], chk["token_nll_rms_tolerance"]),
        "per-token loss, max": (tg["max"], chk["token_nll_max_tolerance"]),
        "router bias after the first step": (bias_gap,
                                             chk["router_bias_tolerance"]),
    }
    for kind, leaves in gaps["gradient_gap"].items():
        checks[f"gradient, {kind}"] = (
            max(leaves.values()), chk["gradient_gap_tolerance"][kind])
    return checks


def load_model(model_config: Dict[str, Any]):
    """(model module, reference module, its config at ``attn_impl="auto"``)
    of a configuration file's ``model_config``."""
    from importlib import import_module

    import jax.numpy as jnp

    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in model_config.items()}
    name, preset = kw.pop("module"), kw.pop("preset")
    model = import_module("ray_tpu.models." + name)
    reference = import_module(f"benchmark.references.{name}_ref")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = getattr(getattr(model, name.capitalize() + "Config"), preset)(
        **kw, attn_impl="auto")
    return model, reference, cfg


def optimizer(traffic: Dict[str, Any]):
    import optax

    lr = traffic["lr"]
    if traffic.get("lr_warmup_steps"):     # the start of a run: a linear ramp
        lr = optax.linear_schedule(0.0, lr, traffic["lr_warmup_steps"])
    return optax.adamw(lr)


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
    bias_gap = 0.0
    # (e): the biases the first step starts from, before it donates them
    bias0 = reference.router_biases(cfg, params)
    for i in range(tr["warmup_steps"]):          # step 0 runs batch 0
        params, opt, loss, cnt, _ = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))
        if i == 0:                  # the rule on the program's own counts
            want = reference.updated_bias(cfg, bias0, np.asarray(cnt))
            bias_gap = float(np.abs(
                reference.router_biases(cfg, params) - want).max())

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends, counts = [], []     # counts: [L, E] of each step
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
            params, opt, loss, cnt, bias_max = compiled(params, opt, batch)
        with ann("bench.wait"):
            loss.block_until_ready()
        ends.append(time.monotonic())
        # to the host at once (train_mixed.py says why)
        losses.append(float(loss))
        counts.append(np.asarray(cnt))
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
    counts = np.stack(counts)                               # [steps, L, E]
    load = counts.max(-1) / counts.mean(-1)                 # [steps, L]
    # rows the held experts multiplied, by step: all where all are held
    held = np.asarray([int(model.rows_held(cfg, c)) for c in counts]
                      if hasattr(model, "rows_held") else counts.sum((1, 2)))
    in_trace = (slice(traced["on"], traced["off"])
                if traced["on"] is not None else slice(None))
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    # ---- correctness, after the window (the module's docstring)
    bias_max = float(bias_max)
    end_biases = {kind: leaves["router_bias"]
                  for kind, leaves in params["layers"].items()
                  if "router_bias" in leaves}
    del params, opt, batch, loss, losses, cnt
    params = init(key)
    tokens = put(0)["tokens"]
    gaps = compare(model, reference, cfg, params, tokens, host[0],
                   mesh=mesh, seed=seed)
    # (f): the first step's weights under the window's last biases
    params = {**params, "layers": {
        kind: {**leaves, **({"router_bias": end_biases[kind]}
                            if kind in end_biases else {})}
        for kind, leaves in params["layers"].items()}}
    biased_regret = choices_under_bias(model, reference, cfg, params, tokens,
                                       mesh=mesh)

    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": ends[-1] - t_open,
        "steps": len(ends), "step_ends": [e - t_open for e in ends],
        "untraced_steps": len(clean), "untraced_s": sum(clean),
        "losses": loss_values,
        "gaps": gaps, "router_bias_gap": bias_gap,
        "biased_choice_regret": biased_regret,
        "compiles": compiles, "mosaic_calls": mosaic_calls,
        "state_bytes": state_bytes,
        "step_memory_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs_not_aliased": (mem.output_size_in_bytes
                                    - mem.alias_size_in_bytes)},
        "traced_steps": (traced["off"] - traced["on"]
                         if traced["on"] is not None else 0),
        "expert_load_max_over_mean": float(load.mean()),
        "expert_load_first_last": [float(load[0].mean()),
                                   float(load[-1].mean())],
        "expert_load_max_over_mean_worst": float(load.max()),
        # the program's own counters of the last step (rtpu_train_*)
        "moe_rows_routed": int(counts[-1].sum()),
        "moe_rows_held": int(held[-1]),
        "moe_rows_held_mean": float(held.mean()),
        "moe_rows_held_traced_mean": float(held[in_trace].mean()),
        "moe_rows_held_by_step": [int(x) for x in held],
        "moe_expert_load_max_over_mean": float(load[-1].mean()),
        "moe_router_bias_abs_max": bias_max,
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
    terms = gaps["ref_terms"]
    rl, ch, tg = (gaps["router_logit_gap"], gaps["choices"],
                  gaps["token_nll_gap"])
    checks = checks_of(traffic["check"], losses[0], gaps,
                       rep["router_bias_gap"], rep["biased_choice_regret"])
    worst = {kind: max(leaves, key=leaves.get)
             for kind, leaves in gaps["gradient_gap"].items()}
    print(f"[bench] first-step loss {losses[0]:.5f}; reference on the "
          f"program's choices {terms['loss']:.5f}; losses finite: {finite}; "
          f"last loss {losses[-1]:.4f}; mosaic calls {rep['mosaic_calls']}; "
          f"state {rep['state_bytes'] / 1e9:.2f} GB; step memory "
          f"{rep['step_memory_bytes']}; peak bytes in use "
          f"{rep['memory_peak_bytes']}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, the worst leaf of each "
          f"kind of layer: {worst}; every leaf: {gaps['gradient_gap']}",
          flush=True)
    print(f"[bench] compared: {ch['count']} choices, {tg['positions']} "
          f"positions; the reference's router logits spread "
          f"{rl['ref_std']:.3f}, its per-token loss {tg['ref_std']:.3f} "
          f"(p99 gap {tg['p99']:.4f}); with the first step's biases (0) the "
          f"program's choices lie {ch['own_regret']:.2e} at most below its "
          f"own selection scores' k-th; largest expert load over the mean "
          f"{rep['expert_load_max_over_mean']:.4f} (mean over layers and "
          f"steps), {rep['expert_load_max_over_mean_worst']:.4f} at worst; "
          f"largest |router bias| after the last step "
          f"{rep['moe_router_bias_abs_max']:.4f}", flush=True)
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
          f"{[(j, round(d, 4)) for d, j in took[:-4:-1]]}; expert load over "
          f"the mean at the first and the last step "
          f"{rep['expert_load_first_last']}; of {rep['moe_rows_routed']} "
          f"routed rows a step the held experts multiplied "
          f"{rep['moe_rows_held_mean']:.0f} (mean; {rep['moe_rows_held']} in "
          f"the last step; by step {rep['moe_rows_held_by_step']})",
          flush=True)

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
                          "expert_load_max_over_mean":
                              rep["expert_load_max_over_mean"],
                          "moe_rows_routed": rep["moe_rows_routed"],
                          "moe_rows_held": rep["moe_rows_held_mean"],
                          "moe_rows_held_traced":
                              rep["moe_rows_held_traced_mean"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
