"""Runs one training cell of a model whose layers are of unequal kinds
(Laguna: window and full attention, a dense layer and routed ones with a
share of the experts held): ``cells/train_moe.py``'s window, tracing,
compile count and report, with the model module named by the
configuration (``model_config["module"]``: ``ray_tpu.models.<module>``
with ``<Module>Config``, ``init_params``, ``forward``, ``loss_terms``,
``param_shardings`` and, where a share of the experts is held,
``rows_held``) and its reference by the same name
(``benchmark.references.<module>_ref``), so that a later ``benchmark``
issue can fold ``train.py`` and ``train_moe.py`` into it.

The comparison is ``train_moe.py``'s, after the window, on the weights
the first step saw and batch 0, of what the timed path's own ``forward``
gives at the timed sizes (``traffic.check`` holds each tolerance with its
reason): the reference is forced to the program's choices of experts, so
that at every layer it has seen what the program saw. Then
(a) the router logits of every routed layer are compared directly;
(b) every expert the program chose and the reference would not have must
    be a near-tie in the reference's own logits: its regret and the share
    of such choices are bounded;
(c) the per-position next-token loss, and the first step's loss with its
    router term, are compared;
(d) the gradient of a seeded scalar (the per-position losses under
    seeded weights) is taken through the same ``forward`` and the
    reference on the same choices, and compared leaf by leaf in the first
    layer of each kind, the embedding, the last norm and the head
    (``<module>_ref.first_layers``), each leaf by the norm of its gap over
    the norm of the reference's; the worst leaf of each kind is bounded.
    This is what sees the backward passes the timed step runs (the
    kernels' at the timed sizes, a held share's written-out transpose).

The step also returns the routed layers' expert counts; the rows the
held experts multiplied (``moe_rows_held``) are read from them.
"""

from __future__ import annotations

import os
import shutil
import time
from functools import lru_cache
from typing import Any, Dict

from benchmark.cells.train import _report_ended
from benchmark.lib import procs, spec


@lru_cache(maxsize=None)
def _gradient_gaps():
    """Jitted: two gradients like the reference's ``first_layers(params)``
    -> {kind of layer, or ``top`` for embedding, last norm and head:
    {leaf: the norm of the gap over the norm of the second's}}."""
    import jax
    import jax.numpy as jnp

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sqrt(jnp.square(a - b).sum() / jnp.square(b).sum())

    def gaps(got, want):
        out = {kind: {name: rel(g, want["layers"][kind][name])
                      for name, g in leaves.items()}
               for kind, leaves in got["layers"].items()}
        out["top"] = {name: rel(g, want[name])
                      for name, g in got.items() if name != "layers"}
        return out

    return jax.jit(gaps)


@lru_cache(maxsize=None)
def _program(model, reference, pcfg, top_k: int, mesh):
    """The program's side of ``compare``, jitted once a configuration
    (``mixed_limits.py`` asks at several seeds)."""
    import jax
    import jax.numpy as jnp

    def program(p, tokens, weights):
        def weighted(p):
            lg, router = model.forward(pcfg, p, tokens[:, :-1], mesh=mesh,
                                       keep_router_logits=True)
            nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, tokens[:, 1:, None], -1)[..., 0]
            return (weights * nll).sum(), (nll, router["logits"])

        (_, (nll, logits)), grads = jax.value_and_grad(
            weighted, has_aux=True)(p)
        chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)[1]
        return nll, logits, chosen, reference.first_layers(grads)

    return jax.jit(program)


def compare(model, reference, cfg, params, tokens, host_tokens, mesh=None,
            program_cfg=None, reference_params=None, seed: int = 0
            ) -> Dict[str, Any]:
    """The gaps between the program's ``forward`` (on ``tokens``, the
    device's copy of ``host_tokens``) and the reference forced to the
    program's choices of experts, and between their gradients of
    ``sum(weights * per-position loss)``, the weights drawn from ``seed``.
    ``program_cfg`` and ``reference_params`` are for
    ``benchmark/tests/mixed_limits.py``, which shows that each tolerance
    refuses a program with a part left out and a reference in a lower
    precision."""
    import numpy as np

    pcfg = program_cfg or cfg
    weights = (np.random.default_rng(seed + 1).uniform(
        0.5, 1.5, host_tokens[:, 1:].shape) / host_tokens[:, 1:].size
               ).astype(np.float32)

    got_nll, got_logits, got_chosen, got_grads = _program(
        model, reference, pcfg, cfg.top_k, mesh)(params, tokens, weights)
    got_nll, got_logits, got_chosen = (
        np.asarray(x) for x in (got_nll, got_logits, got_chosen))
    # the reference on the program's choices: at every layer it has seen
    # what the program saw, so its own router logits there say what the
    # program's should be (a) and how near a tie each differing choice
    # was (b): how far below its k-th largest logit it holds that expert
    ref = reference.token_nll(
        cfg, params if reference_params is None else reference_params,
        host_tokens, forced_topk=got_chosen, grad_weights=weights)
    by_kind = {kind: {name: float(v) for name, v in leaves.items()}
               for kind, leaves in _gradient_gaps()(
                   got_grads, ref.pop("grads")).items()}
    del got_grads
    ref_lg = ref["router_logits"]
    kth = np.sort(ref_lg, axis=-1)[..., -cfg.top_k]
    regret = kth[..., None] - np.take_along_axis(ref_lg, got_chosen, -1)
    differs = regret > 0
    logit_gap = np.abs(got_logits - ref_lg)
    nll_gap = np.abs(got_nll - ref["nll"])
    return {
        "ref_terms": ref["terms"],
        "router_logit_gap": {"rms": float(np.sqrt(np.mean(logit_gap ** 2))),
                             "max": float(logit_gap.max()),
                             "ref_std": float(ref_lg.std())},
        "choices": {"differing_share": float(differs.mean()),
                    "max_regret": float(regret.max()),
                    "count": int(differs.size)},
        "token_nll_gap": {"rms": float(np.sqrt(np.mean(nll_gap ** 2))),
                          "max": float(nll_gap.max()),
                          "p99": float(np.quantile(nll_gap, 0.99)),
                          "positions": int(nll_gap.size),
                          "ref_std": float(ref["nll"].std())},
        "gradient_gap": by_kind}


def _train_loop(config: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.lib import compile_counter
    from importlib import import_module

    from ray_tpu import train
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import batch_sharding

    t_loop_wall = time.time()     # the backend has opened the chips by now
    compile_counter.install()
    tr = config["traffic"]
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["model_config"].items()}
    name, preset = kw.pop("module"), kw.pop("preset")
    model = import_module("ray_tpu.models." + name)
    reference = import_module(f"benchmark.references.{name}_ref")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = getattr(getattr(model, name.capitalize() + "Config"), preset)(
        **kw, attn_impl="auto")
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
    lr = tr["lr"]
    if tr.get("lr_warmup_steps"):     # the start of a run: a linear ramp
        lr = optax.linear_schedule(0.0, lr, tr["lr_warmup_steps"])
    tx = optax.adamw(lr)
    opt = tx.init(params)
    B, S = tr["batch"], tr["seq"]
    host = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (tr["host_batches"], B, S + 1), np.int32)

    def put(i: int):
        return {"tokens": jax.device_put(host[i % len(host)], bsh)}

    def step(params, opt, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: model.loss_terms(cfg, p, batch, mesh=mesh),
            has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt, loss,
                aux["expert_counts"])

    batch = put(0)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    losses = []
    for i in range(tr["warmup_steps"]):          # step 0 runs batch 0
        params, opt, loss, _ = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))

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
            params, opt, loss, cnt = compiled(params, opt, batch)
        with ann("bench.wait"):
            loss.block_until_ready()
        ends.append(time.monotonic())
        # to the host at once: a step's small outputs left on the device
        # for the whole window lie scattered in its memory, and a program
        # that needs 14.75 of its 16.9 GB in two blocks stalled for 2-4 s
        # in one step of every tenth run (PERF.md 6, PR 30)
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
    del params, opt, batch, loss, losses, cnt
    params = init(key)
    gaps = compare(model, reference, cfg, params, put(0)["tokens"], host[0],
                   mesh=mesh, seed=seed)

    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": ends[-1] - t_open,
        "steps": len(ends), "step_ends": [e - t_open for e in ends],
        "untraced_steps": len(clean), "untraced_s": sum(clean),
        "losses": loss_values,
        **gaps,
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
    chk = traffic["check"]
    terms = rep["ref_terms"]
    gap = abs(losses[0] - terms["loss"])
    rl, ch, tg = rep["router_logit_gap"], rep["choices"], rep["token_nll_gap"]
    checks = {
        "first-step loss": (gap, chk["loss_tolerance"]),
        "router logits, rms": (rl["rms"], chk["router_logit_rms_tolerance"]),
        "router logits, max": (rl["max"], chk["router_logit_max_tolerance"]),
        "differing choices, share": (ch["differing_share"],
                                     chk["differing_choice_share_tolerance"]),
        "differing choices, regret": (ch["max_regret"],
                                      chk["choice_regret_tolerance"]),
        "per-token loss, rms": (tg["rms"], chk["token_nll_rms_tolerance"]),
        "per-token loss, max": (tg["max"], chk["token_nll_max_tolerance"]),
    }
    worst = {kind: max(leaves.items(), key=lambda kv: kv[1])
             for kind, leaves in rep["gradient_gap"].items()}
    for kind, (_, value) in worst.items():
        checks[f"gradient, {kind}"] = (
            value, chk["gradient_gap_tolerance"][kind])
    print(f"[bench] first-step loss {losses[0]:.5f}; reference on the "
          f"program's choices {terms['loss']:.5f} (cross entropy "
          f"{terms['cross_entropy']:.5f}, load balance "
          f"{terms['load_balance']:.5f}); "
          f"losses finite: {finite}; last loss {losses[-1]:.4f}; mosaic calls "
          f"{rep['mosaic_calls']}; state {rep['state_bytes'] / 1e9:.2f} GB; "
          f"step memory {rep['step_memory_bytes']}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, the worst leaf of each "
          f"kind of layer: {({k: v[0] for k, v in worst.items()})}; every "
          f"leaf: {rep['gradient_gap']}", flush=True)
    print(f"[bench] compared: {ch['count']} choices, {tg['positions']} "
          f"positions; the reference's router logits spread "
          f"{rl['ref_std']:.3f}, its per-token loss {tg['ref_std']:.3f} "
          f"(p99 gap {tg['p99']:.4f}); largest expert load over the mean "
          f"{rep['expert_load_max_over_mean']:.4f} (mean over layers and "
          f"steps), {rep['expert_load_max_over_mean_worst']:.4f} at worst",
          flush=True)
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
