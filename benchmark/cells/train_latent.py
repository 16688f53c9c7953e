"""Runs one training cell of a model with latent attention and a
group-limited router over a held share of the experts (DeepSeek-V2):
``cells/train_mixed.py``'s window, tracing, compile count and report, with
a comparison of its own. ``train_mixed.py`` cannot run this model unedited:
its ``compare`` takes the program's choices as the plain top-k of its
router logits, which a group limit does not give, and its check has no
first step. ``load_model`` and ``optimizer`` are ``train_hybrid.py``'s,
``first_step_left`` and the first step's gaps ``train_scan.py``'s, the
gradient gaps ``train_mixed.py``'s, by import.

The comparison that decides ``correct``, after the window, on the weights
the first step saw and batch 0, of what the timed path's own ``forward``
gives at the timed sizes (the ``flash_kv_*`` kernels, the held rows'
passes), against ``references/<module>_ref.py`` (float32, highest
precision, the same held heads, experts and rows) forced to the program's
own choices of experts (``route``'s, which the layers report):
(a) the first step's loss with its router term;
(b) the per-position next-token loss, root mean square and largest gap;
(c) the router logits of every routed layer;
(d) every choice the reference would not have made must be a near-tie in
    the reference's own logits: ``choice_regret`` bounds how far, and the
    share of such choices is bounded;
(e) the gradient of a seeded weighted loss for every leaf of layer 0 and
    layer 1 (the first of each kind), the embedding, the last norm and the
    head;
(f) adamw's first moment and the parameters after the timed program's own
    first step, against optax's adamw in float32 on the reference's
    gradient.
"""

from __future__ import annotations

import os
import shutil
import time
from functools import lru_cache
from typing import Any, Dict, Tuple

from benchmark.cells.train import _report_ended
from benchmark.cells.train_hybrid import load_model, optimizer
from benchmark.cells.train_mixed import _gradient_gaps
from benchmark.cells.train_scan import _first_step_gaps, first_step_left
from benchmark.lib import procs, spec


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    the routed layers' expert counts [Lr, E])."""
    import jax
    import optax

    def step(params, opt, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: model.loss_terms(cfg, p, batch, mesh=mesh),
            has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt, loss,
                aux["expert_counts"])

    return step


@lru_cache(maxsize=None)
def _program(model, reference, pcfg, mesh):
    """The program's side of ``compare``, jitted once a configuration."""
    import jax
    import jax.numpy as jnp

    def program(p, tokens, weights):
        def weighted(p):
            lg, router = model.forward(pcfg, p, tokens[:, :-1], mesh=mesh,
                                       keep_router_logits=True)
            nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, tokens[:, 1:, None], -1)[..., 0]
            return (weights * nll).sum(), (nll, router["logits"],
                                           router["chosen"])

        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(p)
        return out + (reference.first_layers(grads),)

    return jax.jit(program)


def choice_regret(ref_logits, got_logits, chosen, n_group: int, keep: int,
                  top_k: int):
    """How far from the reference's own choice each of the program's lies,
    in the reference's logits [Lr, n, E] (numpy): the larger of
    - the group's: the reference's ``keep``-th best group score minus the
      score of the choice's group (0 inside the groups the reference keeps);
    - the expert's: among the experts of the groups the program kept (the
      ``keep`` best by the program's own logits), the reference's
      ``top_k``-th largest logit minus its logit of the choice.
    Both are 0 for a choice the reference makes too and small at a near-tie;
    a choice outside the program's own groups (no group limit) has the
    group's alone. -> regret [Lr, n, K], never negative."""
    import numpy as np

    def by_group(lg):
        return lg.reshape(lg.shape[:-1] + (n_group, -1))

    per = ref_logits.shape[-1] // n_group
    ref_best = by_group(ref_logits).max(-1)                  # [Lr, n, G]
    kth_group = np.sort(ref_best, -1)[..., -keep]
    group_of = chosen // per
    group_regret = kth_group[..., None] - np.take_along_axis(
        ref_best, group_of, -1)
    got_best = by_group(got_logits).max(-1)
    kept = got_best >= np.sort(got_best, -1)[..., -keep, None]
    inside = np.where(np.repeat(kept, per, -1), ref_logits, -np.inf)
    kth = np.sort(inside, -1)[..., -top_k]
    own = np.take_along_axis(kept, group_of, -1)
    expert_regret = np.where(
        own, kth[..., None] - np.take_along_axis(ref_logits, chosen, -1), 0)
    return np.maximum(np.maximum(group_regret, expert_regret), 0.0)


def compare(model, reference, cfg, params, tokens, host_tokens, mesh=None,
            program=None, reference_params=None, seed: int = 0,
            first_step=None) -> Dict[str, Any]:
    """The gaps between the program's ``forward`` (on ``tokens``, the
    device's copy of ``host_tokens``) and the reference forced to the
    program's choices of experts, and between their gradients of
    ``sum(weights * per-position loss)``, the weights drawn from ``seed``.
    ``first_step``: (the optimizer, what ``first_step_left`` gave of a step
    on these weights and tokens) adds the gaps of what that step handed on.
    ``program`` ((model, config) with a fault planted) and
    ``reference_params`` are for ``benchmark/tests/latent_limits.py``."""
    import numpy as np

    pmodel, pcfg = program or (model, cfg)
    weights = (np.random.default_rng(seed + 1).uniform(
        0.5, 1.5, host_tokens[:, 1:].shape) / host_tokens[:, 1:].size
               ).astype(np.float32)

    def floats(by_kind):
        return {kind: {name: float(v) for name, v in leaves.items()}
                for kind, leaves in by_kind.items()}

    got_nll, got_logits, got_chosen, got_grads = _program(
        pmodel, reference, pcfg, mesh)(params, tokens, weights)
    got_nll, got_logits, got_chosen = (
        np.asarray(x) for x in (got_nll, got_logits, got_chosen))
    ref_params = params if reference_params is None else reference_params
    ref = reference.token_nll(cfg, ref_params, host_tokens,
                              forced_topk=got_chosen, grad_weights=weights)
    by_kind = floats(_gradient_gaps()(got_grads, ref.pop("grads")))
    del got_grads
    stepped = {}
    if first_step is not None:
        tx, left = first_step
        mean = reference.token_nll(
            cfg, ref_params, host_tokens, forced_topk=got_chosen,
            grad_weights=np.full_like(weights, 1.0 / weights.size),
            router_term=True)
        moment, moved = _first_step_gaps(tx)(
            left, reference.first_layers(ref_params), mean.pop("grads"))
        stepped = {"first_step": {"moment_gap": floats(moment),
                                  "param_gap": float(moved)}}
    ref_lg = ref["router_logits"]
    regret = choice_regret(ref_lg, got_logits, got_chosen, cfg.n_group,
                           cfg.topk_group, cfg.top_k)
    per = cfg.num_experts // cfg.n_group
    spans = np.sort(got_chosen // per, -1)
    spans = 1 + (spans[..., 1:] != spans[..., :-1]).sum(-1)
    logit_gap = np.abs(got_logits - ref_lg)
    nll_gap = np.abs(got_nll - ref["nll"])
    return {
        **stepped,
        "ref_terms": ref["terms"],
        "program_cross_entropy": float(got_nll.mean()),
        "router_logit_gap": {"rms": float(np.sqrt(np.mean(logit_gap ** 2))),
                             "max": float(logit_gap.max()),
                             "ref_std": float(ref_lg.std())},
        "choices": {"differing_share": float((regret > 0).mean()),
                    "max_regret": float(regret.max()),
                    "count": int(regret.size),
                    "groups_spanned_max": int(spans.max()),
                    "groups_spanned_mean": float(spans.mean())},
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
        "per-token loss, rms": (tg["rms"], chk["token_nll_rms_tolerance"]),
        "per-token loss, max": (tg["max"], chk["token_nll_max_tolerance"]),
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
    opt = tx.init(params)
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
        params, opt, loss, _ = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends, counts = [], []     # counts: [Lr, E] of each step
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
    counts = np.stack(counts)                               # [steps, Lr, E]
    load = counts.max(-1) / counts.mean(-1)                 # [steps, Lr]
    held = np.asarray([int(model.rows_held(cfg, c)) for c in counts])
    passed = int(model.rows_passed(cfg, counts[-1]))
    in_trace = (slice(traced["on"], traced["off"])
                if traced["on"] is not None else slice(None))
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    # ---- correctness, after the window (the module's docstring)
    del params, opt, batch, loss, losses, cnt
    # (f): the timed executable once more on what its first call was given
    # (the seeded weights, a new optimizer state, batch 0), here and not in
    # the warm-up: copies taken there would cost every step of the window
    params = init(key)
    after, opt, *_ = compiled(params, tx.init(params), put(0))
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
        "expert_load_max_over_mean": float(load.mean()),
        "expert_load_first_last": [float(load[0].mean()),
                                   float(load[-1].mean())],
        "expert_load_max_over_mean_worst": float(load.max()),
        # the program's own counters of the last step (rtpu_train_*)
        "moe_rows_routed": int(counts[-1].sum()),
        "moe_rows_held": int(held[-1]),
        "moe_rows_passed": passed,
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
    gaps = rep["gaps"]
    terms, rl, ch, tg = (gaps["ref_terms"], gaps["router_logit_gap"],
                         gaps["choices"], gaps["token_nll_gap"])
    checks = checks_of(traffic["check"], losses[0], gaps)
    print(f"[bench] first-step loss {losses[0]:.5f}; reference on the "
          f"program's choices {terms['loss']:.5f} (cross entropy "
          f"{terms['cross_entropy']:.5f}, sequence balance "
          f"{terms['load_balance']:.5f}); "
          f"losses finite: {finite}; last loss {losses[-1]:.4f}; mosaic calls "
          f"{rep['mosaic_calls']}; state {rep['state_bytes'] / 1e9:.2f} GB; "
          f"step memory {rep['step_memory_bytes']}; peak bytes in use "
          f"{rep['memory_peak_bytes']}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, every leaf: "
          f"{gaps['gradient_gap']}", flush=True)
    print("[bench] what the first step handed on against the reference's "
          f"adamw step, every leaf: {gaps['first_step']}", flush=True)
    print(f"[bench] compared: {ch['count']} choices spanning "
          f"{ch['groups_spanned_mean']:.3f} groups a token (at most "
          f"{ch['groups_spanned_max']}), {tg['positions']} positions; the "
          f"reference's router logits spread {rl['ref_std']:.3f}, its "
          f"per-token loss {tg['ref_std']:.3f} (p99 gap {tg['p99']:.4f}); "
          f"largest expert load over the mean "
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
          f"the last step, in passes of {rep['moe_rows_passed']} rows; by "
          f"step {rep['moe_rows_held_by_step']})", flush=True)

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
