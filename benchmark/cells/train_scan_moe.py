"""Runs one training cell of a model whose layers are one part each
(Mamba-2 scans at grouped heads, mixtures of two-matrix experts in a latent
with a held share, an attention layer) with a multi-token prediction module
beside the head and routers balanced by a bias no optimizer owns
(``nemotron_h``): ``cells/train_delta_moe.py``'s window, tracing, compile
count and report; ``train_hybrid.py``'s step (adamw on
``model.trainable(params)``, then ``model.update_router_bias``) with the
scans' counter, the held rows and both cross entropies beside the counts; a
row of ``seq + 2`` ids (the traffic's ``ids_ahead``), so that each of the
``seq`` positions has the module's target too.

The comparison that decides ``correct``, after the window, on the weights
the first step saw and batch 0, of what the timed path's own forward gives
at the timed sizes (``model.token_nlls``: the scan's kernels at 8 groups, the
taps' kernels, causal flash without rotation, the held rows' passes in the
latent, both passes of the head in blocks), against
``references/<module>_ref.py`` forced to the program's choices of experts:
(a) the first step's loss, and its two cross entropies each;
(b) the per-position loss of both heads, root mean square and largest gap;
(c) the router logits of the stack's mixtures and the module's, and every
    expert the program chose that the reference would not have: its regret
    in the reference's selection scores and the share of such choices;
(d) the scan layers' states after the last position (``train_scan.py``'s);
(e) the gradient of a seeded weighted loss of both heads for every leaf of
    the first layer of each kind, of the module's layers, its norms and
    joining matrix, the embedding, the last norm and the head;
(f) adamw's first moment and the parameters after the timed program's own
    first step, against optax's adamw in float32 on the reference's
    gradient of the step's loss;
(g) the routers' biases after the first step against the reference's rule
    on the program's own counts, and ``route``'s choices under the biases
    the window ended with (``train_hybrid.own_regret``).
"""

from __future__ import annotations

import os
import shutil
import time
from functools import lru_cache
from typing import Any, Dict, Tuple

from benchmark.cells import train_scan
from benchmark.cells.train import _report_ended
from benchmark.cells.train_hybrid import (load_model, model_parts,
                                          optimizer, own_regret)
from benchmark.cells.train_mixed import _gradient_gaps
from benchmark.cells.train_scan import _first_step_gaps, first_step_left
from benchmark.lib import procs, spec


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    one ``aux``: the mixtures' expert counts [Lr, E] (the module's last),
    the rows the held experts multiplied, the largest ``|S|`` of a scan's
    last state, the largest ``|b|`` of a router, both cross entropies)."""
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
        counts = aux["expert_counts"]
        params = model.update_router_bias(cfg, params, counts)
        return params, opt, loss, {
            "expert_counts": counts,
            "moe_rows_held": model.rows_held(cfg, counts),
            "ssm_state_abs_max": aux["ssm_state_abs_max"],
            "moe_router_bias_abs_max": model.router_bias_abs_max(params),
            "cross_entropy": aux["cross_entropy"],
            "mtp_cross_entropy": aux["mtp_cross_entropy"]}

    return step


@lru_cache(maxsize=None)
def _program(model, reference, pcfg, mesh):
    """The program's side of ``compare``, jitted once a configuration."""
    import jax

    trainable, with_trainable = model_parts(model)

    def program(p, tokens, weights):
        def weighted(t):
            nll, more, said = model.token_nlls(
                pcfg, with_trainable(p, t), tokens, mesh=mesh,
                keep_router_logits=True)
            return ((weights[:, 0] * nll).sum()
                    + (weights[:, 1] * more).sum(),
                    (nll, more, said["ssm_state"], said["router"]["logits"],
                     said["router"]["chosen"]))

        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            trainable(p))
        return out + (reference.first_layers(grads),)

    return jax.jit(program)


def with_biases(params, biases):
    """``params`` with the routers' biases ``[Lr, E]`` (the stack's
    mixtures in their order, then the module's) in place of their own."""
    import jax.numpy as jnp

    own = params["layers"]["moe"]["router_bias"]
    n = own.shape[0]
    out = {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"],
        "router_bias": jnp.asarray(biases[:n], own.dtype)}}}
    if "mtp" in params:
        m = params["mtp"]
        out["mtp"] = {**m, "layers": {**m["layers"], "moe": {
            **m["layers"]["moe"],
            "router_bias": jnp.asarray(biases[n:], own.dtype)}}}
    return out


def seeded_weights(host_tokens, seed: int):
    """The weights of the seeded scalar ``sum(w * per-position loss)`` of
    both heads [B, 2, S], uniform in 0.5-1.5 over the positions' count."""
    import numpy as np

    B, S = host_tokens.shape[0], host_tokens.shape[1] - 2
    return (np.random.default_rng(seed + 1).uniform(0.5, 1.5, (B, 2, S))
            / (B * S)).astype(np.float32)


def compare(model, reference, cfg, params, tokens, host_tokens, mesh=None,
            reference_params=None, program_cfg=None,
            reference_mantissa_bits=None, seed: int = 0, first_step=None
            ) -> Dict[str, Any]:
    """The gaps between the program's own forward (on ``tokens``, the
    device's copy of ``host_tokens [B, S + 2]``) and the reference forced to
    the program's choices of experts, and between their gradients of the
    seeded scalar of both heads. ``first_step``: (the optimizer, what
    ``first_step_left`` gave of a step on these weights and tokens) adds the
    gaps of what that step handed on. ``reference_params``, ``program_cfg``
    and ``reference_mantissa_bits`` are for
    ``benchmark/tests/scan_moe_limits.py``."""
    import numpy as np

    pcfg = program_cfg or cfg
    weights = seeded_weights(host_tokens, seed)

    def floats(by_kind):
        return {kind: {name: float(v) for name, v in leaves.items()}
                for kind, leaves in by_kind.items()}

    *got, got_grads = _program(model, reference, pcfg, mesh)(
        params, tokens, weights)
    nll, more, states, logits, chosen = (np.asarray(x) for x in got)
    ref_params = params if reference_params is None else reference_params
    ref = reference.token_nll(cfg, ref_params, host_tokens,
                              forced_topk=chosen, grad_weights=weights,
                              mantissa_bits=reference_mantissa_bits)
    by_kind = floats(_gradient_gaps()(got_grads, ref.pop("grads")))
    del got_grads
    stepped = {}
    if first_step is not None:
        tx, left = first_step
        mean = np.full_like(weights, 1.0 / weights[:, 0].size)
        mean[:, 1] *= cfg.mtp_loss_scale
        step_ref = reference.token_nll(
            cfg, ref_params, host_tokens, forced_topk=chosen,
            grad_weights=mean, mantissa_bits=reference_mantissa_bits)
        moment, moved = _first_step_gaps(tx)(
            left, reference.first_layers(ref_params), step_ref.pop("grads"))
        stepped = {"first_step": {"moment_gap": floats(moment),
                                  "param_gap": float(moved)}}
    select = ref["select_scores"]
    kth = np.sort(select, axis=-1)[..., -cfg.top_k]
    regret = kth[..., None] - np.take_along_axis(select, chosen, -1)
    differs = regret > 0
    logit_gap = np.abs(logits - ref["router_logits"])

    def gap_of(a, b):
        g = np.abs(a - b)
        return {"rms": float(np.sqrt(np.mean(g ** 2))),
                "max": float(g.max()), "p99": float(np.quantile(g, 0.99)),
                "positions": int(g.size), "ref_std": float(b.std())}

    ref_states = ref["last_states"]
    head_gap = (np.sqrt(np.square(states - ref_states).sum((-2, -1)))
                / np.sqrt(np.square(ref_states).sum((-2, -1))))
    return {
        **stepped,
        "ref_terms": ref["terms"],
        "program_terms": {"cross_entropy": float(nll.mean()),
                          "mtp_cross_entropy": float(more.mean())},
        "state_abs_max": {"program": float(np.abs(states).max()),
                          "reference": ref["state_abs_max"]},
        "state_head_gap": {
            "worst": float(head_gap.max()),
            "median": float(np.median(head_gap)),
            "layer_row_head": [int(i) for i in np.unravel_index(
                head_gap.argmax(), head_gap.shape)]},
        "router_logit_gap": {"rms": float(np.sqrt(np.mean(logit_gap ** 2))),
                             "max": float(logit_gap.max()),
                             "ref_std": float(ref["router_logits"].std())},
        "choices": {"differing_share": float(differs.mean()),
                    "max_regret": float(max(regret.max(), 0.0)),
                    "own_regret": own_regret(reference, cfg, params, logits,
                                             chosen),
                    "count": int(differs.size)},
        "token_nll_gap": gap_of(nll, ref["nll"]),
        "mtp_nll_gap": gap_of(more, ref["mtp_nll"]),
        "gradient_gap": by_kind}


def choices_under_bias(model, reference, cfg, params, tokens, mesh=None
                       ) -> float:
    """(g): ``train_hybrid.own_regret`` of the timed path's forward on
    ``params``, whose biases are not 0."""
    import numpy as np

    *_, logits, chosen, _ = _program(model, reference, cfg, mesh)(
        params, tokens, np.zeros((tokens.shape[0], 2, tokens.shape[1] - 2),
                                 np.float32))
    return own_regret(reference, cfg, params, np.asarray(logits),
                      np.asarray(chosen))


def checks_of(chk: Dict[str, Any], first_terms: Dict[str, float],
              gaps: Dict[str, Any], bias_gap=None, biased_regret=None
              ) -> Dict[str, Tuple[float, float]]:
    """what -> (reading, tolerance): the comparison that decides
    ``correct``: ``train_scan.checks_of``'s (the loss, the per-position
    loss, the states, the gradients, the first step), the module's loss and
    per-position loss, and the routers'. ``first_terms``: the first step's
    ``loss``, ``cross_entropy`` and ``mtp_cross_entropy``."""
    rl, ch, mg = gaps["router_logit_gap"], gaps["choices"], gaps["mtp_nll_gap"]
    checks = train_scan.checks_of(chk, first_terms["loss"], gaps)
    checks.update({
        "first-step cross entropy, module": (
            abs(first_terms["mtp_cross_entropy"]
                - gaps["ref_terms"]["mtp_cross_entropy"]),
            chk["mtp_loss_tolerance"]),
        "per-token loss of the module, rms": (
            mg["rms"], chk["mtp_nll_rms_tolerance"]),
        "per-token loss of the module, max": (
            mg["max"], chk["mtp_nll_max_tolerance"]),
        "router logits, rms": (rl["rms"], chk["router_logit_rms_tolerance"]),
        "router logits, max": (rl["max"], chk["router_logit_max_tolerance"]),
        "differing choices, share": (ch["differing_share"],
                                     chk["differing_choice_share_tolerance"]),
        "differing choices, regret": (ch["max_regret"],
                                      chk["choice_regret_tolerance"])})
    if biased_regret is not None:
        checks["choices under the routers' biases, regret"] = (
            biased_regret, chk["biased_choice_regret_tolerance"])
    if bias_gap is not None:
        checks["router bias after the first step"] = (
            bias_gap, chk["router_bias_tolerance"])
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
        0, cfg.vocab_size, (tr["host_batches"], B, S + tr["ids_ahead"]),
        np.int32)

    def put(i: int):
        return {"tokens": jax.device_put(host[i % len(host)], bsh)}

    batch = put(0)
    compiled = jax.jit(make_step(model, cfg, tx, mesh),
                       donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    losses, first_terms, bias_gap = [], {}, 0.0
    # (g): the biases the first step starts from, before it donates them
    bias0 = reference.router_biases(cfg, params)
    for i in range(tr["warmup_steps"]):          # step 0 runs batch 0
        params, opt, loss, aux = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))
        if i == 0:                  # the rule on the program's own counts
            first_terms = {"loss": float(loss),
                           "cross_entropy": float(aux["cross_entropy"]),
                           "mtp_cross_entropy": float(
                               aux["mtp_cross_entropy"])}
            want = reference.updated_bias(
                cfg, bias0, np.asarray(aux["expert_counts"]))
            bias_gap = float(np.abs(
                reference.router_biases(cfg, params) - want).max())

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends, counts, held, state_maxes, bias_maxes, module_ces = (
        [], [], [], [], [], [])
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
            params, opt, loss, aux = compiled(params, opt, batch)
        with ann("bench.wait"):
            loss.block_until_ready()
        ends.append(time.monotonic())
        # to the host at once (train_mixed.py says why)
        losses.append(float(loss))
        counts.append(np.asarray(aux["expert_counts"]))
        held.append(int(aux["moe_rows_held"]))
        state_maxes.append(float(aux["ssm_state_abs_max"]))
        bias_maxes.append(float(aux["moe_router_bias_abs_max"]))
        module_ces.append(float(aux["mtp_cross_entropy"]))
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
    held = np.asarray(held)
    # the module's mixture is the last that reports
    first_held, count_held = cfg.experts_held or (0, cfg.num_experts)
    held_module = counts[:, -1, first_held:first_held + count_held].sum(-1)
    passed = int(model.rows_passed(cfg, counts[-1]))
    passes_most = int(max(model.rows_passed(cfg, c) for c in counts))
    in_trace = (slice(traced["on"], traced["off"])
                if traced["on"] is not None else slice(None))
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))
    bias_end = reference.router_biases(cfg, params)

    # ---- correctness, after the window (the module's docstring)
    del params, opt, batch, loss, losses, aux
    # (f): the timed executable once more on what its first call was given
    params = init(key)
    after, opt, *_ = compiled(params, tx.init(model_parts(model)[0](params)),
                              put(0))
    left = first_step_left(reference, after, opt)
    del after, opt
    params = init(key)
    tokens0 = put(0)["tokens"]
    gaps = compare(model, reference, cfg, params, tokens0, host[0],
                   mesh=mesh, seed=seed, first_step=(tx, left))
    biased_regret = choices_under_bias(
        model, reference, cfg, with_biases(params, bias_end), tokens0, mesh)

    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": ends[-1] - t_open,
        "steps": len(ends), "step_ends": [e - t_open for e in ends],
        "untraced_steps": len(clean), "untraced_s": sum(clean),
        "losses": loss_values, "first_terms": first_terms, "gaps": gaps,
        "bias_gap": bias_gap, "biased_regret": biased_regret,
        "compiles": compiles, "mosaic_calls": mosaic_calls,
        "state_bytes": state_bytes,
        "step_memory_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs_not_aliased": (mem.output_size_in_bytes
                                    - mem.alias_size_in_bytes),
            "peak": getattr(mem, "peak_memory_in_bytes", None)},
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
        "moe_rows_passed_most": passes_most,
        "moe_rows_held_mean": float(held.mean()),
        "moe_rows_held_traced_mean": float(held[in_trace].mean()),
        "moe_rows_held_by_step": [int(x) for x in held],
        "moe_rows_held_module_traced_mean": float(
            held_module[in_trace].mean()),
        "ssm_state_abs_max": state_maxes[-1],
        "ssm_state_abs_max_first_most": [state_maxes[0], max(state_maxes)],
        "moe_router_bias_abs_max": bias_maxes[-1],
        "mtp_cross_entropy": module_ces[-1],
        "mtp_cross_entropy_first": module_ces[0],
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
    gaps, first = rep["gaps"], rep["first_terms"]
    terms, rl, ch, tg, mg, sm = (
        gaps["ref_terms"], gaps["router_logit_gap"], gaps["choices"],
        gaps["token_nll_gap"], gaps["mtp_nll_gap"], gaps["state_abs_max"])
    checks = checks_of(traffic["check"], first, gaps, rep["bias_gap"],
                       rep["biased_regret"])
    print(f"[bench] first-step loss {first['loss']:.5f} (cross entropy "
          f"{first['cross_entropy']:.5f}, the module's "
          f"{first['mtp_cross_entropy']:.5f}); reference on the program's "
          f"choices {terms['loss']:.5f} ({terms['cross_entropy']:.5f}, "
          f"{terms['mtp_cross_entropy']:.5f}); losses finite: {finite}; last "
          f"loss {losses[-1]:.4f}; mosaic calls {rep['mosaic_calls']}; state "
          f"{rep['state_bytes'] / 1e9:.2f} GB; step memory "
          f"{rep['step_memory_bytes']}; peak bytes in use "
          f"{rep['memory_peak_bytes']}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, every leaf: "
          f"{gaps['gradient_gap']}", flush=True)
    print("[bench] what the first step handed on against the reference's "
          f"adamw step, every leaf: {gaps['first_step']}", flush=True)
    print(f"[bench] compared: {ch['count']} choices (own regret "
          f"{ch['own_regret']:.3e}), {tg['positions']} positions a head; the "
          f"reference's router logits spread {rl['ref_std']:.3f}, its "
          f"per-token loss {tg['ref_std']:.3f} (p99 gap {tg['p99']:.4f}), the "
          f"module's {mg['ref_std']:.3f} (p99 gap {mg['p99']:.4f}); the "
          f"largest |S| after the sequence: program {sm['program']:.4f}, "
          f"reference {sm['reference']:.4f}; in the window's first step "
          f"{rep['ssm_state_abs_max_first_most'][0]:.4f}, at most "
          f"{rep['ssm_state_abs_max_first_most'][1]:.4f}; a head's whole last "
          f"state against the reference's: {gaps['state_head_gap']}; largest "
          f"expert load over the mean "
          f"{rep['expert_load_max_over_mean']:.4f} (mean over layers and "
          f"steps), {rep['expert_load_max_over_mean_worst']:.4f} at worst; "
          f"the largest |b| after the window "
          f"{rep['moe_router_bias_abs_max']:.4f}; the module's cross entropy "
          f"in the window's first step {rep['mtp_cross_entropy_first']:.4f}, "
          f"in the last {rep['mtp_cross_entropy']:.4f}", flush=True)
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
          f"the last step, in passes of {rep['moe_rows_passed']} rows, "
          f"{rep['moe_rows_passed_most']} at most; by step "
          f"{rep['moe_rows_held_by_step']})", flush=True)

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
                              rep["moe_rows_held_traced_mean"],
                          "moe_rows_held_module_traced":
                              rep["moe_rows_held_module_traced_mean"],
                          "ssm_state_abs_max": rep["ssm_state_abs_max"],
                          "mtp_cross_entropy": rep["mtp_cross_entropy"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
