"""Runs one training cell of a model whose every layer attends, with
grouped-query heads, over the keys a learned index chooses, under a rope in
three position streams that the batch brings (Keye-VL-2.0-30B-A3B: a softmax
router over a held share of the experts, no shared expert, no bias):
``cells/train_sparse.py``'s window, ticks, traced steps after the window and
report, with a batch, a step and a comparison of its own. ``train_sparse.py``
cannot run this model unedited: its batch is ids alone (here ``positions [3,
b, s]`` and a ``mask`` ride with them, from
``generators/train_batches_mrope.py``), its step moves a router bias this
model has not, its check builds the logits whole (37,984 x 16,384 float32
and their gradient do not fit beside the weights: here the program's side is
the timed path's own blocked head) and knows no rope from the batch.
``load_model`` and ``optimizer`` are ``train_hybrid.py``'s, ``first_step_left``
and the first step's gaps ``train_scan.py``'s, the gradient gaps
``train_mixed.py``'s, the index's score and key gaps ``train_sparse.py``'s,
by import.

The comparison that decides ``correct``, after the window, on the weights
the first step saw and batch 0, of what the timed path's own forward gives
at the timed sizes (the walk of ``ops/dsa.py`` under grouped keys, the rope
tables from the batch's positions, the held rows' passes, the blocked head),
against ``references/<module>_ref.py`` (float32, highest precision, the same
held experts and rows, the same positions) forced to the program's own
choices of experts and of keys:
(a) the first step's cross entropy over the text targets, its index term
    ``L_I`` and its balancing term, apart;
(b) the per-position next-token loss, root mean square and largest gap;
(c) the router logits of every layer;
(d) the index's scores of every layer over the causal pairs, in blocks of
    queries (the program's from the inputs its layers report, through
    ``ops/dsa.index_scores``);
(e) every chosen key the reference would not have chosen must be a near-tie
    in the reference's own scores (its ``regret`` bounded, and the share of
    such keys); the same for experts, in the reference's router logits; the
    keys chosen are as many as the configuration says, ``sum_t min(t + 1,
    topk)`` a sequence and layer, exactly;
(f) the gradient of a seeded weighted loss ``sum(w * per-position loss) +
    sum of L_I``, ``w`` zero on image targets, for every leaf of layers 0
    and 1, the embedding, the last norm and the head;
(g) adamw's first moment and the parameters after the timed program's own
    first step, against optax's adamw in float32 on the reference's
    gradient of the whole loss.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from functools import lru_cache
from typing import Any, Dict, Tuple

from benchmark.cells.train import _report_ended
from benchmark.cells.train_hybrid import load_model, optimizer
from benchmark.cells.train_mixed import _gradient_gaps
from benchmark.cells.train_scan import _first_step_gaps, first_step_left
from benchmark.cells.train_sparse import key_gaps
from benchmark.lib import procs, spec

COUNTERS = ("cross_entropy", "dsa_index_loss", "dsa_pairs_chosen_share",
            "load_balance")


def batch_shapes(traffic: Dict[str, Any]) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, dtype) of what a step's batch holds
    (``tools/step_program.py`` compiles the step from them)."""
    B, S = traffic["batch"], traffic["seq"]
    return {"tokens": ((B, S + 1), "int32"),
            "positions": ((3, B, S), "int32"),
            "mask": ((B, S + 1), "float32")}


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    the layers' expert counts [L, E], the step's counters: the cross
    entropy over the text targets, the index's term, the share of causal
    pairs chosen and the routers' balancing term)."""
    import jax
    import optax

    def step(params, opt, batch):
        trained = model.trainable(params)
        (loss, aux), grads = jax.value_and_grad(
            lambda t: model.loss_terms(
                cfg, model.with_trainable(params, t), batch, mesh=mesh),
            has_aux=True)(trained)
        updates, opt = tx.update(grads, opt, trained)
        params = model.with_trainable(
            params, optax.apply_updates(trained, updates))
        return params, opt, loss, aux["expert_counts"], {
            name: aux[name] for name in COUNTERS}

    return step


@lru_cache(maxsize=None)
def _program(model, reference, pcfg, mesh):
    """The program's side of ``compare``, jitted once a configuration: the
    timed path's forward and blocked head, every layer's reports kept."""
    import jax

    def program(p, batch, weights):
        tokens, positions = batch["tokens"], batch["positions"]

        def weighted(p):
            nll, said = model.token_nll_reports(pcfg, p, tokens, positions,
                                                mesh=mesh)
            router, index = said["router"], said["dsa"]
            l_i = index["kl"].sum(-1) / index["positions"]          # [L]
            return (weights * nll).sum() + l_i.sum(), (
                nll, router["logits"], l_i, index["choice"],
                (index["q_i"], index["k_i"], index["w"]))

        (_, (nll, logits, *rest)), grads = jax.value_and_grad(
            weighted, has_aux=True)(p)
        chosen = jax.lax.top_k(logits, pcfg.top_k)[1]
        return (nll, logits, chosen, *rest, reference.first_layers(grads))

    return jax.jit(program)


def compare(model, reference, cfg, params, batch, host, mesh=None,
            program=None, reference_dtype=None, seed: int = 0,
            first_step=None) -> Dict[str, Any]:
    """The gaps between the program's forward (on ``batch``, the device's
    copy of the host's ``host``: ``tokens [B, S + 1]``, ``positions [3, B,
    S]``, ``mask [B, S + 1]``) and the reference forced to the program's
    choices of experts and keys, and between their gradients of
    ``sum(weights * per-position loss) + sum of L_I``, the weights drawn
    from ``seed`` and zero on image targets. ``first_step``: (the optimizer,
    what ``first_step_left`` gave of a step on these weights and this
    batch) adds the gaps of what that step handed on. ``program`` ((model,
    config) with a fault planted) and ``reference_dtype`` (the reference
    reads its weights rounded to that dtype) are for
    ``benchmark/tests/sparse_gqa_limits.py``."""
    import numpy as np

    pmodel, pcfg = program or (model, cfg)
    text = host["mask"][:, 1:]
    weights = (np.random.default_rng(seed + 1).uniform(0.5, 1.5, text.shape)
               * text / text.sum()).astype(np.float32)

    def floats(by_kind):
        return {kind: {name: float(v) for name, v in leaves.items()}
                for kind, leaves in by_kind.items()}

    (got_nll, got_logits, got_chosen, got_li, choice, got_index,
     got_grads) = _program(pmodel, reference, pcfg, mesh)(
        params, batch, weights)
    got_nll, got_logits, got_chosen, got_li = (
        np.asarray(x) for x in (got_nll, got_logits, got_chosen, got_li))
    forced = dict(positions=host["positions"], forced_topk=got_chosen,
                  forced_keys=choice, weight_dtype=reference_dtype)
    ref = reference.token_nll(cfg, params, host["tokens"], **forced,
                              grad_weights=weights, index_weight=1.0)
    by_kind = floats(_gradient_gaps()(got_grads, ref.pop("grads")))
    del got_grads
    keys = key_gaps(reference, cfg, ref.pop("index"), got_index, choice)
    del got_index
    stepped = {}
    if first_step is not None:
        tx, left = first_step
        mean = reference.token_nll(
            cfg, params, host["tokens"], **forced,
            grad_weights=(text / text.sum()).astype(np.float32),
            index_weight=cfg.index_loss_coef,
            router_weight=cfg.router_aux_coef)
        moment, moved = _first_step_gaps(tx)(
            left, reference.first_layers(params), mean.pop("grads"))
        stepped = {"first_step": {"moment_gap": floats(moment),
                                  "param_gap": float(moved)}}
    ref_lg = ref["router_logits"]
    kth = np.sort(ref_lg, axis=-1)[..., -cfg.top_k]
    regret = np.maximum(
        kth[..., None] - np.take_along_axis(ref_lg, got_chosen, -1), 0.0)
    logit_gap = np.abs(got_logits - ref_lg)
    nll_gap = np.abs(got_nll - ref["nll"])
    ce = float((ref["nll"] * text).sum() / text.sum())
    l_i = float(ref["index_loss"].sum())
    return {
        **stepped, **keys,
        "ref_terms": {"cross_entropy": ce, "index_loss": l_i,
                      "load_balance": ref["balance"],
                      "loss": ce + cfg.index_loss_coef * l_i
                      + cfg.router_aux_coef * ref["balance"]},
        "program_cross_entropy": float((got_nll * text).sum() / text.sum()),
        "index_loss_gap": float(np.abs(got_li - ref["index_loss"]).max()),
        "ref_index_loss": [float(x) for x in ref["index_loss"]],
        "router_logit_gap": {"rms": float(np.sqrt(np.mean(logit_gap ** 2))),
                             "max": float(logit_gap.max()),
                             "ref_std": float(ref_lg.std())},
        "choices": {"differing_share": float((regret > 0).mean()),
                    "max_regret": float(regret.max()),
                    "count": int(regret.size)},
        "token_nll_gap": {"rms": float(np.sqrt(np.mean(nll_gap ** 2))),
                          "max": float(nll_gap.max()),
                          "p99": float(np.quantile(nll_gap, 0.99)),
                          "positions": int(nll_gap.size),
                          "text_targets": int(text.sum()),
                          "ref_std": float(ref["nll"].std())},
        "gradient_gap": by_kind}


def checks_of(chk: Dict[str, Any], first_terms: Dict[str, float],
              gaps: Dict[str, Any]) -> Dict[str, Tuple[float, float]]:
    """what -> (reading, tolerance): the comparison that decides
    ``correct``, of ``compare()``'s ``gaps`` and the first step's three
    loss terms, under the traffic file's ``check``."""
    rl, ch, tg, ix, ky = (gaps["router_logit_gap"], gaps["choices"],
                          gaps["token_nll_gap"], gaps["index_score_gap"],
                          gaps["keys"])
    terms = gaps["ref_terms"]
    checks = {
        "first-step cross entropy": (
            abs(first_terms["cross_entropy"] - terms["cross_entropy"]),
            chk["loss_tolerance"]),
        "first-step index loss": (
            abs(first_terms["dsa_index_loss"] - terms["index_loss"]),
            chk["index_loss_tolerance"]),
        "first-step balancing term": (
            abs(first_terms["load_balance"] - terms["load_balance"]),
            chk["balance_tolerance"]),
        "index loss, a layer": (gaps["index_loss_gap"],
                                chk["index_loss_layer_tolerance"]),
        "router logits, rms": (rl["rms"], chk["router_logit_rms_tolerance"]),
        "router logits, max": (rl["max"], chk["router_logit_max_tolerance"]),
        "differing experts, share": (
            ch["differing_share"], chk["differing_choice_share_tolerance"]),
        "differing experts, regret": (ch["max_regret"],
                                      chk["choice_regret_tolerance"]),
        "index scores, rms": (ix["rms"], chk["index_score_rms_tolerance"]),
        "index scores, max": (ix["max"], chk["index_score_max_tolerance"]),
        "differing keys, share": (ky["differing_share"],
                                  chk["differing_key_share_tolerance"]),
        "differing keys, regret": (ky["max_regret"],
                                   chk["key_regret_tolerance"]),
        "chosen keys, count": (ky["count_gap"], chk["key_count_tolerance"]),
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
    opt = tx.init(model.trainable(params))
    host = spec.generator(tr["kind"]).host_batches(tr, seed, cfg.vocab_size)

    def host_batch(i: int):
        return {name: rows[i % len(rows)] for name, rows in host.items()}

    # the streams' axis leads the positions: their rows are the second
    psh_rows = None if bsh is None else jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, *bsh.spec))

    def put(i: int):
        return {name: jax.device_put(
            rows, psh_rows if name == "positions" else bsh)
            for name, rows in host_batch(i).items()}

    batch = put(0)
    compiled = jax.jit(make_step(model, cfg, tx, mesh),
                       donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    losses, said = [], []     # said: the step's counters, floats
    for i in range(tr["warmup_steps"]):          # step 0 runs batch 0
        params, opt, loss, _, extra = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))
        said.append({k: float(v) for k, v in extra.items()})

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends, counts, parts = [], [], []    # counts: [L, E] of each step
    # a thread that notes the time every 10 ms (``train_sparse.py``): a gap
    # in these as long as a stalled step says this process did not run
    ticks, closed = [], threading.Event()

    def tick():
        while not closed.wait(0.01):
            ticks.append(time.monotonic())

    threading.Thread(target=tick, daemon=True).start()

    def one_step(i, params, opt):
        """Step ``i`` on the clock: where the host spent it goes to
        ``parts`` (handing the batch over, the call, the wait for the
        device, taking the step's numbers to the host at once, the longest
        gap between two ticks that fell into the step)."""
        t = [time.monotonic()]
        with ann("bench.send"):
            batch = put(i)
        t.append(time.monotonic())
        with ann("bench.step"):
            params, opt, loss, cnt, extra = compiled(params, opt, batch)
        t.append(time.monotonic())
        with ann("bench.wait"):
            loss.block_until_ready()
        t.append(time.monotonic())
        ends.append(t[-1])
        losses.append(float(loss))
        counts.append(np.asarray(cnt))
        said.append({k: float(v) for k, v in extra.items()})
        t.append(time.monotonic())
        inside = [x for x in ticks[-int((t[-1] - t[0]) / 0.01) - 2:]
                  if t[0] <= x <= t[-1]]
        parts.append([b - a for a, b in zip(t, t[1:])] + [max(
            b - a for a, b in zip([t[0]] + inside, inside + [t[-1]]))])
        return params, opt

    t_open_wall = time.time()
    t_open = time.monotonic()
    i = tr["warmup_steps"]
    while not ends or ends[-1] - t_open < config["seconds"]:
        params, opt = one_step(i, params, opt)
        i += 1
    window = len(ends)
    # the traced steps follow the window (``train_sparse.py`` says why)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        for i in range(i, i + tr["trace_steps"]):
            params, opt = one_step(i, params, opt)
        jax.profiler.stop_trace()
    closed.set()
    compiles = compile_counter.count() - compiles0
    loss_values = losses
    counts = np.stack(counts)                               # [steps, L, E]
    load = (counts.max(-1) / counts.mean(-1))[:window]      # [steps, L]
    held = np.asarray([int(model.rows_held(cfg, c)) for c in counts])
    passed = int(model.rows_passed(cfg, counts[window - 1]))
    window_s = ends[window - 1] - t_open
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    # ---- correctness, after the window (the module's docstring)
    del params, opt, batch, losses
    # (g): the timed executable once more on what its first call was given
    # (the seeded weights, a new optimizer state, batch 0), here and not in
    # the warm-up: copies taken there would cost every step of the window
    params = init(key)
    after, opt, *_ = compiled(params, tx.init(model.trainable(params)),
                              put(0))
    left = first_step_left(reference, after, opt)
    del after, opt
    params = init(key)
    gaps = compare(model, reference, cfg, params, put(0), host_batch(0),
                   mesh=mesh, seed=seed, first_step=(tx, left))

    of_window = slice(tr["warmup_steps"], tr["warmup_steps"] + window)
    last = said[of_window][-1]
    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": window_s,
        "steps": window, "step_ends": [e - t_open for e in ends[:window]],
        "step_parts": parts[:window],
        # the profiler's span lies after the window: every step is clean
        "untraced_steps": window, "untraced_s": window_s,
        "losses": loss_values, "first_terms": said[0], "gaps": gaps,
        "compiles": compiles, "mosaic_calls": mosaic_calls,
        "state_bytes": state_bytes,
        "text_share": float(host["mask"].mean()),
        "step_memory_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs_not_aliased": (mem.output_size_in_bytes
                                    - mem.alias_size_in_bytes),
            "peak": getattr(mem, "peak_memory_in_bytes", None)},
        "traced_steps": len(ends) - window,
        "expert_load_max_over_mean": float(load.mean()),
        "expert_load_first_last": [float(load[0].mean()),
                                   float(load[-1].mean())],
        "expert_load_max_over_mean_worst": float(load.max()),
        # the program's own counters (rtpu_train_*): the last step's, and
        # the window's means
        "moe_rows_routed": int(counts[window - 1].sum()),
        "moe_rows_held": int(held[window - 1]),
        "moe_rows_passed": passed,
        "moe_rows_held_mean": float(held[:window].mean()),
        "moe_rows_held_traced_mean": float(
            held[window if len(held) > window else 0:].mean()),
        "moe_rows_held_by_step": [int(x) for x in held[:window]],
        "dsa_pairs_chosen_share": last["dsa_pairs_chosen_share"],
        "dsa_index_loss": last["dsa_index_loss"],
        "dsa_index_loss_first_last": [said[0]["dsa_index_loss"],
                                      last["dsa_index_loss"]],
        "dsa_pairs_chosen_share_mean": float(np.mean(
            [s["dsa_pairs_chosen_share"] for s in said[of_window]])),
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
    terms, rl, ch, tg, ix, ky = (
        gaps["ref_terms"], gaps["router_logit_gap"], gaps["choices"],
        gaps["token_nll_gap"], gaps["index_score_gap"], gaps["keys"])
    first = rep["first_terms"]
    checks = checks_of(traffic["check"], first, gaps)
    print(f"[bench] first-step loss {losses[0]:.5f} (cross entropy over the "
          f"text targets {first['cross_entropy']:.5f}, index loss "
          f"{first['dsa_index_loss']:.5f}, balancing term "
          f"{first['load_balance']:.5f}); reference on the program's "
          f"choices {terms['loss']:.5f} (cross entropy "
          f"{terms['cross_entropy']:.5f}, index loss "
          f"{terms['index_loss']:.5f}, by layer {gaps['ref_index_loss']}, "
          f"balancing term {terms['load_balance']:.5f}); "
          f"losses finite: {finite}; last loss {losses[-1]:.4f}; mosaic calls "
          f"{rep['mosaic_calls']}; state {rep['state_bytes'] / 1e9:.2f} GB; "
          f"step memory {rep['step_memory_bytes']}; peak bytes in use "
          f"{rep['memory_peak_bytes']}; share of ids that are text "
          f"{rep['text_share']:.4f}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, every leaf: "
          f"{gaps['gradient_gap']}", flush=True)
    print("[bench] what the first step handed on against the reference's "
          f"adamw step, every leaf: {gaps['first_step']}", flush=True)
    print(f"[bench] compared: {ch['count']} choices of experts, "
          f"{ky['count']} chosen keys over {ix['pairs']} causal pairs, "
          f"{tg['positions']} positions of which {tg['text_targets']} text "
          f"targets; the reference's router logits "
          f"spread {rl['ref_std']:.3f}, its index scores {ix['ref_std']:.4f}, "
          f"its per-token loss {tg['ref_std']:.3f} (p99 gap {tg['p99']:.4f}); "
          f"largest expert load over the mean "
          f"{rep['expert_load_max_over_mean']:.4f} (mean over layers and "
          f"steps), {rep['expert_load_max_over_mean_worst']:.4f} at worst; "
          f"share of causal pairs "
          f"chosen {rep['dsa_pairs_chosen_share']:.5f}; index loss at the "
          f"first and the last step {rep['dsa_index_loss_first_last']}",
          flush=True)
    print(f"[bench] the worker held its chips and entered the train loop "
          f"{rep['t_loop_wall'] - ctx['t_start_wall']:.1f}s after this "
          f"process started", flush=True)
    print(f"[bench] window {rep['window_s']:.3f}s (asked {ctx['seconds']}); "
          f"compilations inside the window: {rep['compiles']}; steps "
          f"{rep['steps']}; after it under the profiler "
          f"{rep['traced_steps']}", flush=True)
    ends = rep["step_ends"]
    took = sorted((b - a, j) for j, (a, b) in enumerate(zip([0.0] + ends,
                                                            ends)))
    median = took[len(took) // 2]
    slow = [(j, d) for d, j in took[::-1] if d > 1.02 * median[0]][:5]

    def parts_of(j):
        return [round(x, 4) for x in rep["step_parts"][j]]

    print(f"[bench] a step took {took[0][0]:.4f} / {median[0]:.4f} / "
          f"{took[-1][0]:.4f}s (least, median, most); the host's time in a "
          f"step as [batch handed over, the call, waiting for the device, "
          f"the step's numbers taken, longest gap between the 10 ms ticks]: "
          f"the longest step {parts_of(took[-1][1])}, the median step "
          f"{parts_of(median[1])}, every step over 1.02 of the median (five "
          f"at most) {[(j, round(d, 4), parts_of(j)) for j, d in slow]}; "
          f"expert load over the mean at the first and the last step "
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
                              rep["moe_rows_held_traced_mean"],
                          "dsa_pairs_chosen_share":
                              rep["dsa_pairs_chosen_share_mean"],
                          "dsa_index_loss": rep["dsa_index_loss"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
