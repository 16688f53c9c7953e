"""Runs one training cell of a model whose mixers are Kimi Delta Attention
layers (a delta rule whose decay is a vector over the key's channels) among
gated latent-attention layers, with dense MLPs first and then a routed
mixture under a group limit and a selection bias that the program moves
after each step (Ling-3.0-flash: five to one): ``cells/train_delta_moe.py``'s
window, compile count and report with the rule's two counters and the
routers' bias beside the counts, the traced steps after the window and not
inside it as ``train_sparse.py``'s are (the walk's trace takes the profiler
minutes to stop), a step that trains
``model.trainable(params)`` and then calls ``model.update_router_bias``
(``train_hybrid.py``'s), and a comparison made of ``train_delta_moe.py``'s
(``checks_of`` by import: the loss, the per-position loss, the states, the
gradients, the first step, the router logits, the differing choices and
their regret), with ``load_model``, ``optimizer`` and ``model_parts``
``train_hybrid.py``'s. None of those files' loops can run this model
unedited: each ``_program`` asks its model for other reports and
``train_delta_moe.make_step`` moves no bias.

The comparison that decides ``correct``, after the window, on the weights
the first step saw and batch 0, of what the timed path's own forward gives
at the timed sizes (``model.token_nll``: the walk of the per-channel rule,
the taps' kernels, the latent layer's flash kernels with no query latent,
the held rows' passes, the head and loss in blocks), against
``references/<module>_ref.py`` (float32, highest precision, the recurrence
token by token, a loop over the held experts) forced to the program's own
choices of experts:
(a) the first step's loss;
(b) the per-position next-token loss, root mean square and largest gap;
(c) the router logits of the routed layers, and every expert the program
    chose that the reference would not have on its own selection scores
    ``s + b`` under the group limit: its regret (how far below the
    reference's k-th allowed score it lies, or how far its group's score
    lies below the last kept group's) and the share of such choices;
(d) the KDA layers' states after the last position: the largest ``|S|``
    (the counter ``kda_state_abs_max``) as a share of the reference's, and
    every head's whole state, the worst head bounded;
(e) the gradient of a seeded weighted loss for every leaf of the first
    layer of each kind (layer 0, the first routed KDA layer, the latent
    layer), the embedding, the last norm and the head: a kind's worst
    leaf and, held closer, its median leaf;
(f) adamw's first moment and the parameters after the timed program's own
    first step, against optax's adamw in float32 on the reference's
    gradient of the step's loss, and the routers' biases after it against
    the reference's rule on the program's own counts (a sign of integer
    differences: the tolerance is 0);
(g) the same forward once more with the biases the window ended with:
    ``route``'s choices against the group limit and the top-k recomputed
    on the host from the program's own logits and those biases
    (``own_regret``; at the first step the biases are 0 and a choice on
    ``s + b`` is a choice on ``s``), and the weights ``route`` gave its
    choices against the scores without the bias, renormalised and scaled
    on the host from the same logits (``own_weight_gap``: what refuses a
    bias that reaches the weights).
"""

from __future__ import annotations

import os
import shutil
import statistics
import time
from functools import lru_cache
from typing import Any, Dict, Tuple

from benchmark.cells import train_delta_moe
from benchmark.cells.train import _report_ended
from benchmark.cells.train_hybrid import load_model, model_parts, optimizer
from benchmark.cells.train_mixed import _gradient_gaps
from benchmark.cells.train_scan import _first_step_gaps, first_step_left
from benchmark.lib import procs, spec


def make_step(model, cfg, tx, mesh=None):
    """The cell's train step: (params, opt, batch) -> (params, opt, loss,
    one ``aux``: the routed layers' expert counts [Lr, E], the rows the
    held experts multiplied, the largest ``|S|`` a KDA layer's state holds
    after the sequence, the step's smallest log decay and the largest
    ``|b|`` after the bias's move)."""
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
            "kda_state_abs_max": aux["kda_state_abs_max"],
            "kda_log_decay_min": aux["kda_log_decay_min"],
            "router_bias_abs_max": model.router_bias_abs_max(params)}

    return step


@lru_cache(maxsize=None)
def _program(model, reference, pcfg, mesh):
    """The program's side of ``compare``, jitted once a configuration."""
    import jax

    trainable, with_trainable = model_parts(model)

    def program(p, tokens, weights):
        def weighted(t):
            nll, said = model.token_nll(pcfg, with_trainable(p, t), tokens,
                                        mesh=mesh, keep_router_logits=True)
            return (weights * nll).sum(), (
                nll, said["kda"]["state"], said["router"]["logits"],
                said["router"]["chosen"], said["router"]["weights"])

        (_, out), grads = jax.value_and_grad(weighted, has_aux=True)(
            trainable(p))
        return (*out, reference.first_layers(grads))

    return jax.jit(program)


def own_choice(cfg, select):
    """The rule on the host: select [Lr, n, E] (``s + b``) -> (whether each
    expert lies in a kept group [Lr, n, E], the groups' scores [Lr, n, G],
    the score of the last kept group [Lr, n], the k-th largest allowed
    score [Lr, n])."""
    import numpy as np

    G, keep = cfg.n_group, cfg.topk_group
    by_group = select.reshape(select.shape[:-1] + (G, -1))
    of_group = np.sort(by_group, axis=-1)[..., -2:].sum(-1)
    # two groups' sums tie to the last bit in a few of a million tokens:
    # the earlier group is kept, as ``lax.top_k`` keeps it
    order = np.argsort(-of_group, axis=-1, kind="stable")
    kept = np.zeros(of_group.shape, bool)
    np.put_along_axis(kept, order[..., :keep], True, -1)
    last = np.take_along_axis(of_group, order[..., keep - 1:keep], -1)[..., 0]
    allowed = np.repeat(kept, by_group.shape[-1], -1)
    kth = np.sort(np.where(allowed, select, -np.inf), axis=-1)[..., -cfg.top_k]
    return allowed, of_group, last, kth


# The host's sigmoid and the chip's differ in a last bit, so two groups
# whose scores lie this near each other at the edge of the kept ones may be
# ranked either way round, and the program is held to neither
GROUP_TIE = 2e-6


def regrets(cfg, select, chosen, ties: float = 0.0):
    """How far each of the ``chosen [Lr, n, K]`` lies from what the rule
    would choose on ``select``: inside a kept group the k-th allowed score
    minus its own, outside one the last kept group's score minus its
    group's; not positive for a choice the rule makes. Two groups' scores
    within ``ties`` of the last kept one's are kept either way round: a
    token that has such a pair is read under the keeping that suits its
    choices best (``_regrets_at_a_tie``)."""
    import numpy as np

    allowed, of_group, last, kth = own_choice(cfg, select)
    size = select.shape[-1] // cfg.n_group
    inside = np.take_along_axis(allowed, chosen, -1)
    regret = np.where(
        inside, kth[..., None] - np.take_along_axis(select, chosen, -1),
        last[..., None] - np.take_along_axis(of_group, chosen // size, -1))
    if ties:
        ranked = np.sort(of_group, -1)
        edge = cfg.n_group - cfg.topk_group       # the last kept, ascending
        for at in zip(*np.nonzero(
                ranked[..., edge] - ranked[..., edge - 1] <= ties)):
            regret[at] = _regrets_at_a_tie(cfg, select[at], chosen[at],
                                           of_group[at], ties)
    return regret


def _regrets_at_a_tie(cfg, select, chosen, of_group, ties):
    """``regrets`` of one token (select [E], chosen [K], of_group [G])
    whose last kept group has others within ``ties`` of it: the smallest,
    by its largest entry, over the keepings that hold every group above
    the tie and fill up from the tied ones."""
    import itertools

    import numpy as np

    size = select.shape[-1] // cfg.n_group
    last = np.sort(of_group)[-cfg.topk_group]
    above = np.flatnonzero(of_group > last + ties)
    tied = np.flatnonzero(np.abs(of_group - last) <= ties)
    best = None
    for some in itertools.combinations(tied, cfg.topk_group - len(above)):
        kept = np.zeros(cfg.n_group, bool)
        kept[list(above) + list(some)] = True
        kth = np.sort(np.where(np.repeat(kept, size), select, -np.inf)
                      )[-cfg.top_k]
        regret = np.where(kept[chosen // size], kth - select[chosen],
                          of_group[kept].min() - of_group[chosen // size])
        if best is None or regret.max() < best.max():
            best = regret
    return best


def own_regret(reference, cfg, params, logits, chosen) -> float:
    """The largest regret of ``route``'s choices in the program's own
    selection scores (the sigmoid of its ``logits [Lr, n, E]`` plus the
    routers' biases of ``params``, recomputed here in float32; groups
    within ``GROUP_TIE`` of the last kept one either way round)."""
    import numpy as np

    select = (1.0 / (1.0 + np.exp(-logits.astype(np.float32)))
              + reference.router_biases(cfg, params)[:, None, :])
    return float(regrets(cfg, select, chosen, GROUP_TIE).max())


def own_weight_gap(cfg, logits, chosen, weights) -> float:
    """How far the weights ``route`` gave its choices ``[Lr, n, K]`` lie
    from the rule's on the program's own ``logits [Lr, n, E]``: the
    sigmoids at the chosen, without the bias, over their sum plus
    ``cfg.renorm_eps``, times ``cfg.routed_scale``; float32 on the
    host."""
    import numpy as np

    s = np.take_along_axis(
        1.0 / (1.0 + np.exp(-logits.astype(np.float32))), chosen, -1)
    rule = s / (s.sum(-1, keepdims=True) + np.float32(cfg.renorm_eps)) \
        * np.float32(cfg.routed_scale)
    return float(np.abs(weights - rule).max())


def choices_under_bias(model, reference, cfg, params, tokens, mesh=None
                       ) -> Tuple[float, float]:
    """(g) of the module's docstring: ``own_regret`` and
    ``own_weight_gap`` of the timed path's forward on ``params``, whose
    biases are not 0."""
    import numpy as np

    _, _, logits, chosen, given, _ = _program(model, reference, cfg, mesh)(
        params, tokens, np.zeros(tokens[:, 1:].shape, np.float32))
    logits, chosen = np.asarray(logits), np.asarray(chosen)
    return (own_regret(reference, cfg, params, logits, chosen),
            own_weight_gap(cfg, logits, chosen, np.asarray(given)))


def compare(model, reference, cfg, params, tokens, host_tokens, mesh=None,
            reference_params=None, program_cfg=None,
            reference_mantissa_bits=None, seed: int = 0, first_step=None
            ) -> Dict[str, Any]:
    """The gaps between the program's own forward (on ``tokens``, the
    device's copy of ``host_tokens``) and the reference forced to the
    program's choices of experts, and between their gradients of
    ``sum(weights * per-position loss)``, the weights drawn from ``seed``.
    ``first_step``: (the optimizer, what ``first_step_left`` gave of a step
    on these weights and tokens) adds the gaps of what that step handed on.
    ``reference_params``, ``program_cfg`` and ``reference_mantissa_bits``
    are for ``benchmark/tests/kda_moe_limits.py``."""
    import numpy as np

    pcfg = program_cfg or cfg
    weights = (np.random.default_rng(seed + 1).uniform(
        0.5, 1.5, host_tokens[:, 1:].shape) / host_tokens[:, 1:].size
               ).astype(np.float32)

    def floats(by_kind):
        return {kind: {name: float(v) for name, v in leaves.items()}
                for kind, leaves in by_kind.items()}

    got_nll, got_states, got_logits, got_chosen, got_given, got_grads = \
        _program(model, reference, pcfg, mesh)(params, tokens, weights)
    got_nll, got_states, got_logits, got_chosen, got_given = (
        np.asarray(x) for x in (got_nll, got_states, got_logits, got_chosen,
                                got_given))
    ref_params = params if reference_params is None else reference_params
    ref = reference.token_nll(cfg, ref_params, host_tokens,
                              forced_topk=got_chosen, grad_weights=weights,
                              mantissa_bits=reference_mantissa_bits)
    by_kind = floats(_gradient_gaps()(got_grads, ref.pop("grads")))
    del got_grads
    stepped = {}
    if first_step is not None:
        tx, left = first_step
        mean = reference.token_nll(
            cfg, ref_params, host_tokens, forced_topk=got_chosen,
            grad_weights=np.full_like(weights, 1.0 / weights.size),
            mantissa_bits=reference_mantissa_bits)
        moment, moved = _first_step_gaps(tx)(
            left, reference.first_layers(ref_params), mean.pop("grads"))
        stepped = {"first_step": {"moment_gap": floats(moment),
                                  "param_gap": float(moved)}}
    # the reference on the program's choices: its own selection scores say
    # what the program should have chosen and how near a tie each differing
    # choice was
    ref_lg = ref["router_logits"]
    regret = regrets(cfg, ref["select_scores"], got_chosen)
    differs = regret > 0
    logit_gap = np.abs(got_logits - ref_lg)
    nll_gap = np.abs(got_nll - ref["nll"])
    # the last states [Lk, B, H, V, K], a head at a time
    ref_states = ref["last_states"]
    head_gap = (np.sqrt(np.square(got_states - ref_states).sum((-2, -1)))
                / np.sqrt(np.square(ref_states).sum((-2, -1))))
    return {
        **stepped,
        "ref_terms": ref["terms"],
        "program_cross_entropy": float(got_nll.mean()),
        "log_decay_min": ref["log_decay_min"],
        "state_abs_max": {"program": float(np.abs(got_states).max()),
                          "reference": ref["state_abs_max"]},
        "state_head_gap": {
            "worst": float(head_gap.max()),
            "median": float(np.median(head_gap)),
            "layer_row_head": [int(i) for i in np.unravel_index(
                head_gap.argmax(), head_gap.shape)]},
        "router_logit_gap": {"rms": float(np.sqrt(np.mean(logit_gap ** 2))),
                             "max": float(logit_gap.max()),
                             "ref_std": float(ref_lg.std())},
        "choices": {"differing_share": float(differs.mean()),
                    "max_regret": float(max(regret.max(), 0.0)),
                    "own_regret": own_regret(reference, cfg, params,
                                             got_logits, got_chosen),
                    "own_weight_gap": own_weight_gap(
                        cfg, got_logits, got_chosen, got_given),
                    "count": int(differs.size)},
        "token_nll_gap": {"rms": float(np.sqrt(np.mean(nll_gap ** 2))),
                          "max": float(nll_gap.max()),
                          "p99": float(np.quantile(nll_gap, 0.99)),
                          "positions": int(nll_gap.size),
                          "ref_std": float(ref["nll"].std())},
        "gradient_gap": by_kind}


def checks_of(chk: Dict[str, Any], first_loss: float, gaps: Dict[str, Any],
              bias_gap: float = None, biased: Tuple[float, float] = None
              ) -> Dict[str, Tuple[float, float]]:
    """what -> (reading, tolerance): the comparison that decides
    ``correct``: ``train_delta_moe.checks_of``'s, each kind's median
    leaf of the gradient, the choices and their weights in the program's
    own scores, and the routers' bias's
    (``bias_gap`` after the first step; ``biased``:
    ``choices_under_bias``'s pair; each left out where the reading was not
    taken: a control that plants its fault in the forward)."""
    checks = train_delta_moe.checks_of(chk, first_loss, gaps)
    # a kind's worst leaf sums few rows (a routed layer's router and held
    # experts) and wanders; its median leaf does not, and is held closer
    for kind, leaves in gaps["gradient_gap"].items():
        checks[f"gradient, median leaf, {kind}"] = (
            statistics.median(leaves.values()),
            chk["gradient_gap_median_tolerance"][kind])
    checks["choices in the program's own scores, regret"] = (
        gaps["choices"]["own_regret"], chk["own_choice_regret_tolerance"])
    checks["weights in the program's own scores, gap"] = (
        gaps["choices"]["own_weight_gap"], chk["own_weight_gap_tolerance"])
    if bias_gap is not None:
        checks["router bias after the first step"] = (
            bias_gap, chk["router_bias_tolerance"])
    if biased is not None:
        checks["choices under the routers' biases, regret"] = (
            biased[0], chk["own_choice_regret_tolerance"])
        checks["weights under the routers' biases, gap"] = (
            biased[1], chk["own_weight_gap_tolerance"])
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
        params, opt, loss, _ = compiled(params, opt, put(i))
        loss.block_until_ready()
        losses.append(float(loss))

    ann = jax.profiler.TraceAnnotation
    trace_dir = config["trace_dir"]
    compiles0 = compile_counter.count()
    ends, counts, held, state_maxes, decay_mins = [], [], [], [], []

    def one_step(i, params, opt):
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
        state_maxes.append(float(aux["kda_state_abs_max"]))
        decay_mins.append(float(aux["kda_log_decay_min"]))
        return params, opt, aux

    t_open_wall = time.time()
    t_open = time.monotonic()
    i = tr["warmup_steps"]
    while not ends or ends[-1] - t_open < config["seconds"]:
        params, opt, aux = one_step(i, params, opt)
        i += 1
    window = len(ends)
    # the traced steps follow the window (``train_sparse.py``'s reason: the
    # walk's thousands of small operations a step make a trace the profiler
    # needs minutes to stop, 468 s for four steps at 32,768 tokens), so a
    # traced run's rate is read from as clean a window as an untraced run's
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        for i in range(i, i + tr["trace_steps"]):
            params, opt, aux = one_step(i, params, opt)
        jax.profiler.stop_trace()
    compiles = compile_counter.count() - compiles0
    loss_values = losses
    counts = np.stack(counts)                               # [steps, Lr, E]
    load = (counts.max(-1) / counts.mean(-1))[:window]      # [steps, Lr]
    held = np.asarray(held)
    passed = int(model.rows_passed(cfg, counts[window - 1]))
    window_s = ends[window - 1] - t_open
    in_trace = slice(window, None) if trace_dir else slice(None)
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        (params, opt)))

    # ---- correctness, after the window (the module's docstring)
    bias_max = float(aux["router_bias_abs_max"])
    end_biases = {kind: leaves["router_bias"]
                  for kind, leaves in params["layers"].items()
                  if "router_bias" in leaves}
    del params, opt, losses, aux
    # (f): the timed executable once more on what its first call was given
    # (the seeded weights, a new optimizer state, batch 0), here and not in
    # the warm-up: copies taken there would cost every step of the window
    params = init(key)
    bias0 = reference.router_biases(cfg, params)
    after, opt, _, aux = compiled(
        params, tx.init(model_parts(model)[0](params)), put(0))
    left = first_step_left(reference, after, opt)
    bias_gap = float(np.abs(
        reference.router_biases(cfg, after) - reference.updated_bias(
            cfg, bias0, np.asarray(aux["expert_counts"]))).max())
    del after, opt, aux
    params = init(key)
    tokens = put(0)["tokens"]
    gaps = compare(model, reference, cfg, params, tokens, host[0],
                   mesh=mesh, seed=seed, first_step=(tx, left))
    # (g): the first step's weights under the window's last biases
    params = {**params, "layers": {
        kind: {**leaves, **({"router_bias": end_biases[kind]}
                            if kind in end_biases else {})}
        for kind, leaves in params["layers"].items()}}
    biased = choices_under_bias(model, reference, cfg, params, tokens,
                                mesh=mesh)

    train.report({
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "memory_peak_bytes": peak,
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "t_loop_wall": t_loop_wall,
        "t_open_wall": t_open_wall, "window_s": window_s,
        "steps": window, "step_ends": [e - t_open for e in ends[:window]],
        # the profiler's span lies after the window: every step is clean
        "untraced_steps": window, "untraced_s": window_s,
        "losses": loss_values, "gaps": gaps, "router_bias_gap": bias_gap,
        "biased_choices": biased,
        "compiles": compiles, "mosaic_calls": mosaic_calls,
        "state_bytes": state_bytes,
        "step_memory_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "outputs_not_aliased": (mem.output_size_in_bytes
                                    - mem.alias_size_in_bytes)},
        "traced_steps": len(ends) - window,
        "expert_load_max_over_mean": float(load.mean()),
        "expert_load_first_last": [float(load[0].mean()),
                                   float(load[-1].mean())],
        "expert_load_max_over_mean_worst": float(load.max()),
        # the program's own counters of the last step (rtpu_train_*)
        "moe_rows_routed": int(counts[window - 1].sum()),
        "moe_rows_held": int(held[window - 1]),
        "moe_rows_passed": passed,
        "moe_rows_held_mean": float(held[:window].mean()),
        "moe_rows_held_traced_mean": float(held[in_trace].mean()),
        "moe_rows_held_by_step": [int(x) for x in held[:window]],
        "moe_expert_load_max_over_mean": float(load[-1].mean()),
        "moe_router_bias_abs_max": bias_max,
        "kda_state_abs_max": state_maxes[window - 1],
        "kda_state_abs_max_first_most": [state_maxes[0],
                                         max(state_maxes[:window])],
        "kda_log_decay_min": min(decay_mins),
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
    terms, rl, ch, tg, sm = (gaps["ref_terms"], gaps["router_logit_gap"],
                             gaps["choices"], gaps["token_nll_gap"],
                             gaps["state_abs_max"])
    checks = checks_of(traffic["check"], losses[0], gaps,
                       rep["router_bias_gap"], rep["biased_choices"])
    lower = ctx["model_config"].get("kda_lower_bound", -5.0)
    checks["smallest log decay of a step, over the bound"] = (
        max(lower - rep["kda_log_decay_min"], 0.0), 0.0)
    print(f"[bench] first-step loss {losses[0]:.5f}; reference on the "
          f"program's choices {terms['loss']:.5f}; losses finite: {finite}; "
          f"last loss {losses[-1]:.4f}; mosaic calls {rep['mosaic_calls']}; "
          f"state {rep['state_bytes'] / 1e9:.2f} GB; step memory "
          f"{rep['step_memory_bytes']}; peak bytes in use "
          f"{rep['memory_peak_bytes']}", flush=True)
    for what, (value, tol) in checks.items():
        print(f"[bench] {what}: {value:.3e} (tolerance {tol}) "
              f"ok={value <= tol}", flush=True)
    print("[bench] gradient of the seeded scalar, every leaf: "
          f"{gaps['gradient_gap']}", flush=True)
    print("[bench] what the first step handed on against the reference's "
          f"adamw step, every leaf: {gaps['first_step']}", flush=True)
    print(f"[bench] compared: {ch['count']} choices, {tg['positions']} "
          f"positions; the reference's router logits spread "
          f"{rl['ref_std']:.3f}, its per-token loss {tg['ref_std']:.3f} (p99 "
          f"gap {tg['p99']:.4f}); the largest |S| after the sequence: "
          f"program {sm['program']:.4f}, reference {sm['reference']:.4f}; in "
          f"the window's first step "
          f"{rep['kda_state_abs_max_first_most'][0]:.4f}, at most "
          f"{rep['kda_state_abs_max_first_most'][1]:.4f}, in the last "
          f"{rep['kda_state_abs_max']:.4f}; the smallest log decay of the "
          f"window {rep['kda_log_decay_min']:.4f} (the reference's on batch "
          f"0 {gaps['log_decay_min']:.4f}; bound {lower}); a head's whole "
          f"last state against the reference's: {gaps['state_head_gap']}; "
          f"largest expert load over the mean "
          f"{rep['expert_load_max_over_mean']:.4f} (mean over layers and "
          f"steps), {rep['expert_load_max_over_mean_worst']:.4f} at worst; "
          f"largest |router bias| after the last step "
          f"{rep['moe_router_bias_abs_max']:.4f}", flush=True)
    print(f"[bench] the worker held its chips and entered the train loop "
          f"{rep['t_loop_wall'] - ctx['t_start_wall']:.1f}s after this "
          f"process started", flush=True)
    print(f"[bench] window {rep['window_s']:.3f}s (asked {ctx['seconds']}); "
          f"compilations inside the window: {rep['compiles']}; steps "
          f"{rep['steps']}; steps traced after the window "
          f"{rep['traced_steps']}", flush=True)
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
                              rep["moe_rows_held_traced_mean"],
                          "kda_state_abs_max": rep["kda_state_abs_max"],
                          "kda_log_decay_min": rep["kda_log_decay_min"]},
                "model": model, "traffic": traffic, "cell": cell},
        "trace_dir": trace_dir if ctx["trace"] else None,
    }
