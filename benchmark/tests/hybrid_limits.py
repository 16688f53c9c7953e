"""On the chip, in one process: the verdict of a ``train_hybrid`` cell's
check (``train_hybrid.checks_of``, the dict ``run()`` decides ``correct``
from) on the honest program and on three controls, each of which it has to
refuse: (a) the reference computed one precision lower (its weights
rounded to float8 e4m3); (b) the program with each convolution's taps
reversed; (c) the program with the bias left out of the choice of experts,
the fault planted in ``ops/moe.route`` itself (its ``select_bias``
dropped), read beside the honest program on the same seeded biases (normal
x 0.05: the cell's first step starts at 0, where the fault changes
nothing, and its check (f) runs on the biases the window ended with).
Same weights, same batch 0 as the cell with this seed. ``--seed`` given
again adds the honest program's verdict at that seed (the range a
tolerance is set from), with no control.

    python3 benchmark/tests/hybrid_limits.py --workload train-lfm2-1chip --seed 17 [--seed 18 ...]

Prints one JSON object and writes it to ``chiprun_out/hybrid_limits.json``:
for each reading ``correct``, ``refused_by`` (the checks over their
tolerance) and ``readings``.
"""
import argparse
import json
import os
import sys
from functools import lru_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-lfm2-1chip")
    ap.add_argument("--seed", type=int, action="append")
    a = ap.parse_args()
    seeds = a.seed or [17]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.cells import train_hybrid
    from benchmark.lib import spec
    from ray_tpu.ops import moe

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    model, reference, cfg = train_hybrid.load_model(
        ctx["config"]["model_config"])
    init = jax.jit(lambda k: model.init_params(cfg, k))

    def inputs(seed):
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)[0]
        return (init(jax.random.PRNGKey(seed % (2 ** 31 - 1))), host,
                jax.device_put(host))

    chk = tr["check"]
    tolerances = {}

    @lru_cache(maxsize=None)
    def first_step():
        """(loss, the biases after the step's update) of the program."""
        def step(p, t):
            loss, aux = model.loss_terms(cfg, p, {"tokens": t})
            return loss, aux["expert_counts"], model.update_router_bias(
                cfg, p, aux["expert_counts"])
        return jax.jit(step)

    def lower(x):
        if x.dtype not in (jnp.bfloat16, jnp.float32):
            return x
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    def on_layers(params, name, fn):
        """``params`` with ``fn(kind index, leaf)`` in place of every
        layer kind's leaf ``name``."""
        return {**params, "layers": {
            kind: {k: fn(n, v) if k == name else v
                   for k, v in leaves.items()}
            for n, (kind, leaves) in enumerate(params["layers"].items())}}

    def verdict(params, host, tokens, seed, program_params=None,
                reference_params=None):
        """The cell's checks: the program on ``program_params`` (``params``
        unless given), the reference on ``reference_params`` (else
        ``params``)."""
        got = params if program_params is None else program_params
        gaps = train_hybrid.compare(
            model, reference, cfg, got, tokens, host, seed=seed,
            reference_params=(params if reference_params is None
                              else reference_params))
        loss, counts, after = first_step()(got, tokens)
        want = reference.updated_bias(
            cfg, reference.router_biases(cfg, got), np.asarray(counts))
        checks = train_hybrid.checks_of(
            chk, float(loss), gaps,
            float(np.abs(reference.router_biases(cfg, after) - want).max()),
            gaps["choices"]["own_regret"])
        tolerances.update((k, t) for k, (_, t) in checks.items())
        return {"correct": all(v <= t for v, t in checks.values()),
                "refused_by": [k for k, (v, t) in checks.items() if v > t],
                "readings": {k: v for k, (v, _) in checks.items()},
                "gradient, every leaf": gaps["gradient_gap"]}

    def replanted():
        """Forget what was traced under another ``moe.route``."""
        train_hybrid._program.cache_clear()
        first_step.cache_clear()

    seed = seeds[0]
    params, host, tokens = inputs(seed)
    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": verdict(params, host, tokens, seed),
           "reference_float8": verdict(
               params, host, tokens, seed,
               reference_params=jax.tree_util.tree_map(lower, params)),
           "program_with_taps_reversed": verdict(
               params, host, tokens, seed,
               program_params=on_layers(params, "w_conv",
                                        lambda n, w: w[..., ::-1]))}
    biased = on_layers(params, "router_bias", lambda n, b: (
        0.05 * jax.random.normal(jax.random.PRNGKey(seed % 2 ** 31 + n),
                                 b.shape)))
    out["bias_seeded_program"] = verdict(biased, host, tokens, seed)
    honest = moe.route
    moe.route = lambda *a, select_bias=None, **kw: honest(*a, **kw)
    replanted()
    try:
        out["bias_seeded_program_with_the_bias_left_out_of_route"] = verdict(
            biased, host, tokens, seed)
    finally:
        moe.route = honest
        replanted()
    del biased
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        params, host, tokens = inputs(seed)
        out["program_at_other_seeds"][seed] = verdict(params, host, tokens,
                                                      seed)
    out["tolerances"] = tolerances
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hybrid_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
