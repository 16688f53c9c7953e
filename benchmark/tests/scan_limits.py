"""On the chip, in one process: the verdict of a ``train_scan`` cell's
check (``train_scan.checks_of``, the dict ``run()`` decides ``correct``
from) on the honest program and on seven controls, each of which it has to
refuse: (a) the reference computed one precision lower (its weights
rounded to float8 e4m3's three mantissa bits where they are used,
``granite_ref.token_nll(mantissa_bits=3)``); (b) the program with the
scan's state not carried from chunk to chunk, the fault planted in
``ops/ssm.py`` itself (one chunk a step of the walk, and ``_walk_step``
handed zeros for the state it was to start from); (c) the program with
``dt_bias`` left out (zeros in its place); (d) the program with the skip
``D`` left out; (e) the program with the
softmax scale at ``head_dim ** -0.5`` (1/8) in place of
``attention_multiplier`` (1/64); (f) the program with the scan's running
sums, decays and carried state rounded to bfloat16 where they are formed
(planted in ``ops/ssm.py`` too: what a kernel that lowered the scan's own
precision would compute); (g) a train step that hands on the state it was
given (the parameters as they were, a first moment of zeros). Same
weights, same batch 0 as the cell with this seed; what the first step
handed on is the cell's own ``make_step``'s, run once a seed. The controls
that plant a fault in the forward are read without the first step's gaps:
the forward's limits have to refuse them. ``--seed`` given again
adds the honest program's verdict at that seed (the range a tolerance is
set from), with no control.

    python3 benchmark/tests/scan_limits.py --workload train-granite-1chip --seed 17 [--seed 18 ...]

Prints one JSON object and writes it to ``chiprun_out/scan_limits.json``:
for each reading ``correct``, ``refused_by`` (the checks over their
tolerance) and ``readings``.
"""
import argparse
import json
import os
import sys
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _planted(reading, **fault):
    """``reading()`` with ``fault`` (attributes of ``ops/ssm.py``) in place
    while it traces; the honest trace is forgotten before and after."""
    from benchmark.cells import train_scan
    from ray_tpu.ops import ssm

    honest = {name: getattr(ssm, name) for name in fault}
    for name, value in fault.items():
        setattr(ssm, name, value)
    train_scan._program.cache_clear()
    try:
        return reading()
    finally:
        for name, value in honest.items():
            setattr(ssm, name, value)
        train_scan._program.cache_clear()


def without_carry(reading):
    """``reading()`` with every chunk of the scan started from zeros (one
    chunk a step of the walk)."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    honest = ssm._walk_step
    return _planted(
        reading, WALK_BYTES=0,
        _walk_step=lambda S, *a: honest(jnp.zeros_like(S), *a))


def with_bfloat16_decays(reading):
    """``reading()`` with the scan's own numbers rounded to bfloat16's
    eight bits where ``_walk_step`` forms them: every running sum
    (``cumsum``), every decay (``exp``) and the state a step starts from.
    ``lax.reduce_precision`` and not a cast there and back, which a
    compiler may drop."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    class Rounding:
        """``jax.numpy`` as ``ops/ssm.py`` sees it, two functions
        rounding their results."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        def cumsum(self, *a, **kw):
            return rounded(jnp.cumsum(*a, **kw))

        def exp(self, *a, **kw):
            return rounded(jnp.exp(*a, **kw))

    honest = ssm._walk_step
    return _planted(reading, jnp=Rounding(),
                    _walk_step=lambda S, *a: honest(rounded(S), *a))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-granite-1chip")
    ap.add_argument("--seed", type=int, action="append")
    a = ap.parse_args()
    seeds = a.seed or [17]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.cells import train_scan
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    model, reference, cfg = train_scan.load_model(
        ctx["config"]["model_config"])
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = train_scan.optimizer(tr)
    step = jax.jit(train_scan.make_step(model, cfg, tx),
                   donate_argnums=(0, 1))

    def inputs(seed):
        """The cell's weights and batch 0 at ``seed``, and what its train
        step hands on from them."""
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)[0]
        key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        tokens = jax.device_put(host)
        params = init(key)
        params, opt, *_ = step(params, tx.init(params), {"tokens": tokens})
        left = train_scan.first_step_left(reference, params, opt)
        del params, opt
        return init(key), host, tokens, left

    chk = tr["check"]
    tolerances = {}

    def without(params, name):
        """``params`` with zeros in place of the scan layers' ``name``."""
        return {**params, "layers": {
            kind: {k: jnp.zeros_like(v) if k == name else v
                   for k, v in leaves.items()}
            for kind, leaves in params["layers"].items()}}

    def verdict(params, host, tokens, seed, left=None, program_params=None,
                program_cfg=None, reference_mantissa_bits=None):
        """The cell's checks: the program on ``program_params`` (``params``
        unless given) under ``program_cfg`` (the cell's unless given), the
        reference on ``params``, rounded to ``reference_mantissa_bits`` if
        given; with ``left``, what a first step handed on, its gaps too."""
        got = params if program_params is None else program_params
        gaps = train_scan.compare(
            model, reference, cfg, got, tokens, host, seed=seed,
            program_cfg=program_cfg, reference_params=params,
            reference_mantissa_bits=reference_mantissa_bits,
            first_step=None if left is None else (tx, left))
        # the first step's loss is the mean of what the forward gave
        checks = train_scan.checks_of(chk, gaps["program_loss"], gaps)
        tolerances.update((k, t) for k, (_, t) in checks.items())
        return {"correct": all(v <= t for v, t in checks.values()),
                "refused_by": [k for k, (v, t) in checks.items() if v > t],
                "readings": {k: v for k, (v, _) in checks.items()},
                "state_abs_max": gaps["state_abs_max"],
                "state_head_gap": gaps["state_head_gap"],
                "gradient, every leaf": gaps["gradient_gap"],
                "first step, every leaf": gaps.get("first_step")}

    seed = seeds[0]
    params, host, tokens, left = inputs(seed)
    unchanged = {"params": jax.device_get(reference.first_layers(params)),
                 "mu": jax.tree_util.tree_map(np.zeros_like, left["mu"])}
    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": verdict(params, host, tokens, seed, left),
           "reference_float8": verdict(
               params, host, tokens, seed, left,
               reference_mantissa_bits=3),
           "step_that_hands_on_what_it_was_given": verdict(
               params, host, tokens, seed, unchanged),
           "program_without_the_carried_state": without_carry(
               lambda: verdict(params, host, tokens, seed)),
           "program_with_bfloat16_decays": with_bfloat16_decays(
               lambda: verdict(params, host, tokens, seed)),
           "program_without_dt_bias": verdict(
               params, host, tokens, seed,
               program_params=without(params, "dt_bias")),
           "program_without_D": verdict(
               params, host, tokens, seed,
               program_params=without(params, "D")),
           "program_with_the_scale_of_the_head_size": verdict(
               params, host, tokens, seed,
               program_cfg=replace(cfg, attention_multiplier=(
                   cfg.head_dim_ ** -0.5)))}
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        params, host, tokens, left = inputs(seed)
        out["program_at_other_seeds"][seed] = verdict(params, host, tokens,
                                                      seed, left)
    out["tolerances"] = tolerances
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "scan_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
