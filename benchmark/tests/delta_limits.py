"""On the chip, in one process: the verdict of a ``train_delta`` cell's
check (``train_delta.checks_of``, the dict ``run()`` decides ``correct``
from) on the honest program and on eight controls, each of which it has to
refuse: (a) the reference computed one precision lower (its weights
rounded to float8 e4m3's three mantissa bits where they are used,
``olmo_hybrid_ref.token_nll(mantissa_bits=3)``); (b) the program with
``beta = sigmoid(b)``, without the factor two of
``linear_allow_neg_eigval``; (c) the program with the rule's state not
carried from chunk to chunk (one chunk a step of the walk, and
``_walk_step`` handed zeros for the state it was to start from); (d) the
program with the rule's running sums, decays and carried state rounded to
bfloat16 where they are formed; (e) the program with q and k not normed;
(f) the program with ``I - A`` in place of ``(I + A)^-1``; (g) the program
with the full layer's softmax scale at ``1 / head_dim``; (h) a train step
that hands on the state it was given (the parameters as they were, a first
moment of zeros). (b) to (g) are planted in ``ops/delta.py`` and
``models/llama.py`` themselves while the program traces: the program has no
option for any of them. Same weights, same batch 0 as the cell with this
seed; what the first step handed on is the cell's own ``make_step``'s, run
once a seed. The controls that plant a fault in the forward are read
without the first step's gaps: the forward's limits have to refuse them.
``--seed`` given again adds the honest program's verdict at that seed (the
range a tolerance is set from), with no control.

    python3 benchmark/tests/delta_limits.py --seed 17 [--seed 18 ...]

Prints one JSON object and writes it to ``chiprun_out/delta_limits.json``:
for each reading ``correct``, ``refused_by`` (the checks over their
tolerance) and ``readings``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _planted(reading, module=None, **fault):
    """``reading()`` with ``fault`` (attributes of ``ops/delta.py``, or of
    ``module``) in place while it traces; the honest trace is forgotten
    before and after."""
    from benchmark.cells import train_scan
    from ray_tpu.ops import delta

    module = module or delta
    honest = {name: getattr(module, name) for name in fault}
    for name, value in fault.items():
        setattr(module, name, value)
    train_scan._program.cache_clear()
    try:
        return reading()
    finally:
        for name, value in honest.items():
            setattr(module, name, value)
        train_scan._program.cache_clear()


def with_half_beta(reading):
    """``beta = sigmoid(b)``: eigenvalues in (0, 1) alone."""
    from ray_tpu.ops import delta

    honest = delta._gates

    def gates(*a):
        g, beta = honest(*a)
        return g, 0.5 * beta

    return _planted(reading, _gates=gates)


def without_carry(reading):
    """Every chunk of the rule started from zeros (one chunk a step of the
    walk)."""
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    honest = delta._walk_step
    return _planted(
        reading, WALK_BYTES=0,
        _walk_step=lambda S, *a: honest(jnp.zeros_like(S), *a))


def with_bfloat16_decays(reading):
    """The rule's own numbers rounded to bfloat16's eight bits where
    ``_walk_step`` forms them: every running sum (``cumsum``), every decay
    (``exp``) and the state a chunk starts from (one chunk a step of the
    walk). ``lax.reduce_precision`` and not a cast there and back, which a
    compiler may drop."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    class Rounding:
        """``jax.numpy`` as ``ops/delta.py`` sees it, two functions
        rounding their results."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        def cumsum(self, *a, **kw):
            return rounded(jnp.cumsum(*a, **kw))

        def exp(self, *a, **kw):
            return rounded(jnp.exp(*a, **kw))

    honest = delta._walk_step
    return _planted(reading, jnp=Rounding(), WALK_BYTES=0,
                    _walk_step=lambda S, *a: honest(rounded(S), *a))


def without_qk_norm(reading):
    """q and k as the taps leave them (q still times ``K ** -0.5``)."""
    return _planted(reading, l2_norm=lambda x, eps=0.0, scale=1.0: x * scale)


def with_first_order_inverse(reading):
    """``I - A`` where ``(I + A)^-1`` belongs: the sum cut after its
    second term."""
    import jax.numpy as jnp

    return _planted(
        reading,
        _unit_lower_inverse=lambda A: jnp.eye(A.shape[-1], dtype=A.dtype) - A)


def with_softmax_scale(reading, scale):
    """The full layer's scores times ``scale``."""
    from ray_tpu.models import llama

    honest = llama._attend
    return _planted(
        reading, module=llama,
        _attend=lambda *a, **kw: honest(*a, **{**kw, "sm_scale": scale}))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-olmo-hybrid-1chip")
    ap.add_argument("--seed", type=int, action="append")
    a = ap.parse_args()
    seeds = a.seed or [17]
    import jax
    import numpy as np

    from benchmark.cells import train_delta
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    model, reference, cfg = train_delta.load_model(
        ctx["config"]["model_config"])
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = train_delta.optimizer(tr)
    step = jax.jit(train_delta.make_step(model, cfg, tx),
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
        left = train_delta.first_step_left(reference, params, opt)
        del params, opt
        return init(key), host, tokens, left

    chk = tr["check"]
    tolerances = {}

    def verdict(params, host, tokens, seed, left=None,
                reference_mantissa_bits=None):
        """The cell's checks: the program and the reference on ``params``,
        the reference rounded to ``reference_mantissa_bits`` if given; with
        ``left``, what a first step handed on, its gaps too."""
        gaps = train_delta.compare(
            model, reference, cfg, params, tokens, host, seed=seed,
            reference_mantissa_bits=reference_mantissa_bits,
            first_step=None if left is None else (tx, left))
        # the first step's loss is the mean of what the forward gave
        checks = train_delta.checks_of(chk, gaps["program_loss"], gaps)
        tolerances.update((k, t) for k, (_, t) in checks.items())
        # (a reading that is not a number is over every tolerance)
        return {"correct": all(v <= t for v, t in checks.values()),
                "refused_by": [k for k, (v, t) in checks.items()
                               if not v <= t],
                "readings": {k: v for k, (v, _) in checks.items()},
                "state_abs_max": gaps["state_abs_max"],
                "state_head_gap": gaps["state_head_gap"],
                "gradient, every leaf": gaps["gradient_gap"],
                "first step, every leaf": gaps.get("first_step")}

    seed = seeds[0]
    params, host, tokens, left = inputs(seed)
    unchanged = {"params": jax.device_get(reference.first_layers(params)),
                 "mu": jax.tree_util.tree_map(np.zeros_like, left["mu"])}

    def forward_alone():
        return verdict(params, host, tokens, seed)

    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": verdict(params, host, tokens, seed, left),
           "reference_float8": verdict(params, host, tokens, seed, left,
                                       reference_mantissa_bits=3),
           "step_that_hands_on_what_it_was_given": verdict(
               params, host, tokens, seed, unchanged),
           "program_with_beta_without_its_two": with_half_beta(forward_alone),
           "program_without_the_carried_state": without_carry(forward_alone),
           "program_with_bfloat16_decays": with_bfloat16_decays(
               forward_alone),
           "program_without_the_qk_norm": without_qk_norm(forward_alone),
           "program_with_i_minus_a_for_the_inverse":
               with_first_order_inverse(forward_alone),
           "program_with_the_scale_of_one_over_the_head_size":
               with_softmax_scale(forward_alone, 1.0 / cfg.head_dim_)}
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        params, host, tokens, left = inputs(seed)
        out["program_at_other_seeds"][seed] = verdict(params, host, tokens,
                                                      seed, left)
    out["tolerances"] = tolerances
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "delta_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
