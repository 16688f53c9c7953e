"""On the chip, in one process: the verdict of a ``train_delta_moe`` cell's
check (``train_delta_moe.checks_of``, the dict ``run()`` decides ``correct``
from) on the honest program and on eight controls, each of which it has to
refuse: (a) the reference computed one precision lower (its weights rounded
to float8 e4m3's three mantissa bits where they are used,
``qwen3_next_ref.token_nll(mantissa_bits=3)``); (b) a train step that hands
on the state it was given; and six faults planted while the program traces,
for none of which the program has an option the configuration sets: (c)
``beta = 2 sigmoid(b)`` (``ops/delta._gates``); (d) value head ``i`` reading
key head ``i mod 16`` for ``i // 2`` (``ops/delta._join_heads``); (e) rope
on the whole head (the config's ``partial_rotary_factor`` at 1); (f) the
attention gate left off (``models/llama._wide_gated``); (g) the shared
expert ungated (``ops/moe._token_gated``); (h) the norms' ``1 + w`` as ``w``
(the config's ``zero_centred_norm`` off). Same weights, same batch 0 as the
cell with this seed; what the first step handed on is the cell's own
``make_step``'s, run once a seed. The controls that plant a fault in the
forward are read without the first step's gaps: the forward's limits have
to refuse them. ``--seed`` given again adds the honest program's verdict at
that seed (the range a tolerance is set from), with no control.

    python3 benchmark/tests/delta_moe_limits.py --seed 17 [--seed 18 ...]
    python3 benchmark/tests/delta_moe_limits.py --tiny      (CPU rehearsal)
    python3 benchmark/tests/delta_moe_limits.py --honest-only --seed 7 ...

Prints one JSON object and writes it to
``chiprun_out/delta_moe_limits.json``: for each reading ``correct``,
``refused_by`` (the checks over their tolerance) and ``readings``.
"""
import argparse
import json
import os
import sys
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# --tiny: Qwen3NextConfig.tiny() holding experts 4-7, float32, on the CPU,
# under limits a rounding passes and a fault does not
TINY = {"module": "qwen3_next", "preset": "tiny", "dtype": "float32",
        "param_dtype": "float32", "experts_held": [4, 4]}
_KINDS = ("linear", "full", "top")
TINY_TRAFFIC = {
    "batch": 1, "seq": 32, "host_batches": 2, "lr": 1e-4,
    "lr_warmup_steps": 2000,
    "check": {"loss_tolerance": 1e-4, "token_nll_rms_tolerance": 1e-4,
              "token_nll_max_tolerance": 1e-3,
              "state_abs_max_tolerance": 1e-4,
              "state_head_gap_tolerance": 1e-4,
              "router_logit_rms_tolerance": 1e-4,
              "router_logit_max_tolerance": 1e-3,
              "differing_choice_share_tolerance": 0.0,
              "choice_regret_tolerance": 0.0,
              "first_step_moment_tolerance": dict.fromkeys(_KINDS, 1e-4),
              "first_step_param_tolerance": 0.0,
              "gradient_gap_tolerance": dict.fromkeys(_KINDS, 1e-4)}}


def _planted(reading, module, **fault):
    """``reading()`` with ``fault`` (attributes of ``module``) in place
    while it traces; the honest trace is forgotten before and after."""
    from benchmark.cells import train_delta_moe

    honest = {name: getattr(module, name) for name in fault}
    for name, value in fault.items():
        setattr(module, name, value)
    train_delta_moe._program.cache_clear()
    try:
        return reading()
    finally:
        for name, value in honest.items():
            setattr(module, name, value)
        train_delta_moe._program.cache_clear()


def with_doubled_beta(reading):
    """``beta = 2 sigmoid(b)``: Olmo-Hybrid's, eigenvalues in (-1, 1)."""
    from ray_tpu.ops import delta

    honest = delta._gates

    def gates(*a):
        g, beta = honest(*a)
        return g, 2.0 * beta

    return _planted(reading, delta, _gates=gates)


def with_key_heads_tiled(reading):
    """Value head ``i`` reads key head ``i mod key heads``."""
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    return _planted(reading, delta, _join_heads=lambda x, heads: jnp.tile(
        x, (1, 1, heads // x.shape[2], 1)))


def without_attention_gate(reading):
    """Each head's output as the kernels leave it."""
    from ray_tpu.models import llama

    return _planted(reading, llama, _wide_gated=lambda attn, gate: attn)


def without_shared_gate(reading):
    """The shared expert added ungated, as Laguna's is."""
    from ray_tpu.ops import moe

    return _planted(reading, moe, _token_gated=lambda out, u, w: out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-qwen3-next-1chip")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--honest-only", action="store_true",
                    help="no control: the honest program at every seed")
    a = ap.parse_args()
    seeds = a.seed or [17]
    if a.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.cells import train_delta_moe as cell
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = {**ctx["traffic"], **(TINY_TRAFFIC if a.tiny else {})}
    model, reference, cfg = cell.load_model(
        TINY if a.tiny else ctx["config"]["model_config"])
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = cell.optimizer(tr)
    step = jax.jit(cell.make_step(model, cfg, tx), donate_argnums=(0, 1))

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
        left = cell.first_step_left(reference, params, opt)
        del params, opt
        return init(key), host, tokens, left

    chk = tr["check"]
    tolerances = {}

    def verdict(params, host, tokens, seed, left=None, program_cfg=None,
                reference_mantissa_bits=None):
        """The cell's checks: the program (at ``program_cfg`` if given) and
        the reference on ``params``, the reference rounded to
        ``reference_mantissa_bits`` if given; with ``left``, what a first
        step handed on, its gaps too."""
        gaps = cell.compare(
            model, reference, cfg, params, tokens, host, seed=seed,
            program_cfg=program_cfg,
            reference_mantissa_bits=reference_mantissa_bits,
            first_step=None if left is None else (tx, left))
        # the first step's loss is the mean of what the forward gave and
        # the router's term, which the reference's choices settle
        terms = gaps["ref_terms"]
        checks = cell.checks_of(
            chk, gaps["program_cross_entropy"] + terms["loss"]
            - terms["cross_entropy"], gaps)
        tolerances.update((k, t) for k, (_, t) in checks.items())
        # (a reading that is not a number is over every tolerance)
        return {"correct": all(v <= t for v, t in checks.values()),
                "refused_by": [k for k, (v, t) in checks.items()
                               if not v <= t],
                "readings": {k: v for k, (v, _) in checks.items()},
                "state_abs_max": gaps["state_abs_max"],
                "state_head_gap": gaps["state_head_gap"],
                "router_logit_gap": gaps["router_logit_gap"],
                "choices": gaps["choices"],
                "gradient, every leaf": gaps["gradient_gap"],
                "first step, every leaf": gaps.get("first_step")}

    seed = seeds[0]
    params, host, tokens, left = inputs(seed)
    unchanged = {"params": jax.device_get(reference.first_layers(params)),
                 "mu": jax.tree_util.tree_map(np.zeros_like, left["mu"])}

    def forward_alone(program_cfg=None):
        return verdict(params, host, tokens, seed, program_cfg=program_cfg)

    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": verdict(params, host, tokens, seed, left)}
    if not a.honest_only:
        out.update({
           "reference_float8": verdict(params, host, tokens, seed, left,
                                       reference_mantissa_bits=3),
           "step_that_hands_on_what_it_was_given": verdict(
               params, host, tokens, seed, unchanged),
           "program_with_beta_doubled": with_doubled_beta(forward_alone),
           "program_with_key_heads_tiled": with_key_heads_tiled(
               forward_alone),
           "program_with_rope_on_the_whole_head": forward_alone(
               replace(cfg, partial_rotary_factor=1.0)),
           "program_without_the_attention_gate": without_attention_gate(
               forward_alone),
           "program_with_the_shared_expert_ungated": without_shared_gate(
               forward_alone),
           "program_with_norms_scaled_by_w_alone": forward_alone(
               replace(cfg, zero_centred_norm=False))})
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        params, host, tokens, left = inputs(seed)
        out["program_at_other_seeds"][seed] = verdict(params, host, tokens,
                                                      seed, left)
    out["tolerances"] = tolerances
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = ("delta_moe_limits.tiny.json" if a.tiny else
            "delta_moe_limits.honest.json" if a.honest_only else
            "delta_moe_limits.json")
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
