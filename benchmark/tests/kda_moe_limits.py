"""On the chip, in one process: the verdict of a ``train_kda_moe`` cell's
check (``train_kda_moe.checks_of``, the dict ``run()`` decides ``correct``
from) on the honest program and on eleven controls, each of which it has to
refuse: (a) the reference computed one precision lower (its weights rounded
to float8 e4m3's three mantissa bits where they are used,
``ling3_ref.token_nll(mantissa_bits=3)``); (b) a train step that hands on
the state it was given; and nine faults planted while the program traces,
for none of which the program has an option the configuration sets: (c) a
head's decay the mean of its key channels (``ops/delta._channel_gates``:
what proves the channels reach the rule); (d) the lower bound dropped, ``g
= -exp(A_log) softplus(f + dt_bias)``; (e) ``beta = 2 sigmoid(b)``; (f) q
and k not normed (``ops/delta.l2_norm``); (g) the head-wise gate left off
in the KDA layers (``ops/delta._head_gated``); (h) and in the latent layer
(``ops/mla.head_gate``); (i) no rope (``ops/mla._rotate``); (j) the group
limit ignored (``ops/moe.route_choice``: the 8 largest ``s + b`` of all
512); (k) the bias added to the weights (``ops/moe.route``). The last two
are read on weights whose routers' biases are seeded with a spread of 0.05,
a window's worth of steps of one sign (program and reference alike: the
honest program on them is ``program_with_biases``): at the first step a bias
is 0 and takes no part. A bias in the weights moves the held experts'
weights by a tenth there and no reading against the reference by more than
its seed does (the held experts are 1/64 of the routed output): what refuses
it is the weights ``route`` gave against the rule on the program's own
logits (``train_kda_moe.own_weight_gap``), whatever the biases' size. At a
spread of 0.5 (read once, PR 60) the biases alone decide the choice, the
held experts of a layer get no row, their leaves' gradients are 0 over 0
and the honest program is refused with the controls: not a size to read
anything at. Same weights, same batch 0 as
the cell with this seed; what the first step handed on is the cell's own
``make_step``'s, run once a seed. The controls that plant a fault in the
forward are read without the first step's gaps: the forward's limits have
to refuse them. ``--seed`` given again adds the honest program's verdict at
that seed (the range a tolerance is set from), with no control.

    python3 benchmark/tests/kda_moe_limits.py --seed 17 [--seed 18 ...]
    python3 benchmark/tests/kda_moe_limits.py --tiny      (CPU rehearsal)
    python3 benchmark/tests/kda_moe_limits.py --honest-only --seed 7 ...
    python3 benchmark/tests/kda_moe_limits.py --only <control> ...

Prints one JSON object and writes it to
``chiprun_out/kda_moe_limits.json``: for each reading ``correct``,
``refused_by`` (the checks over their tolerance) and ``readings``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# --tiny: Ling3Config.tiny() holding experts 4-7, float32, on the CPU,
# under limits a rounding passes and a fault does not
TINY = {"module": "ling3", "preset": "tiny", "dtype": "float32",
        "param_dtype": "float32", "experts_held": [4, 4]}
_KINDS = ("kda+dense", "kda+moe", "mla+moe", "top")
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
              "own_choice_regret_tolerance": 1e-6,
              "own_weight_gap_tolerance": 1e-6,
              "router_bias_tolerance": 0.0,
              "first_step_moment_tolerance": dict.fromkeys(_KINDS, 1e-4),
              "first_step_param_tolerance": 0.0,
              "gradient_gap_tolerance": dict.fromkeys(_KINDS, 1e-4),
              "gradient_gap_median_tolerance": dict.fromkeys(_KINDS, 1e-4)}}


def _planted(reading, module, **fault):
    """``reading()`` with ``fault`` (attributes of ``module``) in place
    while it traces; the honest trace is forgotten before and after."""
    from benchmark.cells import train_kda_moe

    honest = {name: getattr(module, name) for name in fault}
    for name, value in fault.items():
        setattr(module, name, value)
    train_kda_moe._program.cache_clear()
    try:
        return reading()
    finally:
        for name, value in honest.items():
            setattr(module, name, value)
        train_kda_moe._program.cache_clear()


def _gates(change):
    """A control of the KDA gate: ``change(g, beta, f, p) -> (g, beta)``
    after the honest ``_channel_gates``."""
    def control(reading):
        from ray_tpu.ops import delta

        honest = delta._channel_gates

        def gates(f, b_, p, lower):
            g, beta = honest(f, b_, p, lower)
            return change(g, beta, f, p)

        return _planted(reading, delta, _channel_gates=gates)
    return control


def _mean_decay(g, beta, f, p):
    import jax.numpy as jnp

    return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta


def _unbounded(g, beta, f, p):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    H, K = f.shape[-2:]
    return (-jnp.exp(p["k_A_log"].astype(f32))[:, None] * jax.nn.softplus(
        f.astype(f32) + p["k_dt_bias"].astype(f32).reshape(H, K)), beta)


def without_qk_norms(reading):
    from ray_tpu.ops import delta

    return _planted(reading, delta, l2_norm=lambda x, eps=1e-6, scale=1.0: (
        x * scale).astype(x.dtype))


def without_kda_gate(reading):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    def ungated(o, gate, weight, eps):
        of = o.astype(jnp.float32)
        return (of * jax.lax.rsqrt(jnp.mean(jnp.square(of), -1, keepdims=True)
                                   + eps) * weight.astype(jnp.float32)
                ).astype(o.dtype)

    return _planted(reading, delta, _head_gated=ungated)


def without_latent_gate(reading):
    import jax.numpy as jnp

    from ray_tpu.ops import mla

    return _planted(reading, mla, head_gate=lambda cfg, u, wg: jnp.ones(
        u.shape[:-1] + (wg.shape[-1],), jnp.float32))


def without_rope(reading):
    from ray_tpu.ops import mla

    return _planted(reading, mla, _rotate=lambda x, cos, sin: x)


def without_group_limit(reading):
    import jax

    from ray_tpu.ops import moe

    def plain(scores, select_bias, top_k, groups, group_score):
        return jax.lax.top_k(jax.lax.stop_gradient(
            scores + select_bias.astype(scores.dtype)), top_k)[1]

    return _planted(reading, moe, route_choice=plain)


def with_bias_in_the_weights(reading):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    honest = moe.route

    def route(x, router_w, top_k, renormalize=False, scale=1.0, **how):
        logits, _, top_e = honest(x, router_w, top_k, renormalize, scale,
                                  **how)
        top_w = jnp.take_along_axis(
            jax.nn.sigmoid(logits) + how["select_bias"].astype(jnp.float32),
            top_e, axis=-1)
        top_w = top_w / (top_w.sum(-1, keepdims=True) + how["renorm_eps"])
        return logits, top_w * scale, top_e

    return _planted(reading, moe, route=route)


CONTROLS = {
    "program_with_a_heads_decay_the_mean_of_its_channels": _gates(_mean_decay),
    "program_without_the_lower_bound": _gates(_unbounded),
    "program_with_beta_doubled": _gates(
        lambda g, beta, f, p: (g, 2.0 * beta)),
    "program_with_q_and_k_not_normed": without_qk_norms,
    "program_without_the_kda_gate": without_kda_gate,
    "program_without_the_latent_gate": without_latent_gate,
    "program_without_rope": without_rope}
BIASED_CONTROLS = {
    "program_without_the_group_limit": without_group_limit,
    "program_with_the_bias_in_the_weights": with_bias_in_the_weights}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-ling3-flash-1chip")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--honest-only", action="store_true",
                    help="no control: the honest program at every seed")
    ap.add_argument("--only", action="append",
                    help="these controls alone (and the honest program)")
    a = ap.parse_args()
    seeds = a.seed or [17]
    if a.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.cells import train_kda_moe as cell
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = {**ctx["traffic"], **(TINY_TRAFFIC if a.tiny else {})}
    model, reference, cfg = cell.load_model(
        TINY if a.tiny else ctx["config"]["model_config"])
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = cell.optimizer(tr)
    step = jax.jit(cell.make_step(model, cfg, tx), donate_argnums=(0, 1))

    def inputs(seed):
        """The cell's weights and batch 0 at ``seed``, what its train step
        hands on from them and how far the biases it hands on lie from the
        rule's."""
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)[0]
        key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        tokens = jax.device_put(host)
        params = init(key)
        bias0 = reference.router_biases(cfg, params)
        params, opt, _, aux = step(params, tx.init(
            cell.model_parts(model)[0](params)), {"tokens": tokens})
        left = cell.first_step_left(reference, params, opt)
        bias_gap = float(np.abs(
            reference.router_biases(cfg, params) - reference.updated_bias(
                cfg, bias0, np.asarray(aux["expert_counts"]))).max())
        del params, opt
        return init(key), host, tokens, left, bias_gap

    chk = tr["check"]
    tolerances = {}

    def verdict(params, host, tokens, seed, left=None, bias_gap=None,
                reference_mantissa_bits=None):
        """The cell's checks: the program and the reference on ``params``,
        the reference rounded to ``reference_mantissa_bits`` if given; with
        ``left``, what a first step handed on, its gaps too."""
        gaps = cell.compare(
            model, reference, cfg, params, tokens, host, seed=seed,
            reference_mantissa_bits=reference_mantissa_bits,
            first_step=None if left is None else (tx, left))
        checks = cell.checks_of(chk, gaps["program_cross_entropy"], gaps,
                                bias_gap)
        tolerances.update((k, t) for k, (_, t) in checks.items())
        # (a reading that is not a number is over every tolerance)
        return {"correct": all(v <= t for v, t in checks.values()),
                "refused_by": [k for k, (v, t) in checks.items()
                               if not v <= t],
                "readings": {k: v for k, (v, _) in checks.items()},
                "state_abs_max": gaps["state_abs_max"],
                "state_head_gap": gaps["state_head_gap"],
                "log_decay_min": gaps["log_decay_min"],
                "router_logit_gap": gaps["router_logit_gap"],
                "choices": gaps["choices"],
                "gradient, every leaf": gaps["gradient_gap"],
                "first step, every leaf": gaps.get("first_step")}

    seed = seeds[0]
    params, host, tokens, left, bias_gap = inputs(seed)
    unchanged = {"params": jax.device_get(reference.first_layers(params)),
                 "mu": jax.tree_util.tree_map(np.zeros_like, left["mu"])}

    def forward_alone(on=None):
        return verdict(on or params, host, tokens, seed)

    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": verdict(params, host, tokens, seed, left, bias_gap)}
    wanted = lambda name: not a.only or name in a.only     # noqa: E731
    if not a.honest_only:
        if wanted("reference_float8"):
            out["reference_float8"] = verdict(
                params, host, tokens, seed, left, bias_gap,
                reference_mantissa_bits=3)
        if wanted("step_that_hands_on_what_it_was_given"):
            out["step_that_hands_on_what_it_was_given"] = verdict(
                params, host, tokens, seed, unchanged, bias_gap)
        for name, control in CONTROLS.items():
            if wanted(name):
                out[name] = control(forward_alone)
        if any(map(wanted, ("program_with_biases", *BIASED_CONTROLS))):
            keys = iter(jax.random.split(jax.random.PRNGKey(seed + 5), 8))
            biased = {**params, "layers": {
                kind: ({**leaves, "router_bias": 0.05 * jax.random.normal(
                    next(keys), leaves["router_bias"].shape)}
                       if "router_bias" in leaves else leaves)
                for kind, leaves in params["layers"].items()}}
            out["program_with_biases"] = forward_alone(biased)
            for name, control in BIASED_CONTROLS.items():
                if wanted(name):
                    out[name] = control(lambda: forward_alone(biased))
            del biased
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        params, host, tokens, left, bias_gap = inputs(seed)
        out["program_at_other_seeds"][seed] = verdict(
            params, host, tokens, seed, left, bias_gap)
    out["tolerances"] = tolerances
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = ("kda_moe_limits.tiny.json" if a.tiny else
            "kda_moe_limits.honest.json" if a.honest_only else
            "kda_moe_limits.only.json" if a.only else
            "kda_moe_limits.json")
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
