"""On the chip, in one process: the verdict of a ``train_scan_moe`` cell's
check (``train_scan_moe.checks_of``, the dict ``run()`` decides ``correct``
from) on the honest program and on nine controls, each of which it has to
refuse: (a) the reference computed one precision lower (its weights rounded
to float8 e4m3's three mantissa bits where they are used,
``nemotron_h_ref.token_nll(mantissa_bits=3)``); (b) a train step that hands
on the state it was given; (c) a train step whose loss lacks the module's
term (the config's ``mtp_loss_scale`` at 0); and six faults planted while
the program traces, for none of which the program has an option the
configuration sets: (d) the gated norm over all channels as one group
(``ops/ssm.mamba2_mixer``'s ``norm_groups`` forced to 1); (e) the scan's
running sums, decays and state rounded to bfloat16 (the kernels' seams
``_kernel_state``, ``_kernel_sums`` and the module's ``jnp.exp``, and the
walk's on the CPU); (f) experts of ``silu(l W1) W2`` for ``relu(l W1)^2 W2``
(``ops/moe._relu2_rows``); (g) the latent projections left out: the first
1,024 columns of ``u`` for ``u W_dn`` and the sums padded with zeros for
``r W_up`` (``ops/moe._to_latent``); (h) the module reading the next token
for the one after it (``models/stack.MTP_AHEAD`` at 1); (i) B and C of one
group for all heads (the config's ``ssm_groups`` is what it is: the planted
``mamba2_mixer`` hands every head group 0's). Same weights, same batch 0 as
the cell with this seed; what the first step handed on is the cell's own
``make_step``'s, run once a seed. The controls that plant a fault in the
forward are read without the first step's gaps: the forward's limits have
to refuse them. ``--seed`` given again adds the honest program's verdict at
that seed (the range a tolerance is set from), with no control.
``--spread N`` reads, over ``N`` further seeds, each mixture's held rows
over their balanced share (one forward a seed: what
``held_share_spread.py`` reads for a cell of ``seq + 1`` ids).

    python3 benchmark/tests/scan_moe_limits.py --seed 17 [--seed 18 ...]
    python3 benchmark/tests/scan_moe_limits.py --tiny      (CPU rehearsal)
    python3 benchmark/tests/scan_moe_limits.py --honest-only --seed 7 ...

Prints one JSON object and writes it to
``chiprun_out/scan_moe_limits.json``: for each reading ``correct``,
``refused_by`` (the checks over their tolerance) and ``readings``.
"""
import argparse
import functools
import json
import os
import sys
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _planted(reading, module, **fault):
    """``reading()`` with ``fault`` (attributes of ``module``) in place
    while it traces; the honest trace is forgotten before and after."""
    from benchmark.cells import train_scan_moe

    honest = {name: getattr(module, name) for name in fault}
    for name, value in fault.items():
        setattr(module, name, value)
    train_scan_moe._program.cache_clear()
    try:
        return reading()
    finally:
        for name, value in honest.items():
            setattr(module, name, value)
        train_scan_moe._program.cache_clear()


def with_one_norm_group(reading):
    from ray_tpu.ops import ssm

    honest = ssm.mamba2_mixer
    return _planted(reading, ssm, mamba2_mixer=lambda *a, **kw: honest(
        *a, **{**kw, "norm_groups": 1}))


def with_one_group_of_b_and_c(reading, groups: int):
    """Every head reads group 0's B and C: the mixer is handed the taps'
    output with group 0's channels copied over the other ``groups - 1``."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    honest = ssm.causal_conv_silu

    def taps(*a, sizes=None, **kw):
        x, B, C = honest(*a, sizes=sizes, **kw)
        state = sizes[1] // groups
        return x, *(jnp.tile(a_[:, :state], (1, groups, 1)) for a_ in (B, C))

    return _planted(reading, ssm, causal_conv_silu=taps)


def with_bfloat16_scan(reading):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def cast(a):
        # inside a Mosaic kernel ``reduce_precision`` does not lower
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    class Rounding:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def cumsum(self, *a, **kw):
            return rounded(jnp.cumsum(*a, **kw))

        def exp(self, *a, **kw):
            return cast(jnp.exp(*a, **kw))

    sums, walk = ssm._kernel_sums, ssm._walk_step
    return _planted(reading, ssm, jnp=Rounding(), _kernel_state=cast,
                    _kernel_sums=lambda da: rounded(sums(da)),
                    _walk_step=lambda S, *a: walk(rounded(S), *a))


def with_silu_experts(reading):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    def rows(rows_, w_rows, sizes, e_up, e_down):
        dt = rows_.dtype
        up = moe.grouped_matmul(rows_, e_up.astype(dt), sizes)
        act = jax.nn.silu(up.astype(jnp.float32)) * w_rows[:, None]
        return moe.grouped_matmul(act.astype(dt), e_down.astype(dt), sizes)

    return _planted(reading, moe, _relu2_rows=rows)


def without_latent_projections(reading):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    def cut_or_pad(x, w):
        a, b = w.shape
        return (x[..., :b] if b < a else
                jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, b - a)]))

    return _planted(reading, moe, _to_latent=cut_or_pad)


def with_the_module_reading_the_next_token(reading):
    from ray_tpu.models import stack

    return _planted(reading, stack, MTP_AHEAD=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-nemotron3-super-1chip")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--honest-only", action="store_true",
                    help="no control: the honest program at every seed")
    ap.add_argument("--spread", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="controls to read, by name and comma (all)")
    a = ap.parse_args()
    seeds = a.seed or [17]
    if a.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.cells import train_scan_moe as cell
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr, model_config = ctx["traffic"], ctx["config"]["model_config"]
    if a.tiny:
        from benchmark.tests.rehearse_scan_moe import OVERRIDES
        tr = {**tr, **OVERRIDES["traffic"], "host_batches": 2}
        model_config = OVERRIDES["model_config"]
    model, reference, cfg = cell.load_model(model_config)
    trainable = cell.model_parts(model)[0]
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = cell.optimizer(tr)

    @functools.lru_cache(maxsize=None)
    def step_of(step_cfg):
        return jax.jit(cell.make_step(model, step_cfg, tx),
                       donate_argnums=(0, 1))

    def batch0(seed):
        return np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (tr["host_batches"], tr["batch"],
                                tr["seq"] + tr["ids_ahead"]), np.int32)[0]

    def first_step(seed, step_cfg):
        """What the cell's train step at ``step_cfg`` hands on from the
        cell's weights and batch 0 at ``seed``: (its loss and cross
        entropies, ``first_step_left``'s copies, the routers' biases' gap to
        the reference's rule on the step's own counts)."""
        params = init(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        bias0 = reference.router_biases(cfg, params)
        params, opt, loss, aux = step_of(step_cfg)(
            params, tx.init(trainable(params)),
            {"tokens": jax.device_put(batch0(seed))})
        terms = {"loss": float(loss),
                 "cross_entropy": float(aux["cross_entropy"]),
                 "mtp_cross_entropy": float(aux["mtp_cross_entropy"])}
        want = reference.updated_bias(cfg, bias0,
                                      np.asarray(aux["expert_counts"]))
        bias_gap = float(np.abs(reference.router_biases(cfg, params)
                                - want).max())
        return terms, cell.first_step_left(reference, params, opt), bias_gap

    def inputs(seed):
        host = batch0(seed)
        return (init(jax.random.PRNGKey(seed % (2 ** 31 - 1))), host,
                jax.device_put(host))

    chk = tr["check"]
    tolerances = {}

    def verdict(params, host, tokens, seed, stepped=None, left=None,
                reference_mantissa_bits=None):
        """The cell's checks: the program and the reference on ``params``,
        the reference rounded to ``reference_mantissa_bits`` if given;
        ``stepped`` (``first_step``'s three) adds what a first step handed
        on (``left`` in place of its copies, if given)."""
        terms, own_left, bias_gap = stepped or (None, None, None)
        gaps = cell.compare(
            model, reference, cfg, params, tokens, host, seed=seed,
            reference_mantissa_bits=reference_mantissa_bits,
            first_step=None if stepped is None else (tx, left or own_left))
        if terms is None:
            # a forward's control: the loss is the mean of what it gave
            got = gaps["program_terms"]
            terms = {**got, "loss": got["cross_entropy"]
                     + cfg.mtp_loss_scale * got["mtp_cross_entropy"]}
        checks = cell.checks_of(chk, terms, gaps, bias_gap)
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
                "token_nll_gap": gaps["token_nll_gap"],
                "mtp_nll_gap": gaps["mtp_nll_gap"],
                "gradient, every leaf": gaps["gradient_gap"],
                "first step, every leaf": gaps.get("first_step")}

    seed = seeds[0]
    stepped = first_step(seed, cfg)
    # (a step runs while no second copy of the weights is held: the cell's
    # step is allotted 13.4 of the chip's 16.9 GB)
    stepped_without = None if a.honest_only or (
        a.only and "step_without_the_modules_term" not in a.only
    ) else first_step(seed, replace(cfg, mtp_loss_scale=0.0))
    step_of.cache_clear()
    params, host, tokens = inputs(seed)

    def forward_alone():
        return verdict(params, host, tokens, seed)

    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": verdict(params, host, tokens, seed, stepped)}

    def write():
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        name = ("scan_moe_limits.tiny.json" if a.tiny else
                "scan_moe_limits.honest.json" if a.honest_only else
                "scan_moe_limits.json")
        with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
            json.dump({**out, "tolerances": tolerances}, f, indent=1)

    write()
    if not a.honest_only:
        import jax.tree_util as tu

        unchanged = {
            "params": jax.device_get(reference.first_layers(params)),
            "mu": tu.tree_map(np.zeros_like, stepped[1]["mu"])}
        controls = {
            "reference_float8": lambda: verdict(
                params, host, tokens, seed, stepped,
                reference_mantissa_bits=3),
            "step_that_hands_on_what_it_was_given": lambda: verdict(
                params, host, tokens, seed, stepped, left=unchanged),
            "step_without_the_modules_term": lambda: verdict(
                params, host, tokens, seed, stepped_without),
            "program_with_one_norm_over_all_channels": lambda:
                with_one_norm_group(forward_alone),
            "program_with_one_group_of_b_and_c": lambda:
                with_one_group_of_b_and_c(forward_alone, cfg.ssm_groups),
            "program_with_a_bfloat16_scan": lambda:
                with_bfloat16_scan(forward_alone),
            "program_with_silu_experts": lambda:
                with_silu_experts(forward_alone),
            "program_without_the_latent_projections": lambda:
                without_latent_projections(forward_alone),
            "program_with_the_module_reading_the_next_token": lambda:
                with_the_module_reading_the_next_token(forward_alone)}
        for name, control in controls.items():
            if not a.only or name in a.only.split(","):
                out[name] = control()
                write()
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        stepped = first_step(seed, cfg)
        params, host, tokens = inputs(seed)
        out["program_at_other_seeds"][seed] = verdict(params, host, tokens,
                                                      seed, stepped)
        write()
    if a.spread:
        first, count = cfg.experts_held
        share = (tr["batch"] * tr["seq"] * cfg.top_k * count
                 / cfg.num_experts)
        counts_of = jax.jit(lambda p, b: model.loss_terms(cfg, p, b)[1][
            "expert_counts"])
        over = []
        for n in range(a.spread):
            seed = 2_147_483_659 + 7919 * n
            del params
            params, _, tokens = inputs(seed)
            counts = np.asarray(counts_of(params, {"tokens": tokens}))
            over.append((counts[:, first:first + count].sum(-1)
                         / share).tolist())
        over_np = np.asarray(over)
        out["held_rows_over_share"] = {
            "balanced_share_rows": share, "by_seed_and_layer": over,
            "mean": float(over_np.mean()), "std": float(over_np.std()),
            "largest": float(over_np.max()), "least": float(over_np.min()),
            "layers_over": {f"1/{part}": int((over_np > 1 + 1 / part).sum())
                            for part in (6, 4, 3, 2, 1)},
            "layers": int(over_np.size)}
    out["tolerances"] = tolerances
    write()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
