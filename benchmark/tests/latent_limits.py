"""On the chip, in one process: that the check of a ``train_latent`` cell
refuses each of its planted controls by at least one limit, and what the
honest program reads beside them. Same weights, same batch 0 as the cell
with this seed. The controls:

- ``reference_float8``: the reference one precision lower (its weights
  rounded to float8 e4m3);
- ``scale_without_m2``: the program's softmax scale ``192 ** -0.5``
  (0.0722) for ``192 ** -0.5 * m ** 2`` (0.1147);
- ``shared_key_rope_off_by_one``: the shared key rotated for the position
  before its own (the queries as they should be);
- ``kv_latent_norm_left_out``: the kv latent goes to its expansion
  without its RMSNorm;
- ``plain_top_k``: the six largest scores, no group limit;
- ``weights_renormalised``: the six weights divided by their sum;
- ``scale_16_left_out``: ``routed_scaling_factor`` 1;
- ``state_handed_on``: a step that hands on the parameters and the
  optimizer state it was given.

``--seed`` given again adds the honest program's reading at that seed (the
range a tolerance is set from), with no control.

    python3 benchmark/tests/latent_limits.py --seed 17 [--seed 18 ...]

Prints one JSON object and writes it to ``chiprun_out/latent_limits.json``.
"""
import argparse
import json
import os
import sys
from dataclasses import replace
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


class _Program:
    """A model as ``train_latent.compare`` asks of one: its ``forward``.
    A new one is a new key of that module's cache of jitted programs."""

    def __init__(self, forward):
        self.forward = forward


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-deepseek-v2-1chip")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--tiny", action="store_true",
                    help="DeepseekV2Config.tiny() at 2 x 32 tokens in "
                         "float32: the script's own rehearsal on a CPU")
    a = ap.parse_args()
    seeds = a.seed or [17]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.cells import train_latent
    from benchmark.lib import spec
    from ray_tpu.models import stack
    from ray_tpu.ops import mla
    from ray_tpu.ops.moe import routed_part

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    model_config = ctx["config"]["model_config"]
    if a.tiny:
        tr = {**tr, "batch": 2, "seq": 32, "host_batches": 2}
        model_config = {"module": "deepseek_v2", "preset": "tiny",
                        "num_heads": 2, "heads_of": 4,
                        "experts_held": [4, 4], "dtype": "float32",
                        "param_dtype": "float32"}
    model, reference, cfg = train_latent.load_model(model_config)
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = train_latent.optimizer(tr)

    def weights(seed):
        return init(jax.random.PRNGKey(seed % (2 ** 31 - 1)))

    def batch_0(seed):
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)[0]
        return host, jax.device_put(host)

    step = jax.jit(train_latent.make_step(model, cfg, tx),
                   donate_argnums=(0, 1))

    def first_step(seed, tokens):
        """What the timed step hands on from the seeded weights and a new
        optimizer state, as the cell takes it: while no other copy of the
        weights is held, the step fills the chip."""
        params = weights(seed)
        after, opt, *_ = step(params, tx.init(params), {"tokens": tokens})
        return train_latent.first_step_left(reference, after, opt)

    def readings(g):
        out = {f"gradient, {kind}": max(leaves.values())
               for kind, leaves in g["gradient_gap"].items()}
        for kind, leaves in g["first_step"]["moment_gap"].items():
            out[f"first step, moment, {kind}"] = max(leaves.values())
        out.update({
            "first step, parameters": g["first_step"]["param_gap"],
            "first-step loss": abs(g["program_cross_entropy"]
                                   - g["ref_terms"]["cross_entropy"]),
            "router logits, rms": g["router_logit_gap"]["rms"],
            "router logits, max": g["router_logit_gap"]["max"],
            "differing choices, share": g["choices"]["differing_share"],
            "differing choices, regret": g["choices"]["max_regret"],
            "per-token loss, rms": g["token_nll_gap"]["rms"],
            "per-token loss, max": g["token_nll_gap"]["max"]})
        return out, {"gradient": g["gradient_gap"], **g["first_step"],
                     "groups_spanned_max":
                         g["choices"]["groups_spanned_max"]}

    seed = seeds[0]
    host, tokens = batch_0(seed)
    left = first_step(seed, tokens)
    params = weights(seed)

    def read(left=left, **how):
        return readings(train_latent.compare(
            model, reference, cfg, params, tokens, host, seed=seed,
            first_step=(tx, left), **how))

    def faulty(cfg_=None, forward=None, **patches):
        """The program with a fault planted: another config, another
        table's forward, or functions of ``ops/mla.py`` replaced while it
        is traced."""
        program = (_Program(forward or model.forward),
                   cfg_ or cfg)
        if not patches:
            return read(program=program)
        with mock.patch.multiple(mla, **patches):
            return read(program=program)

    def lower(x):
        if x.dtype not in (jnp.bfloat16, jnp.float32):
            return x
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    rotate, norm = mla._rotate, mla.rms_norm
    renormalised = stack.Stack(
        {**model.LAYER_KINDS, "mla_moe": (
            model.LAYER_KINDS["mla_moe"][0],
            routed_part(shared=True, balance="sequence", renormalize=True,
                        groups=True))}, reports="router")
    out = {"device": jax.devices()[0].device_kind, "seed": seed}
    out["program"], out["program, every leaf"] = read()
    controls = {
        "reference_float8": lambda: read(
            reference_params=jax.tree_util.tree_map(lower, params)),
        "scale_without_m2": lambda: faulty(softmax_scale=lambda c: (
            c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5),
        "shared_key_rope_off_by_one": lambda: faulty(
            _rotate=lambda x, cos, sin: rotate(
                x, *((jnp.roll(cos, 1, 0), jnp.roll(sin, 1, 0))
                     if x.shape[2] == 1 else (cos, sin)))),
        "kv_latent_norm_left_out": lambda: faulty(
            rms_norm=lambda x, w, eps: x if w.shape[-1] == cfg.kv_lora_rank
            else norm(x, w, eps)),
        "plain_top_k": lambda: faulty(replace(cfg, n_group=1, topk_group=1)),
        "weights_renormalised": lambda: faulty(forward=renormalised.forward),
        "scale_16_left_out": lambda: faulty(replace(cfg, routed_scale=1.0)),
        "state_handed_on": lambda: read(left=jax.device_get({
            "params": reference.first_layers(params),
            "mu": reference.first_layers(tx.init(params)[0].mu)})),
    }
    chk = tr["check"]
    tolerances = {
        **{f"gradient, {kind}": tol
           for kind, tol in chk["gradient_gap_tolerance"].items()},
        **{f"first step, moment, {kind}": tol
           for kind, tol in chk["first_step_moment_tolerance"].items()},
        "first step, parameters": chk["first_step_param_tolerance"],
        "first-step loss": chk["loss_tolerance"],
        "router logits, rms": chk["router_logit_rms_tolerance"],
        "router logits, max": chk["router_logit_max_tolerance"],
        "differing choices, share": chk["differing_choice_share_tolerance"],
        "differing choices, regret": chk["choice_regret_tolerance"],
        "per-token loss, rms": chk["token_nll_rms_tolerance"],
        "per-token loss, max": chk["token_nll_max_tolerance"]}

    def outside(reading):
        return sorted(k for k, v in reading.items() if v > tolerances[k])

    out["outside"] = {"program": outside(out["program"])}
    for name, control in controls.items():
        out[name], _ = control()
        out["outside"][name] = outside(out[name])
        print(f"[limits] {name}: outside {out['outside'][name]}", flush=True)
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        host, tokens = batch_0(seed)
        left = first_step(seed, tokens)
        params = weights(seed)
        out["program_at_other_seeds"][seed], _ = read(left=left)
    out["tolerances"] = tolerances
    out["honest_inside_every_limit"] = not out["outside"]["program"] and all(
        not outside(r) for r in out["program_at_other_seeds"].values())
    out["every_control_outside_some_limit"] = all(
        out["outside"][name] for name in controls)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "latent_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
