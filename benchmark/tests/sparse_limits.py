"""On the chip, in one process: that the check of a ``train_sparse`` cell
refuses each of its planted controls by at least one limit, and what the
honest program reads beside them. Same weights, same batch 0 as the cell
with this seed. The controls:

- ``reference_float8``: the reference one precision lower (its weights
  rounded to float8 e4m3);
- ``selection_ignored``: dense causal attention in the full layers (every
  causal key chosen);
- ``half_the_keys``: ``index_topk / 2`` keys chosen for ``index_topk``;
- ``relu_left_out``: the index's scores without their ReLU;
- ``index_key_rms_norm``: the index key's LayerNorm as an RMSNorm (no mean
  taken off);
- ``rescale_left_out``: the two latents' ``(hidden / rank) ** 0.5`` left out;
- ``gate_left_out``: every head's gate 1;
- ``window_one_short``: ``sliding_window - 1`` keys a query in the window
  layers;
- ``window_at_full_theta``: the window layers' rope at the full layers'
  theta;
- ``target_not_normalised``: ``p_t`` the heads' sum, not L1-normalised.

``--seed`` given again adds the honest program's reading at that seed (the
range a tolerance is set from), with no control.

    python3 benchmark/tests/sparse_limits.py --seed 17 [--seed 18 ...]
    python3 benchmark/tests/sparse_limits.py --tiny       # on a CPU

Prints one JSON object and writes it to ``chiprun_out/sparse_limits.json``.
"""
import argparse
import json
import os
import sys
from contextlib import ExitStack
from dataclasses import replace
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TINY_CHECK = {
    "loss_tolerance": 1e-4, "index_loss_tolerance": 1e-4,
    "router_logit_rms_tolerance": 1e-4, "router_logit_max_tolerance": 1e-3,
    "differing_choice_share_tolerance": 0.0, "choice_regret_tolerance": 0.0,
    "index_score_rms_tolerance": 1e-5, "index_score_max_tolerance": 1e-4,
    "differing_key_share_tolerance": 0.0, "key_regret_tolerance": 0.0,
    "key_count_tolerance": 0.0, "band_tolerance": 1e-4,
    "token_nll_rms_tolerance": 1e-4, "token_nll_max_tolerance": 1e-3,
    "gradient_gap_tolerance": dict.fromkeys(
        ("full_dense", "full_moe", "sliding_moe", "top"), 1e-3),
    "first_step_moment_tolerance": dict.fromkeys(
        ("full_dense", "full_moe", "sliding_moe", "top"), 1e-3),
    "first_step_param_tolerance": 1e-6, "router_bias_tolerance": 0.0}


class _Program:
    """A model as ``train_sparse.compare`` asks of its program: its
    ``forward_reports``. A new one is a new key of that module's cache of
    jitted programs, so a fault planted while it traces is traced."""

    def __init__(self, model):
        self.forward_reports = model.forward_reports


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-dots3-1chip")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--only", action="append",
                    help="run these controls alone")
    ap.add_argument("--tiny", action="store_true",
                    help="Dots3Config.tiny() at 2 x 48 tokens in float32: "
                         "the script's own rehearsal on a CPU")
    a = ap.parse_args()
    seeds = a.seed or [17]
    if a.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.cells import train_sparse
    from benchmark.lib import spec
    from ray_tpu.ops import dsa, mla

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    model_config = ctx["config"]["model_config"]
    if a.tiny:
        tr = {**tr, "batch": 2, "seq": 48, "host_batches": 2,
              "check": TINY_CHECK}
        model_config = {"module": "dots3", "preset": "tiny", "num_heads": 2,
                        "heads_of": 4, "swa_num_heads": 1, "swa_heads_of": 2,
                        "experts_held": [4, 4], "dtype": "float32",
                        "param_dtype": "float32"}
    model, reference, cfg = train_sparse.load_model(model_config)
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = train_sparse.optimizer(tr)

    def weights(seed):
        return init(jax.random.PRNGKey(seed % (2 ** 31 - 1)))

    def batch_0(seed):
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)[0]
        return host, jax.device_put(host)

    step = jax.jit(train_sparse.make_step(model, cfg, tx),
                   donate_argnums=(0, 1))

    def first_step(seed, tokens):
        """What the timed step hands on from the seeded weights and a new
        optimizer state, as the cell takes it (while no other copy of the
        weights is held: the step fills the chip), its two loss terms and
        the gap of its biases to the rule on its own counts."""
        params = weights(seed)
        bias0 = reference.router_biases(cfg, params)
        after, opt, _, cnt, said = step(
            params, tx.init(model.trainable(params)), {"tokens": tokens})
        bias_gap = float(np.abs(
            reference.router_biases(cfg, after)
            - reference.updated_bias(cfg, bias0, np.asarray(cnt))).max())
        return (train_sparse.first_step_left(reference, after, opt),
                {k: float(v) for k, v in said.items()}, bias_gap)

    seed = seeds[0]
    host, tokens = batch_0(seed)
    left, first_terms, bias_gap = first_step(seed, tokens)
    params = weights(seed)

    def read(left=left, first_terms=first_terms, bias_gap=bias_gap, **how):
        gaps = train_sparse.compare(
            model, reference, cfg, params, tokens, host, seed=seed,
            first_step=(tx, left), **how)
        checks = train_sparse.checks_of(tr["check"], first_terms, gaps,
                                        bias_gap)
        return ({k: v for k, (v, _) in checks.items()},
                sorted(k for k, (v, tol) in checks.items() if not v <= tol),
                {k: tol for k, (_, tol) in checks.items()})

    def faulty(cfg_=None, **patches):
        """The program with a fault planted: another config, or functions
        of ``ops/mla.py`` and ``ops/dsa.py`` replaced while it is traced."""
        train_sparse._band_probe.cache_clear()
        program = (_Program(model), cfg_ or cfg)
        in_mla = {k: v for k, v in patches.items() if hasattr(mla, k)}
        in_dsa = {k: v for k, v in patches.items() if k not in in_mla}
        with ExitStack() as stack:
            for module, names in ((mla, in_mla), (dsa, in_dsa)):
                if names:
                    stack.enter_context(mock.patch.multiple(module, **names))
            return read(program=program)

    honest_choose = dsa.choose

    def every_causal_key(scores, first_q, topk):
        return honest_choose(scores, first_q, scores.shape[-1])

    def scores_without_relu(q_i, k_i, w):
        x = jnp.einsum("njd,sd->njs", q_i, k_i,
                       preferred_element_type=jnp.float32)
        return (x * w.astype(jnp.float32)[:, :, None]).sum(1)

    def rms_for_layer_norm(x, weight, bias, eps=1e-5):
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + eps)
        return (xf * weight.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)

    out = {"device": jax.devices()[0].device_kind, "seed": seed}
    out["program"], honest_outside, out["tolerances"] = read()
    controls = {
        "reference_float8": lambda: read(reference_dtype="float8_e4m3fn"),
        "selection_ignored": lambda: faulty(choose=every_causal_key),
        "half_the_keys": lambda: faulty(
            replace(cfg, index_topk=cfg.index_topk // 2)),
        "relu_left_out": lambda: faulty(index_scores=scores_without_relu),
        "index_key_rms_norm": lambda: faulty(layer_norm=rms_for_layer_norm),
        "rescale_left_out": lambda: faulty(
            rescale_factor=lambda cfg_, rank: 1.0),
        "gate_left_out": lambda: faulty(
            head_gate=lambda cfg_, u, wg: jnp.ones(
                u.shape[:2] + wg.shape[-1:], jnp.float32)),
        "window_one_short": lambda: faulty(
            replace(cfg, sliding_window=cfg.sliding_window - 1)),
        "window_at_full_theta": lambda: faulty(
            replace(cfg, swa_rope_theta=cfg.rope_theta)),
        "target_not_normalised": lambda: faulty(
            kl_target=lambda p: jax.lax.stop_gradient(p.sum(0))),
    }
    out["outside"] = {"program": honest_outside}
    for name, control in controls.items():
        if a.only and name not in a.only:
            continue
        out[name], out["outside"][name], _ = control()
        print(f"[limits] {name}: outside {out['outside'][name]}", flush=True)
    out["program_at_other_seeds"] = {}
    others_inside = True
    for seed in seeds[1:]:
        del params, tokens
        host, tokens = batch_0(seed)
        left, first_terms, bias_gap = first_step(seed, tokens)
        params = weights(seed)
        reading, outside, _ = read(left=left, first_terms=first_terms,
                                   bias_gap=bias_gap)
        out["program_at_other_seeds"][seed] = reading
        others_inside &= not outside
    out["honest_inside_every_limit"] = not honest_outside and others_inside
    out["every_control_outside_some_limit"] = all(
        out["outside"][name] for name in controls if name in out["outside"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sparse_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
