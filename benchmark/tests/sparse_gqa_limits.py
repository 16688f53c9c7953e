"""On the chip, in one process: that the check of a ``train_sparse_gqa``
cell refuses each of its planted controls by at least one limit, and what
the honest program reads beside them. Same weights, same batch 0 as the
cell with this seed. The controls:

- ``reference_float8``: the reference one precision lower (its weights
  rounded to float8 e4m3);
- ``selection_ignored``: dense causal attention (every causal key chosen);
- ``half_the_keys``: ``index_topk / 2`` keys chosen for ``index_topk``;
- ``relu_left_out``: the index's scores without their ReLU;
- ``one_position_stream``: all three rope streams given ``pos_t`` (what
  proves that the batch's positions reach the kernels);
- ``group_0_for_every_head``: the keys and values of group 0 served to
  every query head;
- ``head_norms_left_out``: q and k not normed a head at a time;
- ``target_not_normalised``: ``p_t`` the heads' sum, not L1-normalised.

``--seed`` given again adds the honest program's reading at that seed (the
range a tolerance is set from), with no control; ``--honest-only`` skips the
controls.

    python3 benchmark/tests/sparse_gqa_limits.py --seed 17 [--seed 18 ...]
    python3 benchmark/tests/sparse_gqa_limits.py --tiny       # on a CPU

Prints one JSON object and writes it to
``chiprun_out/sparse_gqa_limits.json``.
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


class _Program:
    """A model as ``train_sparse_gqa.compare`` asks of its program: its
    ``token_nll_reports``. A new one is a new key of that module's cache of
    jitted programs, so a fault planted while it traces is traced."""

    def __init__(self, model):
        self.token_nll_reports = model.token_nll_reports


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-keye-vl2-1chip")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--only", action="append",
                    help="run these controls alone")
    ap.add_argument("--honest-only", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="KeyeVL2Config.tiny() at 1 x 48 tokens in float32: "
                         "the script's own rehearsal on a CPU")
    a = ap.parse_args()
    seeds = a.seed or [17]
    if a.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from benchmark.cells import train_sparse_gqa as cell
    from benchmark.lib import spec
    from ray_tpu.models import keye_vl2, llama
    from ray_tpu.ops import dsa

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    model_config = ctx["config"]["model_config"]
    if a.tiny:
        from benchmark.tests import rehearse_sparse_gqa as tiny

        tr = {**tr, **tiny.TRAFFIC, "host_batches": 2}
        model_config = tiny.TINY
    model, reference, cfg = cell.load_model(model_config)
    init = jax.jit(lambda k: model.init_params(cfg, k))
    tx = cell.optimizer(tr)

    def weights(seed):
        return init(jax.random.PRNGKey(seed % (2 ** 31 - 1)))

    def batch_0(seed):
        host = {name: rows[0] for name, rows in spec.generator(
            tr["kind"]).host_batches(tr, seed, cfg.vocab_size).items()}
        return host, jax.device_put(host)

    step = jax.jit(cell.make_step(model, cfg, tx), donate_argnums=(0, 1))

    def first_step(seed, batch):
        """What the timed step hands on from the seeded weights and a new
        optimizer state, as the cell takes it (while no other copy of the
        weights is held), and its loss terms."""
        params = weights(seed)
        after, opt, _, _, said = step(
            params, tx.init(model.trainable(params)), batch)
        return (cell.first_step_left(reference, after, opt),
                {k: float(v) for k, v in said.items()})

    seed = seeds[0]
    host, batch = batch_0(seed)
    left, first_terms = first_step(seed, batch)
    params = weights(seed)

    def read(left=left, first_terms=first_terms, **how):
        gaps = cell.compare(model, reference, cfg, params, batch, host,
                            seed=seed, first_step=(tx, left), **how)
        checks = cell.checks_of(tr["check"], first_terms, gaps)
        return ({k: v for k, (v, _) in checks.items()},
                sorted(k for k, (v, tol) in checks.items() if not v <= tol),
                {k: tol for k, (_, tol) in checks.items()})

    def faulty(cfg_=None, **patches):
        """The program with a fault planted: another config, or functions
        of ``ops/dsa.py``, ``models/llama.py`` and ``models/keye_vl2.py``
        replaced while it is traced."""
        program = (_Program(model), cfg_ or cfg)
        with ExitStack() as stack:
            for module in (dsa, llama, keye_vl2):
                names = {k: v for k, v in patches.items()
                         if hasattr(module, k)}
                if names:
                    stack.enter_context(mock.patch.multiple(module, **names))
            return read(program=program)

    honest = {name: getattr(dsa, name) for name in (
        "choose", "plain_attend_grouped", "attend_kernels_grouped")}
    honest_block = llama.attention_block
    honest_tables = keye_vl2.mrope_frequencies

    def every_causal_key(scores, first_q, topk):
        return honest["choose"](scores, first_q, scores.shape[-1])

    def scores_without_relu(q_i, k_i, w):
        x = jnp.einsum("njd,sd->njs", q_i, k_i,
                       preferred_element_type=jnp.float32)
        return (x * w.astype(jnp.float32)[:, :, None]).sum(1)

    def temporal_alone(width, positions, sections, theta, dtype):
        return honest_tables(width, jnp.broadcast_to(
            positions[:1], positions.shape), sections, theta, dtype=dtype)

    def group_0(axis):
        """``k`` and ``v`` (arguments 1 and 2) replaced by group 0's,
        repeated along the groups' ``axis``."""
        def served(name):
            def attend(q, k, v, *rest, **more):
                k, v = (jnp.repeat(jax.lax.slice_in_dim(x, 0, 1, axis=axis),
                                   x.shape[axis], axis) for x in (k, v))
                return honest[name](q, k, v, *rest, **more)
            return attend
        return served

    def block_without_head_norms(cfg_, x, p, *rest, **more):
        return honest_block(cfg_, x, {k: v for k, v in p.items()
                                      if k not in ("q_norm", "k_norm")},
                            *rest, **more)

    out = {"device": jax.devices()[0].device_kind, "seed": seed}
    out["program"], honest_outside, out["tolerances"] = read()
    controls = {
        "reference_float8": lambda: read(reference_dtype="float8_e4m3fn"),
        "selection_ignored": lambda: faulty(choose=every_causal_key),
        "half_the_keys": lambda: faulty(
            replace(cfg, index_topk=cfg.index_topk // 2)),
        "relu_left_out": lambda: faulty(index_scores=scores_without_relu),
        "one_position_stream": lambda: faulty(
            mrope_frequencies=temporal_alone),
        "group_0_for_every_head": lambda: faulty(
            plain_attend_grouped=group_0(1)("plain_attend_grouped"),
            attend_kernels_grouped=group_0(0)("attend_kernels_grouped")),
        "head_norms_left_out": lambda: faulty(
            attention_block=block_without_head_norms),
        "target_not_normalised": lambda: faulty(
            kl_target=lambda p: jax.lax.stop_gradient(p.sum(0))),
    }
    out["outside"] = {"program": honest_outside}
    for name, control in controls.items():
        if a.honest_only or (a.only and name not in a.only):
            continue
        out[name], out["outside"][name], _ = control()
        print(f"[limits] {name}: outside {out['outside'][name]}", flush=True)
    out["program_at_other_seeds"] = {}
    others_inside = True
    for seed in seeds[1:]:
        del params, batch
        host, batch = batch_0(seed)
        left, first_terms = first_step(seed, batch)
        params = weights(seed)
        reading, outside, _ = read(left=left, first_terms=first_terms)
        out["program_at_other_seeds"][seed] = reading
        others_inside &= not outside
        print(f"[limits] seed {seed}: outside {outside}", flush=True)
    out["honest_inside_every_limit"] = not honest_outside and others_inside
    out["every_control_outside_some_limit"] = all(
        out["outside"][name] for name in controls if name in out["outside"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sparse_gqa_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
