"""On the chip, in one process: that each tolerance of a ``train_mixed``
cell's check refuses (a) the reference computed one precision lower (its
weights rounded to float8 e4m3) and (b) the program with its window mask
left off (every sliding layer plain causal), and what the honest program
reads beside them. Same weights, same batch 0 as the cell with this seed.
``--seed`` given again adds the honest program's reading at that seed (the
range a tolerance is set from), with neither control.

    python3 benchmark/tests/mixed_limits.py --workload train-laguna-1chip --seed 17 [--seed 18 ...]

Prints one JSON object and writes it to ``chiprun_out/mixed_limits.json``.
"""
import argparse
import json
import os
import sys
from dataclasses import replace
from functools import lru_cache
from importlib import import_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-laguna-1chip")
    ap.add_argument("--seed", type=int, action="append")
    a = ap.parse_args()
    seeds = a.seed or [17]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.cells import train_mixed
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr = ctx["traffic"]
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in ctx["config"]["model_config"].items()}
    name, preset = kw.pop("module"), kw.pop("preset")
    model = import_module("ray_tpu.models." + name)
    reference = import_module(f"benchmark.references.{name}_ref")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = getattr(getattr(model, name.capitalize() + "Config"), preset)(
        **kw, attn_impl="auto")
    init = jax.jit(lambda k: model.init_params(cfg, k))

    def inputs(seed):
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)[0]
        return (init(jax.random.PRNGKey(seed % (2 ** 31 - 1))), host,
                jax.device_put(host))

    seed = seeds[0]
    params, host, tokens = inputs(seed)

    @lru_cache(maxsize=None)
    def loss_of(program_cfg):
        return jax.jit(lambda p, t: model.loss_terms(
            program_cfg, p, {"tokens": t})[0])

    def first_loss(program_cfg):
        return float(loss_of(program_cfg)(params, tokens))

    def lower(x):
        if x.dtype not in (jnp.bfloat16, jnp.float32):
            return x
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    def read(**how):
        g = train_mixed.compare(model, reference, cfg, params, tokens, host,
                                seed=seed, **how)
        loss = first_loss(how.get("program_cfg", cfg))
        return {**{f"gradient, {kind}": max(leaves.values())
                   for kind, leaves in g["gradient_gap"].items()},
                "gradient, every leaf": g["gradient_gap"],
                "first-step loss": abs(loss - g["ref_terms"]["loss"]),
                "router logits, rms": g["router_logit_gap"]["rms"],
                "router logits, max": g["router_logit_gap"]["max"],
                "differing choices, share": g["choices"]["differing_share"],
                "differing choices, regret": g["choices"]["max_regret"],
                "per-token loss, rms": g["token_nll_gap"]["rms"],
                "per-token loss, max": g["token_nll_gap"]["max"]}

    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "program": read(),
           "reference_float8": read(
               reference_params=jax.tree_util.tree_map(lower, params))}
    if getattr(cfg, "sliding_window", None):
        out["program_without_window"] = read(
            program_cfg=replace(cfg, sliding_window=None))
    out["program_at_other_seeds"] = {}
    for seed in seeds[1:]:
        del params, tokens
        params, host, tokens = inputs(seed)
        out["program_at_other_seeds"][seed] = read()
    chk = tr["check"]
    out["tolerances"] = {
        **{f"gradient, {kind}": tol
           for kind, tol in chk["gradient_gap_tolerance"].items()},
        "first-step loss": chk["loss_tolerance"],
        "router logits, rms": chk["router_logit_rms_tolerance"],
        "router logits, max": chk["router_logit_max_tolerance"],
        "differing choices, share": chk["differing_choice_share_tolerance"],
        "differing choices, regret": chk["choice_regret_tolerance"],
        "per-token loss, rms": chk["token_nll_rms_tolerance"],
        "per-token loss, max": chk["token_nll_max_tolerance"]}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mixed_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
