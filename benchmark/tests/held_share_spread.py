"""On the chip: how far from its balanced share a layer's held rows lie,
seed by seed, in a training cell whose layers hold a share of their experts
(``ops/moe.routed_experts(held=...)``), at the cell's sizes: the cell's
seeded weights and its batch 0 (as ``cells/train_delta_moe.py`` makes
them), one forward a seed, each layer's held rows over the balanced share.
A pass of the held rows is the share and a headroom (``ops/moe._held_chunk``);
a layer over it pays a second pass, so which seeds do is which runs of the
cell are slower. ``--time`` also compiles the cell's own train step at each
given ``held_headroom`` (0: the op's own part) and reads a step's time at
the first seed, median of ``--steps``.

    python3 benchmark/tests/held_share_spread.py --seeds 32 --time 0,4,3
    python3 benchmark/tests/held_share_spread.py --tiny --seeds 3 --time 0,2

Prints one JSON object and writes it to
``chiprun_out/held_share_spread.json``.
"""
import argparse
import json
import os
import sys
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-qwen3-next-1chip")
    ap.add_argument("--seeds", type=int, default=32)
    ap.add_argument("--first-seed", type=int, default=2_147_483_659)
    ap.add_argument("--time", default="")
    ap.add_argument("--steps", type=int, default=7)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal")
    a = ap.parse_args()
    if a.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from benchmark.cells import train_delta_moe as cell
    from benchmark.lib import spec

    ctx = spec.resolve_cell(spec.load_benchmark(ROOT), a.workload, ROOT)
    tr, model_config = ctx["traffic"], ctx["config"]["model_config"]
    if a.tiny:
        from benchmark.tests.delta_moe_limits import TINY, TINY_TRAFFIC
        tr, model_config = {**tr, **TINY_TRAFFIC, "host_batches": 9}, TINY
    model, _, cfg = cell.load_model(model_config)
    first, count = cfg.experts_held
    init = jax.jit(lambda k: model.init_params(cfg, k))
    counts_of = jax.jit(lambda p, b: model.loss_terms(cfg, p, b)[1][
        "expert_counts"])

    def inputs(seed):
        host = np.random.default_rng(seed).integers(
            0, cfg.vocab_size,
            (tr["host_batches"], tr["batch"], tr["seq"] + 1), np.int32)
        return (init(jax.random.PRNGKey(seed % (2 ** 31 - 1))),
                [{"tokens": jax.device_put(h)} for h in host[:a.steps + 2]])

    share = tr["batch"] * tr["seq"] * cfg.top_k * count / cfg.num_experts
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    over = []                                   # [seed][layer] rows / share
    for seed in seeds:
        params, batches = inputs(seed)
        counts = np.asarray(counts_of(params, batches[0]))
        over.append((counts[:, first:first + count].sum(-1) / share).tolist())
        del params
    over_np = np.asarray(over)
    out = {"device": jax.devices()[0].device_kind, "workload": a.workload,
           "balanced_share_rows": share, "seeds": seeds,
           "held_rows_over_share": over,
           "mean": float(over_np.mean()), "std": float(over_np.std()),
           "largest": float(over_np.max()), "least": float(over_np.min()),
           "layers_over": {f"1/{part}": int((over_np > 1 + 1 / part).sum())
                           for part in (8, 6, 5, 4, 3, 2)},
           "seeds_with_a_layer_over": {
               f"1/{part}": int((over_np > 1 + 1 / part).any(-1).sum())
               for part in (8, 6, 5, 4, 3, 2)},
           "layers": int(over_np.size), "timed": {}}

    def write():
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               "held_share_spread.json"), "w") as f:
            json.dump(out, f, indent=1)

    write()
    tx = cell.optimizer(tr)
    for part in [int(x) for x in a.time.split(",") if x]:
        pcfg = replace(cfg, held_headroom=part or None)
        params, batches = inputs(seeds[0])
        opt = tx.init(params)
        step = jax.jit(cell.make_step(model, pcfg, tx),
                       donate_argnums=(0, 1)).lower(
            params, opt, batches[0]).compile()
        took = []
        for batch in batches:
            t0 = time.monotonic()
            params, opt, loss, aux = step(params, opt, batch)
            loss.block_until_ready()
            took.append(time.monotonic() - t0)
        counts = np.asarray(aux["expert_counts"])
        out["timed"][str(part)] = {
            "step_s": took, "median_s": float(np.median(took[2:])),
            "rows_passed": int(model.rows_passed(pcfg, counts)),
            "rows_held": int(aux["moe_rows_held"]),
            "temporaries": step.memory_analysis().temp_size_in_bytes}
        del params, opt, step
        write()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
