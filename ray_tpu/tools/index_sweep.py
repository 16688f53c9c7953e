"""The index's scores alone on the chip, one process:
``ops/dsa.index_scores`` forward and backward over one layer's walk at a
cell's shapes (``train-dots3-1chip``'s by default: 16,384 positions, 64
index heads of 128, blocks of 128 queries in four tiers of keys, two such
layers, bfloat16), milliseconds a layer, for XLA's form and for the
kernels over their constants, and how far each form's scores and
gradients lie from XLA's form in float32 at the highest matmul precision.

    python3 ray_tpu/tools/index_sweep.py [--kernels 512,64 ...] \
        [--block 128] [--seq 16384]

- ``forward_ms``: every block of the walk scored against its tier's keys,
  the kernels told where the block's diagonal lies (``dsa._scores``), as
  the walk calls them; ``forward_all_tiles_ms``: the same with every tile
  scored (``index_scores`` as the cell's check calls it).
- ``backward_ms``: every block's three gradients from a float32 cotangent
  ``[block, keys]`` that is zero off a planted choice (a query's
  ``--topk`` keys); the forward call is not in it (its residuals are its
  inputs).
- ``step_ms``: what a train step spends in them over ``--layers`` layers,
  three forwards and a backward a layer (the forward, the layer's remat and
  the block's own ``jax.checkpoint``).

``--kernels tile,rows`` sets ``SCORE_TILE`` and ``SCORE_ROWS`` and may be
given again; without it the module's constants are read alone. Every array
is an argument of the jitted call; a time is the wall clock around
``block_until_ready`` of one layer's walk (the device is busy all through
it: 128 calls back to back), the median of ``--calls`` after two warm
calls. Prints a line a reading and writes all of them to
``chiprun_out/<--out>`` (``index_sweep.json``). Run as a file; a time read
on the CPU is no device number (the kernels then run in the Pallas
interpreter: use a short ``--seq``).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--tiers", type=int, default=4)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--kernels", action="append", default=[])
    ap.add_argument("--no-gaps", action="store_true")
    ap.add_argument("--out", default="index_sweep.json")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    f32, bf16 = jnp.float32, jnp.bfloat16
    s, J, d = a.seq, a.heads, a.dim
    block, tiers = dsa.walk_plan(s, a.block, a.tiers)
    per_tier = s // block // tiers
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (s, J, d), f32)
    k = jax.random.normal(keys[1], (s, d), f32)
    w = jax.random.normal(keys[2], (s, J), f32) * (J * d) ** -0.5
    # a cotangent that is zero off a planted choice: ``topk`` keys a query
    g = jnp.where(jax.random.uniform(keys[3], (block, s)) < a.topk / s,
                  jax.random.normal(keys[3], (block, s), f32), 0.0)
    on_cpu = jax.default_backend() == "cpu"

    def small(x):
        """A few numbers of ``x`` that no fusion can reach past."""
        return jax.lax.optimization_barrier(x)[:1].astype(f32).sum()

    def walks(scores):
        """Jitted: one layer's walk of ``scores(q_b, k_t, w_b, first)``
        forward, and of its three gradients under ``g``; new functions
        every time, so that jit traces them anew."""
        def over_blocks(body, q, k, w, *more):
            def by_block(x):
                return x.reshape((tiers, per_tier, block) + x.shape[1:])

            firsts = (jnp.arange(s // block, dtype=jnp.int32) * block
                      ).reshape(tiers, per_tier)
            total = 0.0
            for t in range(tiers):
                end = (t + 1) * per_tier * block
                total = total + jax.lax.map(
                    lambda x, end=end: body(k[:end], end, *x, *more),
                    (by_block(q)[t], by_block(w)[t], firsts[t])).sum()
            return total

        def forward(q, k, w):
            return over_blocks(
                lambda k_t, end, q_b, w_b, first:
                small(scores(q_b, k_t, w_b, first)), q, k, w)

        def backward(q, k, w, g):
            def body(k_t, end, q_b, w_b, first, g):
                _, vjp = jax.vjp(
                    lambda *x: scores(*x, first), q_b, k_t, w_b)
                return sum(small(x) for x in vjp(g[:, :end]))

            return over_blocks(body, q, k, w, g)

        return jax.jit(forward), jax.jit(backward)

    def ms(fn, *xs):
        times = []
        for _ in range(a.calls + 2):
            t = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            times.append((time.perf_counter() - t) * 1e3)
        return statistics.median(times[2:])

    def gap(got, want):
        got, want = got.astype(f32), want.astype(f32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    # one block against every key, the last block of the sequence
    rows = slice(s - block, s)
    cell = (q[rows].astype(bf16), k.astype(bf16), w[rows])
    exact = tuple(x.astype(f32) for x in cell)

    def one_block(scores):
        def outputs(q_b, k, w_b, g):
            out, vjp = jax.vjp(lambda *x: scores(*x, None), q_b, k, w_b)
            return (out,) + vjp(g)

        return jax.jit(outputs)

    def xla(q_b, k_t, w_b, first):
        return dsa.plain_scores(q_b, k_t, w_b)

    want = None
    if not a.no_gaps:
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(one_block(xla)(*exact, g))

    def reading(scores, every_tile=None):
        forward, backward = walks(scores)
        half = (q.astype(bf16), k.astype(bf16), w)
        out = {}
        try:
            out["forward_ms"] = ms(forward, *half)
            if every_tile is not None:
                out["forward_all_tiles_ms"] = ms(walks(every_tile)[0], *half)
            out["backward_ms"] = ms(backward, *half, g)
            out["step_ms"] = a.layers * (3 * out["forward_ms"]
                                         + out["backward_ms"])
            if want is not None:
                got = one_block(scores)(*cell, g)
                out["gap_to_float32"] = {
                    n: gap(x, y) for n, x, y in zip(
                        ("scores", "dq_i", "dk_i", "dw"), got, want)}
        except Exception as e:  # noqa: BLE001 (a setting Mosaic refuses)
            out["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        return out

    out = {"device": jax.devices()[0].device_kind, "seq": s, "heads": J,
           "dim": d, "block": block, "tiers": tiers, "calls": a.calls,
           "pairs_scored": sum(per_tier * block * (t + 1) * per_tier * block
                               for t in range(tiers)),
           "xla": reading(xla), "kernels": {}}
    print(json.dumps({"xla": out["xla"]}), flush=True)
    if on_cpu:
        dsa.score_kernels = functools.partial(dsa.score_kernels,
                                              interpret=True)
        jax.default_backend = lambda: "tpu"
    names = ("SCORE_TILE", "SCORE_ROWS")
    settings = [tuple(int(x) for x in text.split(",")) for text in a.kernels
                ] or [tuple(getattr(dsa, n) for n in names)]
    for setting in settings:
        for name, value in zip(names, setting):
            setattr(dsa, name, value)
        key = ",".join(str(v) for v in setting)
        plan = dsa.scores_plan(block, per_tier * block, J, d)
        out["kernels"][key] = dict(plan, **(reading(
            dsa._scores, every_tile=lambda q_b, k_t, w_b, first:
            dsa.index_scores(q_b, k_t, w_b))
            if plan["scores_form"] == "kernel" else {}))
        print(json.dumps({key: out["kernels"][key]}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", a.out), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
