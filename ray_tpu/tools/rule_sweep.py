"""The gated delta rule alone on the chip, one process: forward and
gradient of ``ops/delta.gated_delta_rule`` at a cell's shapes (by default
``train-olmo-hybrid-1chip``'s: 1 x 32,768 positions, 30 heads, keys of 96,
values of 192, bfloat16), milliseconds a layer, for XLA's walk and for the
kernels over their three constants (``KERNEL_HEADS``, ``KERNEL_CHUNKS``,
``KERNEL_BASE``), and how far each form's outputs and gradients lie from
XLA's walk on float32 operands at the highest matmul precision.

    python3 ray_tpu/tools/rule_sweep.py [--seq 32768] [--kernels 2,8,16 ...]

``--kernels heads,chunks,base`` may be given again; without it the
module's constants are read alone; ``--no-gaps`` reads the times alone.
Prints one JSON object and writes it to ``chiprun_out/<--out>``
(``rule_sweep.json``). Run as a file; a time read on the CPU is
no device number (the kernels then run in the Pallas interpreter: use a
short ``--seq``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=30)
    ap.add_argument("--key-dim", type=int, default=96)
    ap.add_argument("--value-dim", type=int, default=192)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--kernels", action="append", default=[])
    ap.add_argument("--no-gaps", action="store_true")
    ap.add_argument("--out", default="rule_sweep.json")
    a = ap.parse_args()
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta
    from ray_tpu.ops.layers import l2_norm

    on_cpu = jax.default_backend() == "cpu"
    f32, bf16 = jnp.float32, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (1, a.seq, a.heads)
    q, k = (l2_norm(jax.random.normal(key, shape + (a.key_dim,), f32),
                    scale=scale)
            for key, scale in ((keys[0], a.key_dim ** -0.5), (keys[1], 1.0)))
    v = jax.random.normal(keys[2], shape + (a.value_dim,), f32)
    g = -jax.nn.softplus(jax.random.normal(keys[3], shape) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    do = jax.random.normal(keys[5], v.shape, f32)
    exact = (q, k, v, g, beta)
    cell = (q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta)
    # any mesh keeps XLA's walk
    walk = jax.sharding.Mesh(jax.devices()[:1], ("x",))

    def programs(mesh):
        def rule(*xs):
            return delta.gated_delta_rule(*xs, chunk=a.chunk, mesh=mesh)

        def loss(*xs):
            o, _ = rule(*xs[:-1])
            return (o.astype(f32) * xs[-1]).sum()

        return jax.jit(rule), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))

    def ms(fn, *xs):
        jax.block_until_ready(fn(*xs))
        jax.block_until_ready(fn(*xs))
        t = time.perf_counter()
        for _ in range(a.calls):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / a.calls * 1e3

    def gap(got, want):
        got, want = got.astype(f32), want.astype(f32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    want = None
    if not a.no_gaps:
        with jax.default_matmul_precision("highest"):
            rule, grad = programs(walk)
            want = jax.block_until_ready((rule(*exact), grad(*exact, do)))

    def reading(mesh):
        rule, grad = programs(mesh)
        plan = delta.rule_plan(1, a.seq, a.heads, a.key_dim, a.value_dim,
                               a.chunk, mesh)
        out = {"plan": {n: plan[n] for n in (
            "form", "steps", "heads_a_block", "chunks_a_call", "states_kept",
            "float32_bytes_in_hbm")}}
        try:
            out["forward_ms"] = ms(rule, *cell)
            out["gradient_ms"] = ms(grad, *cell, do.astype(bf16))
            if want is not None:
                (o, S), grads = rule(*cell), grad(*cell, do.astype(bf16))
                out["gap_to_float32_walk"] = dict(
                    o=gap(o, want[0][0]), state=gap(S, want[0][1]),
                    **{n: gap(x, w) for n, x, w in zip(
                        ("dq", "dk", "dv", "dg", "dbeta"), grads, want[1])})
        except Exception as e:  # noqa: BLE001 (a setting Mosaic refuses)
            out["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        return out

    out = {"device": jax.devices()[0].device_kind, "seq": a.seq,
           "heads": a.heads, "key_dim": a.key_dim, "value_dim": a.value_dim,
           "chunk": a.chunk, "xla_walk": reading(walk), "kernels": {}}
    if on_cpu:
        delta.rule_kernels = functools.partial(delta.rule_kernels,
                                               interpret=True)
        jax.default_backend = lambda: "tpu"
    settings = [tuple(int(x) for x in s.split(",")) for s in a.kernels] or [
        (delta.KERNEL_HEADS, delta.KERNEL_CHUNKS, delta.KERNEL_BASE)]
    for heads, chunks, base in settings:
        delta.KERNEL_HEADS, delta.KERNEL_CHUNKS, delta.KERNEL_BASE = (
            heads, chunks, base)
        out["kernels"][f"{heads},{chunks},{base}"] = reading(None)
        print(json.dumps({f"{heads},{chunks},{base}":
                          out["kernels"][f"{heads},{chunks},{base}"]}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", a.out), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
