"""On the chip: device time of latent attention's flash kernels (forward
and both backward calls) at the cell's sizes, 1 x 8,192 positions, 32
heads, keys of 128 + 64 rope dims, values of 128, in the two ways the 64
rope dims can ride: ``shared`` (the kernels take the one rotated key a
position beside the heads' 128-wide keys: a second small product inside
the kernel, what ``ops/mla.py`` runs) and ``broadcast`` (one 192-wide key a
head, the shared part copied to every head in HBM before the call); and,
beside them, the causal kernels at equal widths of 128 (what the dense
cells run) for the same heads and positions.

    python3 benchmark/tests/latent_flash_variants.py

Wall time around ``block_until_ready`` of a jitted gradient (median of
ten, the broadcast's copy and its gradient's sum included), and the
kernels' own device time from a trace of three calls (``lib/scopes.py``'s
``kernel_s``, by kernel name).
"""
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import scopes, trace
    from ray_tpu.ops.attention import flash_attention, with_shared_key

    seq, heads, scale = 8192, 32, 0.114721
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, seq, heads, 192), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, seq, heads, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, seq, heads, 128), jnp.bfloat16)
    kr = jax.random.normal(ks[3], (1, seq, 64), jnp.bfloat16)
    variants = {
        "shared": lambda q, k, v, kr: flash_attention(
            q, k, v, k_shared=kr, sm_scale=scale),
        "broadcast": lambda q, k, v, kr: flash_attention(
            q, with_shared_key(k, kr), v, sm_scale=scale),
        "equal-128": lambda q, k, v, kr: flash_attention(
            q[..., :128], k, v, sm_scale=scale)}
    out = {"device": jax.devices()[0].device_kind}
    for name, attend in variants.items():
        fn = jax.jit(jax.grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3) if name != "equal-128" else (0, 1, 2)))
        jax.block_until_ready(fn(q, k, v, kr))
        took = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k, v, kr))
            took.append(time.perf_counter() - t0)
        d = os.path.join(ROOT, ".bench_tmp", f"latent-flash-{name}")
        shutil.rmtree(d, ignore_errors=True)
        jax.profiler.start_trace(d)
        for _ in range(3):
            jax.block_until_ready(fn(q, k, v, kr))
        jax.profiler.stop_trace()
        reduced = scopes.reduce_scopes(trace.find_xplane(d))
        out[name] = {"wall_ms": 1e3 * sorted(took)[5],
                     "busy_ms": 1e3 * reduced["busy_s"] / 3,
                     "kernel_ms": {n: 1e3 * s / 3 for n, s in sorted(
                         reduced["kernel_s"].items())}}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
