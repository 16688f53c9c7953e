"""On the chip: device time of the window flash kernels (forward and
both backward calls, Laguna's sliding layer: 72 query heads, 8 kv heads
of 128, window 512) at 4,096 and at 8,192 positions, same token count
(4 x 4,096 against 2 x 8,192), and of the causal kernels beside them; a
time that grows with the window and not with the sequence reads a ratio
near 1 a token (2 a sequence), where causal reads 2 (4).

    python3 benchmark/tests/window_scaling.py

Wall time around ``block_until_ready`` of a jitted gradient (median of
ten), and the kernels' own device time from a trace of three calls
(``lib/scopes.py``'s ``kernel_s``, by kernel name).
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
    from ray_tpu.ops.attention import flash_attention

    out = {"device": jax.devices()[0].device_kind}
    for window in (512, None):
        for batch, seq in ((4, 4096), (2, 8192)):
            ks = jax.random.split(jax.random.PRNGKey(seq), 3)
            q = jax.random.normal(ks[0], (batch, seq, 72, 128), jnp.bfloat16)
            k = jax.random.normal(ks[1], (batch, seq, 8, 128), jnp.bfloat16)
            v = jax.random.normal(ks[2], (batch, seq, 8, 128), jnp.bfloat16)
            fn = jax.jit(jax.grad(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, window=window
                ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
            jax.block_until_ready(fn(q, k, v))
            took = []
            for _ in range(10):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, k, v))
                took.append(time.perf_counter() - t0)
            out[f"window={window} {batch}x{seq} ms"] = 1e3 * sorted(took)[5]
            # the kernels' own device time, three calls traced
            d = os.path.join(ROOT, ".bench_tmp", f"scaling-{window}-{seq}")
            shutil.rmtree(d, ignore_errors=True)
            jax.profiler.start_trace(d)
            for _ in range(3):
                jax.block_until_ready(fn(q, k, v))
            jax.profiler.stop_trace()
            out[f"window={window} {batch}x{seq} kernel ms"] = {
                name: 1e3 * s / 3 for name, s in sorted(scopes.reduce_scopes(
                    trace.find_xplane(d))["kernel_s"].items())}
    for window in (512, None):
        a, b = (out[f"window={window} {bs} ms"] for bs in ("4x4096", "2x8192"))
        out[f"window={window} ratio at equal tokens"] = b / a
        ka, kb = (sum(out[f"window={window} {bs} kernel ms"].values())
                  for bs in ("4x4096", "2x8192"))
        out[f"window={window} kernel ratio at equal tokens"] = kb / ka
    print(json.dumps(out))


if __name__ == "__main__":
    main()
