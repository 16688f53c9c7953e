"""On the chip: what the layer that holds a share of its experts
(``ops/moe.routed_experts(held=...)``) costs against the rows held, at
the sizes of ``train-laguna-1chip`` (16,384 tokens of 3072, 10 of 256
experts a token, experts 0-15 of 1024 held): forward and backward of the
op alone at routings that hold none of the rows, a part of the balanced
share, the share, several passes' worth and every row, each against a
dense loop over the 16 held experts (output and three gradients), in one
compiled program; and a least-squares fit of the time to a constant, a
cost a pass and a cost a row.

    python3 benchmark/tests/held_rows_scaling.py

Wall time around ``block_until_ready`` of the jitted gradient, median of
seven; PERF.md 5 quotes it (PR 30).
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

N, H, F, E, K, HELD = 16384, 3072, 1024, 256, 10, 16
# added to the held experts' router logits, in units of the logits' spread
SHIFTS = (-30.0, -0.7, -0.3, 0.0, 0.3, 0.7, 1.5, 30.0)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jnp.abs(jax.random.normal(ks[0], (N, H), jnp.bfloat16))
    base = jax.random.normal(ks[1], (H, E), jnp.float32) * 0.02
    eg, eu = ((jax.random.normal(k, (HELD, H, F)) / 55).astype(jnp.bfloat16)
              for k in ks[2:4])
    ed = (jax.random.normal(ks[4], (HELD, F, H)) / 32).astype(jnp.bfloat16)
    cot = jax.random.normal(ks[5], (N, H), jnp.bfloat16)
    chunk = moe._held_chunk(N * K, HELD, E)
    f32 = jnp.float32

    def held_fn(x, r):
        def loss(x, r, eg, eu, ed):
            out, _, counts = moe.routed_experts(
                x, r, eg, eu, ed, K, True, held=(0, HELD), scale=2.5)
            return (out.astype(f32) * cot).sum(), (out, counts)
        (_, (out, counts)), g = jax.value_and_grad(
            loss, argnums=(0, 2, 4), has_aux=True)(x, r, eg, eu, ed)
        return out, counts, g

    def loop_fn(x, r):
        def loss(x, r, eg, eu, ed):
            probs = jax.nn.softmax(jnp.dot(x, r.astype(x.dtype),
                                           preferred_element_type=f32), -1)
            tw, te = jax.lax.top_k(probs, K)
            tw = 2.5 * tw / tw.sum(-1, keepdims=True)
            out = jnp.zeros(x.shape, f32)
            for e in range(HELD):
                gate = jnp.where(te == e, tw, 0.0).sum(-1)
                a = jax.nn.silu(jnp.dot(x, eg[e], preferred_element_type=f32)
                                ) * jnp.dot(x, eu[e],
                                            preferred_element_type=f32)
                out = out + jnp.dot((a * gate[:, None]).astype(x.dtype),
                                    ed[e], preferred_element_type=f32)
            out = out.astype(x.dtype)
            return (out.astype(f32) * cot).sum(), out
        (_, out), g = jax.value_and_grad(
            loss, argnums=(0, 2, 4), has_aux=True)(x, r, eg, eu, ed)
        return out, g

    def rel(a, b):
        a, b = a.astype(f32), b.astype(f32)
        return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))

    hf, lf = jax.jit(held_fn), jax.jit(loop_fn)
    per_logit = float(0.02 * np.sqrt(H) / x.astype(f32).sum(-1).mean())
    rows = []
    for shift in SHIFTS:
        r = base.at[:, :HELD].add(shift * per_logit)
        out, counts, g = hf(x, r)
        want, wg = lf(x, r)
        jax.block_until_ready((out, want))
        took = []
        for _ in range(7):
            t0 = time.perf_counter()
            jax.block_until_ready(hf(x, r))
            took.append(time.perf_counter() - t0)
        held = int(counts[:HELD].sum())
        rows.append({"shift": shift, "held_rows": held,
                     "passes": -(-held // chunk),
                     "fwd_bwd_ms": 1e3 * sorted(took)[3],
                     "out_rel": rel(out, want),
                     "grads_rel": [rel(a, b) for a, b in zip(g, wg)]})
    a = np.array([[1.0, r["passes"], r["held_rows"]] for r in rows])
    fit, *_ = np.linalg.lstsq(a, np.array([r["fwd_bwd_ms"] for r in rows]),
                              rcond=None)
    out = {"device": jax.devices()[0].device_kind, "chunk": chunk,
           "routings": rows, "programs": hf._cache_size(),
           "fit_ms": {"constant": fit[0], "a_pass": fit[1],
                      "a_row": fit[2]},
           "fit_residual_ms": float(np.abs(
               a @ fit - [r["fwd_bwd_ms"] for r in rows]).max())}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
