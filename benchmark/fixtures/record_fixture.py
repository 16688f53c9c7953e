#!/usr/bin/env python3
"""Records the small profiler trace that checks the trace reduction.

Run once on the chip (``chiprun -- python3 benchmark/fixtures/record_fixture.py``);
it writes ``chiprun_out/fixture/<name>.xplane.pb``. The two files kept
beside this script were recorded this way and then left alone: the
reduction in ``benchmark/lib/trace.py`` is tested against them, so a
change to it that changes a number shows.

The programs are the repo's own (the paged prefill and decode chunk, a
train step with the flash kernel) at a small size with head size 128, so
that the Mosaic kernels compile and the device events carry the names the
real cells print: ``jit_paged_decode_chunk``, ``jit_prefill_chunk``,
``jit_step``.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import llama, llama_paged

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    out_dir = os.path.join("chiprun_out", "fixture")
    os.makedirs(out_dir, exist_ok=True)
    cfg = llama.LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=128, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    params = jax.jit(lambda k: llama.init_params(cfg, k))(
        jax.random.PRNGKey(0))

    def save(tmp: str, name: str) -> None:
        (pb,) = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
        shutil.copy(pb, os.path.join(out_dir, name + ".xplane.pb"))
        shutil.rmtree(tmp)

    # ---- serve: 2 prefill chunks, 3 decode chunks of 4 steps, 4 slots
    S, page, maxp, npages = 4, 64, 4, 16
    prefill, decode = llama_paged.make_paged_engine_fns(cfg, params)
    cache = llama_paged.init_paged_cache(cfg, npages, page)
    bt = jnp.asarray(np.arange(S * maxp, dtype=np.int32).reshape(S, maxp))
    toks = jnp.zeros((S,), jnp.int32)
    pos = jnp.full((S,), 128, jnp.int32)
    act = jnp.ones((S,), bool)
    key = jnp.zeros((2,), jnp.uint32)
    temps = jnp.zeros((S,), jnp.float32)
    row = jnp.ones((1, 128), jnp.int32)

    def serve_round(cache, toks, pos):
        for c in range(2):
            cache, _ = prefill(cache, row, bt[0], jnp.asarray(0, jnp.int32),
                               jnp.asarray(128, jnp.int32))
        for c in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                cache, out, toks, pos = decode(cache, toks, pos, act, bt, 4,
                                               key, temps, 0, False)
            with jax.profiler.TraceAnnotation("bench.wait"):
                np.asarray(out)
            pos = jnp.full((S,), 128, jnp.int32)
        return cache, toks, pos

    cache, toks, pos = serve_round(cache, toks, pos)     # compiles
    tmp = os.path.join(out_dir, "tmp_serve")
    jax.profiler.start_trace(tmp)
    cache, toks, pos = serve_round(cache, toks, pos)
    jax.profiler.stop_trace()
    save(tmp, "serve")

    # ---- train: 3 adamw steps, batch 2 x 512, flash forward + backward
    tx = optax.adamw(1e-3)
    opt = tx.init(params)
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 513), np.int32))}

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, batch))(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    step_j = jax.jit(step, donate_argnums=(0, 1))
    params, opt, loss = step_j(params, opt, batch)
    loss.block_until_ready()
    tmp = os.path.join(out_dir, "tmp_train")
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            params, opt, loss = step_j(params, opt, batch)
            loss.block_until_ready()
    jax.profiler.stop_trace()
    save(tmp, "train")

    # a listing, for reading by hand
    from jax.profiler import ProfileData

    for name in ("serve", "train"):
        pd = ProfileData.from_file(os.path.join(out_dir, name + ".xplane.pb"))
        with open(os.path.join(out_dir, name + ".txt"), "w") as f:
            for plane in pd.planes:
                f.write(f"PLANE {plane.name!r}\n")
                for line in plane.lines:
                    evs = list(line.events)
                    f.write(f"  LINE {line.name!r} events={len(evs)}\n")
                    for ev in evs[:400]:
                        f.write(f"    {ev.start_ns:.0f} {ev.duration_ns:.0f} "
                                f"{ev.name!r} {dict(ev.stats)!r}\n"[:400] + "\n")
    print("fixture recorded:", sorted(os.listdir(out_dir)))


if __name__ == "__main__":
    main()
