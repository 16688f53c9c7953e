#!/usr/bin/env python3
"""Records ``train_scoped.xplane.pb``: the trace that checks
``benchmark/lib/scopes.py`` (device time by model scope, program spans on
the host plane).

Run once on the chip (``chiprun -- python3
benchmark/fixtures/record_scoped_fixture.py``); it writes
``chiprun_out/fixture/train_scoped.xplane.pb`` and a listing of the
paths it holds (``train_scoped.txt``). Copy the ``.pb`` beside this
script and leave it alone: ``benchmark/tests/test_scopes.py`` pins its
numbers. Re-record only when the model's scopes change (then the pinned
numbers change with them).

The program is ``record_fixture.py``'s train step (llama at hidden 256, 2
layers, head size 128 so that the flash kernels compile, adamw, batch 2 x
512) as it is in the tree now, with its ``jax.named_scope`` names. Three
steps are traced; the first two run inside a span of the program
(``rtpu.fixture.step``, through ``ray_tpu.util.tracing``), the third
outside any, so that idle gaps with and without a program span exist.
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

    from benchmark.lib import scopes
    from ray_tpu.models import llama
    from ray_tpu.util import tracing

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
    tmp = os.path.join(out_dir, "tmp_train_scoped")
    shutil.rmtree(tmp, ignore_errors=True)
    tracing.start_profile(tmp)
    for i in range(3):
        if i < 2:
            with tracing.span("rtpu.fixture.step"):
                params, opt, loss = step_j(params, opt, batch)
                loss.block_until_ready()
        else:
            params, opt, loss = step_j(params, opt, batch)
            loss.block_until_ready()
    tracing.stop_profile()
    (pb,) = glob.glob(os.path.join(tmp, "plugins/profile/*/*.xplane.pb"))
    dst = os.path.join(out_dir, "train_scoped.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(tmp)

    planes = scopes.read_planes(dst)
    with open(os.path.join(out_dir, "train_scoped.txt"), "w") as f:
        for name, p in planes.items():
            f.write(f"PLANE {name}\n")
            for ln in p["lines"]:
                f.write(f"  LINE {ln['name']!r} events={len(ln['events'])}\n")
            for mid, path in sorted(p["paths"].items()):
                f.write(f"    {scopes.scope_of(path):10s} {path}  <-  "
                        f"{p['names'].get(mid, '')[:80]}\n")
    reduced = scopes.reduce_scopes(dst)
    print("fixture recorded:", os.path.getsize(dst), "bytes")
    print(reduced)


if __name__ == "__main__":
    main()
