"""Kernels of the main path compiled for a v5e chip that is described
and not attached, at published widths: what Mosaic refuses (a tile that
does not fit VMEM, a slice off the tiling) it refuses here, at no chip
time. Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture and never at import: only one
process may load libtpu, and only the xdist worker that is handed this
file does. Keep such tests in this one file.
"""

import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# OLMoE-1B-7B's routed layer at the benchmark cell's tokens: n, h, f, E, K
OLMOE_ROWS = (8192, 2048, 1024, 64, 8)


@pytest.fixture
def S(one_chip):
    """A bf16 array of a shape on the described chip, for ``lower``."""
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)


def _mosaic_calls(text: str):
    """(kernel name, its line) of every Mosaic call in ``text``."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return [(re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)", c).group(1), c)
            for c in calls]


def test_routed_experts_compile_at_olmoe_widths(S, no_compile_cache,
                                                monkeypatch):
    """Forward and backward of the routed layer at OLMoE-1B-7B's widths
    and the benchmark cell's 8,192 tokens: the grouped matmuls are the
    megablox kernels (three ``gmm`` forward, three transposed, three
    ``tgmm``), and they carry the scope ``moe_experts``. The value is
    asked for with the gradient: the gradient alone does not need the
    forward's down projection (PR 29) and compiles to five ``gmm``."""
    from ray_tpu.ops.moe import routed_experts

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, h, f, E, K = OLMOE_ROWS

    def loss(*a):
        return routed_experts(*a, K)[0].astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
                       ).lower(S(n, h), S(h, E), S(E, h, f), S(E, h, f),
                               S(E, f, h)).compile()
    calls = _mosaic_calls(compiled.as_text())
    assert sorted(name for name, _ in calls) == ["gmm"] * 6 + ["tgmm"] * 3
    assert all("moe_experts" in line for _, line in calls)
    # rows, gate, up, activation, their cotangents: a few n*K-row arrays
    # in bf16 and none in float32. 675,798,528 compiled (PR 29; 1,078 MB
    # with the weight behind the down projection) and a tenth
    assert compiled.memory_analysis().temp_size_in_bytes < 709 << 20


def test_rematted_routed_layer_recomputes_two_grouped_matmuls(
        S, no_compile_cache, monkeypatch):
    """The same layer as the train step runs it: stacked three deep
    under ``llama.run_layers``' scan and its "full" ``jax.checkpoint``.
    The backward loop's body holds five ``gmm`` (gate and up recomputed,
    three transposed) and three ``tgmm``: nothing there asks for the
    down projection's output, so its recomputation is gone."""
    from ray_tpu.models import llama
    from ray_tpu.ops.moe import routed_experts

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, h, f, E, K = OLMOE_ROWS

    def loss(x, layers):
        x, _ = llama.run_layers(
            lambda x_, p: (x_ + routed_experts(x_, *p, K)[0], None),
            x, layers, level="full", scan=True)
        return x.astype(jnp.float32).sum()

    layers = (S(3, h, E), S(3, E, h, f), S(3, E, h, f), S(3, E, f, h))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        S(n, h), layers).compile().as_text()
    bodies = [sorted(name for name, _ in _mosaic_calls(body))
              for body in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text)]
    assert sorted(b for b in bodies if b) == [
        ["gmm"] * 3, ["gmm"] * 5 + ["tgmm"] * 3]


def test_remat_plan_holds_against_the_compiler_at_7b_widths(
        one_chip, no_compile_cache, monkeypatch):
    """The adamw train step the benchmark's one-chip dense cell runs
    (Mistral-7B widths, 4 layers, 2 x 4,096 tokens, bf16 state), compiled
    with the level ``remat_policy="auto"`` resolves for a v5e's memory:
    what the plan reckons is not under what the compiler allots and
    within a tenth of it, and the program leaves the plan's reserve free.
    The richest level keeps the flash kernel's residuals, so the forward
    kernel is called once (three Mosaic calls, not four)."""
    import optax

    from ray_tpu.models import llama
    from ray_tpu.util import tracing

    limit = int(15.75 * 2 ** 30)          # a v5e chip's bytes_limit
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: limit)
    cfg = llama.LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=4, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1e6, param_dtype=jnp.bfloat16)

    def placed(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    tx = optax.adamw(1e-4)
    params = llama.init_shapes(cfg)
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 4097), jnp.int32,
                                            sharding=one_chip)}

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, batch))(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    n0 = len(tracing.chrome_events())
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        placed(params), placed(opt), batch).compile()
    (plan,) = [e["args"] for e in tracing.chrome_events()[n0:]
               if e["name"] == "rtpu.train.remat_plan"]
    assert plan["level"] == "level4"
    ma = compiled.memory_analysis()
    allotted = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert allotted <= plan["need_bytes"] <= 1.1 * allotted, (
        allotted, plan["need_bytes"])
    assert allotted <= (1 - llama.REMAT_RESERVE) * limit
    assert compiled.as_text().count("tpu_custom_call") == 3
