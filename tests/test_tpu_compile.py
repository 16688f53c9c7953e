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


def _placed(tree, sharding):
    return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


V5E_LIMIT = int(15.75 * 2 ** 30)          # a v5e chip's bytes_limit


def _planned(lower):
    """``lower()`` compiled, and the one remat plan its trace wrote, held
    against the compiler: the program is not more than 3% over what the
    plan reckoned, the plan not more than a tenth over the program, and
    the program leaves the plan's reserve free."""
    from ray_tpu.models import llama
    from ray_tpu.util import tracing

    n0 = len(tracing.chrome_events())
    compiled = lower().compile()
    (plan,) = [e["args"] for e in tracing.chrome_events()[n0:]
               if e["name"] == "rtpu.train.remat_plan"]
    ma = compiled.memory_analysis()
    allotted = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert allotted <= 1.03 * plan["need_bytes"] <= 1.03 * 1.1 * allotted, (
        allotted, plan["need_bytes"])
    assert allotted <= (1 - llama.REMAT_RESERVE) * V5E_LIMIT
    return compiled, plan


def test_laguna_cell_step_compiles_within_a_v5e_chip(one_chip,
                                                     no_compile_cache,
                                                     monkeypatch):
    """The adamw step of the benchmark's ``train-laguna-1chip`` at its
    published widths (2 x 8,192 tokens, five layers of three kinds, 16 of
    256 experts held, bf16 state: ``benchmark/configs/
    laguna-s-2.1-c1.json``): Mosaic takes the window kernels and the
    8,192-position dK/dV call (which asks for more than the default
    scoped VMEM), the passes over the held rows compile to loops whose
    trip count is data; the remat plan gives each kind its level for a
    v5e's memory, and the program fits what it reckoned."""
    import json

    import optax

    from ray_tpu.models import laguna, llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: V5E_LIMIT)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-s-2.1-c1.json")) as f:
        kw = {k: tuple(v) if isinstance(v, list) else v
              for k, v in json.load(f)["model_config"].items()}
    assert (kw.pop("module"), kw.pop("preset")) == ("laguna", "laguna_s_2_1")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = laguna.LagunaConfig.laguna_s_2_1(**kw)
    assert cfg.pattern == ("full_dense", "sliding_moe", "sliding_moe",
                           "sliding_moe", "full_moe")
    tx = optax.adamw(1e-4)
    params = jax.eval_shape(lambda k: laguna.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 1_113_007_104
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 8193), jnp.int32,
                                            sharding=one_chip)}

    def step(params, opt, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: laguna.loss_terms(cfg, p, batch), has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt, loss,
                aux["expert_counts"])

    compiled, plan = _planned(lambda: jax.jit(
        step, donate_argnums=(0, 1)).lower(
            _placed(params, one_chip), _placed(opt, one_chip), batch))
    # by kind: the walked dense layer keeps its flash outputs, q/k/v, the
    # two products of its 12,288-wide SwiGLU and, since a pass over the
    # held rows is 11,520 rows for 20,480 (PR 35: the routed kinds'
    # working set is 0.22 GB less), its attention's residual sum too; the
    # scanned sliding layers their flash outputs, the last layer all four
    assert plan["level"] == {"full_dense": "level4", "sliding_moe": "level1",
                             "full_moe": "level4"}
    # five flash forwards, not ten; five dQ and five dK/dV calls
    calls = [name for name, _ in _mosaic_calls(compiled.as_text())]
    assert [sum(n == name for n in calls) for name in (
        "flash_fwd", "flash_win_fwd", "flash_bwd_dq", "flash_win_bwd_dq")
            ] == [2, 1, 2, 1]      # the three sliding layers are one scan
    # (inside the held rows' backward pass jax names megablox's calls
    # after the transformation it traced them under)
    names = {name for name, _ in _mosaic_calls(compiled.as_text())}
    assert names == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                     "flash_win_fwd", "flash_win_bwd_dq",
                     "flash_win_bwd_dkv", "gmm", "jvp_jit_gmm__",
                     "jvp_jit_tgmm__"}


@pytest.mark.parametrize("kv_heads", [8, 32], ids=["gqa-32-on-8", "mha"])
def test_flash_kernels_compile_at_a_head_of_64(S, no_compile_cache,
                                               kv_heads):
    """LFM2's heads are 64 wide, half a lane tile: Mosaic takes the causal
    forward and both backward kernels at 2 x 8,192 positions as they are
    (no padding inside the call), at the model's 32 query heads on 8 and
    with as many kv heads as query heads."""
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, use_pallas=True).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        S(2, 8192, 32, 64), S(2, 8192, kv_heads, 64),
        S(2, 8192, kv_heads, 64)).compile().as_text()
    # (outside a model jax names a call after the transformations it
    # traced it under: ``transpose_jvp_flash_bwd_dq__``)
    names = {name for name, _ in _mosaic_calls(text)}
    assert len(names) == 3
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(kernel in name for name in names), (kernel, names)


def test_flash_kernels_compile_at_32k_positions_of_64(S, no_compile_cache):
    """Granite 4.0-H's attention layer in its cell: one sequence of 32,768
    positions, 32 query heads on 8 of 64, scores scaled by 1/64. A head's
    whole k and v (forward, dQ) or q and dO (dK/dV) lie in VMEM, a row of
    64 padded to 128 lanes: 33.5 MB, past Mosaic's default 16 MB, so every
    kernel asks for its limit (``_dkv_vmem``) and Mosaic takes all three."""
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, use_pallas=True,
                               sm_scale=0.015625).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        S(1, 32768, 32, 64), S(1, 32768, 8, 64),
        S(1, 32768, 8, 64)).compile().as_text()
    names = {name for name, _ in _mosaic_calls(text)}
    assert len(names) == 3
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(kernel in name for name in names), (kernel, names)


MIXER_SHAPES = {"m_in": (2048, 8512), "m_conv": (4352, 4),
                "m_conv_bias": (4352,), "dt_bias": (64,), "A_log": (64,),
                "D": (64,), "m_norm": (4096,), "m_out": (4096, 2048)}


@pytest.fixture
def mixer_gradient(S, no_compile_cache, monkeypatch):
    """One Mamba-2 mixer at the published widths over 32,768 positions,
    forward and backward, compiled as a TPU runs it."""
    from ray_tpu.ops.ssm import mamba2_mixer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(h, p):
        out, last = mamba2_mixer(h, p, heads=64, head_dim=64, state=128)
        return jnp.square(out.astype(jnp.float32)).sum() + jnp.abs(last).max()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        S(1, 32768, 2048),
        {k: S(*v) for k, v in MIXER_SHAPES.items()}).compile()


def test_mamba2_mixer_compiles_without_all_chunks_decay_matrices(
        mixer_gradient):
    """The mixer's four Mosaic calls: the taps, their bias and the silu are
    ``ops/conv.taps_silu``'s two, one forward and one backward, and the
    scan ``ops/ssm.scan_kernels``' two, ``ssd_scan_fwd`` (the forward that
    keeps its states) and ``ssd_scan_bwd``. A chunk's decay matrices live
    in VMEM: no float32 array of ``[.., 256, 256]`` lies in HBM under
    ``ssm_scan`` (XLA's walk put 8 chunks' there, 134 MB a step of 16, and
    all 128 chunks' at once would be 2.1 GB and as much again for the
    backward); the skip ``D x`` is the kernels' too. The whole
    gradient's temporaries are under 2.2 GB (2.74 GB with XLA's walk, PR
    37; 3.89 GB with XLA's taps too, PR 36): the projections' outputs,
    the gate's passes and their gradients."""
    text = mixer_gradient.as_text()
    names = sorted(name for name, _ in _mosaic_calls(text))
    assert len(names) == 4, names
    for kernel in ("ssd_scan_bwd", "ssd_scan_fwd", "taps_silu_bwd",
                   "taps_silu_fwd"):
        assert any(kernel in name for name in names), (kernel, names)
    in_hbm = [line for line in text.splitlines() if "ssm_scan" in line
              and re.search(r"f32\[[0-9,]*256,256\]", line)]
    assert not in_hbm, in_hbm[:3]
    assert (mixer_gradient.memory_analysis().temp_size_in_bytes
            < 2.2 * 2 ** 30)


def test_mamba2_mixer_keeps_no_float32_copy_of_the_taps_channels(
        mixer_gradient):
    """Nothing the size of the taps' 4,352 channels at 32,768 positions is
    float32 in HBM under ``ssm_conv``: XLA's form laid four shifted copies
    of it out, forward and backward (PERF.md 6, PR 37); the kernels keep
    what is float32 in VMEM and move bf16."""
    wide = [line for line in mixer_gradient.as_text().splitlines()
            if "ssm_conv" in line
            and re.search(r"f32\[1,(32768,4352|4352,32768)\]", line)]
    assert not wide, wide[:3]


def test_conv_mix_pass_compiles_to_fusions_without_a_kernel(
        S, no_compile_cache):
    """The pass between a convolution's two projections at LFM2's width
    and the cell's tokens, forward and backward: plain XLA fusions (no
    Mosaic call, no convolution instruction) within a gigabyte of
    temporaries."""
    from ray_tpu.ops.conv import conv_mix

    def loss(bcx, w):
        return conv_mix(bcx, w).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        S(2, 8192, 6144), S(2048, 3)).compile()
    text = compiled.as_text()
    assert not _mosaic_calls(text) and " convolution(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_lfm2_cell_step_compiles_within_a_v5e_chip(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch):
    """The step of the benchmark's ``train-lfm2-1chip`` at its published
    widths (2 x 8,192 tokens, five layers of three kinds, 16 of 32 experts
    held, bf16 state and float32 biases: ``benchmark/configs/
    lfm2-8b-a1b-c1.json``), built by the cell's own ``make_step``: adamw
    on ``trainable(params)``, then the bias update. Mosaic takes the flash
    kernels at a head of 64, the held rows' passes compile, the biases are
    no part of adamw's state, and the program fits what the remat plan
    reckoned for a v5e's memory."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)                  # benchmark/ lies beside tests/
    from benchmark.cells import train_hybrid
    from ray_tpu.models import lfm2, llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: V5E_LIMIT)
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-8b-a1b-c1.json")) as f:
        model, _, cfg = train_hybrid.load_model(json.load(f)["model_config"])
    assert model is lfm2
    assert cfg.pattern == ("conv_dense", "attn_moe", "conv_moe", "conv_moe",
                           "conv_moe")
    with open(os.path.join(root, "benchmark", "traffic",
                           "train-lfm2-1chip.json")) as f:
        tx = train_hybrid.optimizer(json.load(f))
    params = jax.eval_shape(lambda k: lfm2.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 893_696_256
    opt = jax.eval_shape(tx.init, lfm2.trainable(params))
    assert sum(a.size for a in jax.tree_util.tree_leaves(opt)) \
        == 2 * (893_696_256 - 4 * 32) + 2          # two moments, two counts
    batch = {"tokens": jax.ShapeDtypeStruct((2, 8193), jnp.int32,
                                            sharding=one_chip)}
    compiled, plan = _planned(lambda: jax.jit(
        train_hybrid.make_step(lfm2, cfg, tx), donate_argnums=(0, 1)).lower(
            _placed(params, one_chip), _placed(opt, one_chip), batch))
    # a convolution keeps nothing of its operator: the dense layer the
    # two products of its SwiGLU, the routed ones nothing (held experts)
    assert plan["level"] == {"conv_dense": "level3", "attn_moe": "level4",
                             "conv_moe": "full"}
    text = compiled.as_text()
    assert sum(name == "flash_fwd" for name, _ in _mosaic_calls(text)) == 1
    assert {name for name, _ in _mosaic_calls(text)} == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "gmm",
        "jvp_jit_gmm__", "jvp_jit_tgmm__"}
    assert re.search(r'op_name="[^"]*/moe_route/moe_bias_update/', text)
    assert re.search(r'op_name="[^"]*short_conv[^"]*/conv_mix/', text)


@pytest.mark.parametrize("model", ["llama", "olmoe"])
def test_one_kind_of_layer_compiles_the_scan_it_always_did(
        model, one_chip, no_compile_cache, monkeypatch):
    """``window``, ``held`` and ``pattern`` at their defaults: a dense
    and an OLMoE gradient step (head size 128, the flash and megablox
    kernels in) through ``llama.run_layers`` compile, metadata aside, to
    the text they compile to through the walker written out as it was
    before layers had kinds, one ``lax.scan`` of one checkpointed layer,
    and no window call is in it. (``step_program.py --compare`` holds the
    cells' whole steps to the parent's text.)"""
    from ray_tpu.models import llama, olmoe
    from ray_tpu.tools.step_program import strip_metadata

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sizes = dict(vocab_size=512, hidden_size=256, num_layers=2, num_heads=2,
                 num_kv_heads=1, head_dim=128, max_seq_len=512,
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                 remat_policy="full")
    if model == "llama":
        mod, cfg = llama, llama.LlamaConfig(intermediate_size=512, **sizes)
    else:
        mod, cfg = olmoe, olmoe.OlmoeConfig(
            intermediate_size=128, num_experts=8, top_k=2, **sizes)
    params = _placed(jax.eval_shape(lambda k: mod.init_params(cfg, k),
                                    jax.random.PRNGKey(0)), one_chip)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 513), jnp.int32,
                                            sharding=one_chip)}

    def text():
        jax.config.update("jax_traceback_in_locations_limit", 0)
        return strip_metadata(jax.jit(jax.grad(
            lambda p, b: mod.loss_fn(cfg, p, b))).lower(
            params, batch).compile().as_text())

    def walker_before(layer_fn, x, layers, *, level, scan, pattern=None):
        assert level == "full" and scan and pattern is None
        return jax.lax.scan(jax.checkpoint(layer_fn), x, layers)

    limit = jax.config.jax_traceback_in_locations_limit
    try:
        now = text()
        monkeypatch.setattr(llama, "run_layers", walker_before)
        before = text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert now == before
    names = {name for name, _ in _mosaic_calls(now)}
    assert names == ({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
                     | ({"gmm", "tgmm"} if model == "olmoe" else set()))
    assert "flash_win" not in now


def test_taps_kernels_compile_over_a_delta_layers_channels(S,
                                                           no_compile_cache):
    """The taps of an Olmo-Hybrid linear layer through Granite's kernel
    pair: q, k and v's 11,520 channels read where the in-projection left
    them (after the gate's 5,760, before a and b's 60) over 32,768
    positions, in blocks of 64 channels (what divides 5,760, 2,880 and
    5,760), a zero bias, three outputs. Mosaic takes both calls."""
    from ray_tpu.ops.conv import taps_silu

    def loss(u, w):
        q, k, v = taps_silu(u, w, jnp.zeros((11520,), jnp.float32),
                            first=5760, sizes=(2880, 2880, 5760))
        return sum(a.astype(jnp.float32).sum() for a in (q, k, v))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        S(1, 17340, 32768), S(11520, 4)).compile().as_text()
    names = [name for name, _ in _mosaic_calls(text)]
    assert len(names) == 2, names
    for kernel in ("taps_silu_fwd", "taps_silu_bwd"):
        assert any(kernel in name for name in names), (kernel, names)


def _arrays_under(text: str, scope: str):
    """(op, dtype, elements) of every instruction of a compiled program's
    text, fused or not, whose ``op_name`` holds ``scope``."""
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        head = line.strip().split(" = ", 1)
        if not name or scope not in name.group(1) or len(head) < 2:
            continue
        shape = re.match(r"\(?(\w+)\[([\d,]*)\]", head[1])
        op = re.search(r"\}?\)? ([a-z\-]+)\(", head[1])
        if shape and op:
            elements = 1
            for d in shape.group(2).split(","):
                elements *= int(d or 1)
            found.append((op.group(1), shape.group(1), elements))
    return found


def _mixer_compiles_with_nothing_around_the_rule(S, hidden, heads, key_heads,
                                                 key_dim, value_dim):
    """The gradient of ``gated_delta_mixer`` at 1 x 32,768 positions of a
    cell's widths, compiled for a v5e: the rule is two Mosaic calls (the
    forward that keeps its states and the backward), and under
    ``gdn_rule`` nothing else of the program holds an array of q's, k's,
    v's or o's size: no swap, no norm pass, no head-major copy, no copy to
    more heads, no sum over pairs; what XLA still does there are the gates,
    the running sums and their two layouts, float32 [s, heads] (the
    largest, ``cols``, twice that)."""
    from ray_tpu.ops import delta

    s = 32768
    hv, hk = heads * value_dim, key_heads * key_dim
    p = {"g_in": S(hidden, 2 * hv + 2 * hk + 2 * heads),
         "g_conv": S(2 * hk + hv, 4), "g_dt_bias": S(heads),
         "g_A_log": S(heads), "g_norm": S(value_dim), "g_out": S(hv, hidden)}

    def loss(h, p):
        return jnp.square(delta.gated_delta_mixer(
            h, p, heads=heads, key_heads=key_heads, key_dim=key_dim,
            value_dim=value_dim, chunk=64)[0].astype(jnp.float32)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        S(1, s, hidden), p).compile().as_text()
    names = [name for name, _ in _mosaic_calls(text) if "delta_rule" in name]
    assert len(names) == 2, names        # (named after the transformation)
    for kernel in ("delta_rule_fwd", "delta_rule_bwd"):
        assert any(kernel in name for name in names), (kernel, names)
    around = [a for a in _arrays_under(text, "gdn_rule") if a[0] not in (
        "custom-call", "get-tuple-element", "bitcast")]
    assert around and all(n <= 2 * s * heads and dtype != "bf16" or n <= s
                          * heads for _, dtype, n in around), sorted(
        set(a for a in around if a[2] > s * heads))
    return text


def test_delta_rule_kernels_compile_at_the_cells_shapes(S, no_compile_cache,
                                                       monkeypatch):
    """The mixer of ``train-olmo-hybrid-1chip`` (1 x 32,768 positions of
    3,840, 30 heads, keys of 96 and values of 192, bfloat16) on a TPU
    backend: Mosaic takes the rule's two calls on operands positions last
    (keys of 96 and values of 192 are whole sublane tiles there, where they
    are not whole registers along the lanes), 15 heads a grid step, and
    XLA relays nothing for them."""
    from ray_tpu.ops import delta

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = delta.rule_plan(1, 32768, 30, 96, 192, 64)
    assert (plan["form"], plan["heads_a_block"], plan["joined"],
            plan["operands"]) == ("pallas", 15, None, "positions_last")
    _mixer_compiles_with_nothing_around_the_rule(S, 3840, 30, 30, 96, 192)


def _vocab_products(text: str, block: int, vocab: int) -> int:
    """The ``dot_general`` of a lowered step that read or write a
    ``[block, vocab]`` array: the blocked head's products, three a block
    since its loss takes a block's gradients while the logits stand
    (``ops/layers.blocked_head_loss``), four when a checkpointed block
    rebuilt them."""
    return sum("dot_general" in line and f"{block}x{vocab}x" in line
               for line in text.splitlines())


def test_olmo_hybrid_cell_step_lowers_for_a_v5e_chip(one_chip,
                                                     no_compile_cache,
                                                     monkeypatch):
    """The step of the benchmark's ``train-olmo-hybrid-1chip`` at its
    published widths (1 x 32,768 tokens, three delta-rule layers and one
    full layer, 12,544 rows of the vocabulary, bf16 state:
    ``benchmark/configs/olmo-hybrid-7b-c1.json``), built by the cell's own
    ``make_step`` and lowered for a v5e (Mosaic's own lowering of every
    kernel call; the whole program's compile, a minute and a half, is
    ``tools/step_program.py``'s): the taps' pair and the rule's pair once
    for the scanned linear layers and the three flash kernels at a head of
    128 without rope; the rule runs as its kernels, 15 heads and 8 chunks
    of 64 a grid step, 64 states kept; the plan reckons more than a v5e's
    budget at every layer's "full", so no rung is taken, and its need
    lies within 3% of the 18,010,376,704 bytes the compiler allots that
    step (``step_program.py``, PR 53: the rule's operands positions last)."""
    import json

    import optax

    from benchmark.cells import train_delta
    from ray_tpu.models import llama, olmo_hybrid
    from ray_tpu.util import tracing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: V5E_LIMIT)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b-c1.json")) as f:
        kw = {k: tuple(v) if isinstance(v, list) else v
              for k, v in json.load(f)["model_config"].items()}
    assert (kw.pop("module"), kw.pop("preset")) == ("olmo_hybrid",
                                                    "olmo_hybrid_7b")
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    cfg = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(**kw)
    assert cfg.pattern == ("linear", "linear", "linear", "full")
    tx = optax.adamw(optax.linear_schedule(0.0, 1e-4, 2000))
    params = jax.eval_shape(lambda k: olmo_hybrid.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == 928_862_196
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 32769), jnp.int32,
                                            sharding=one_chip)}
    n0 = len(tracing.chrome_events())
    lowered = jax.jit(train_delta.make_step(olmo_hybrid, cfg, tx),
                      donate_argnums=(0, 1)).lower(
        _placed(params, one_chip), _placed(opt, one_chip), batch)
    spans = {}
    for e in tracing.chrome_events()[n0:]:
        spans.setdefault(e["name"], []).append(e["args"])
    (plan,) = spans["rtpu.train.remat_plan"]
    assert plan["level"] == {"linear": "full", "full": "full"}
    assert plan["need_bytes"] > (1 - llama.REMAT_RESERVE) * V5E_LIMIT
    assert 1.0 < plan["need_bytes"] / 18_010_376_704 < 1.03
    assert {(r["form"], r["chunks"], r["heads_a_block"], r["chunks_a_call"],
             r["states_kept"], r["operands"])
            for r in spans["rtpu.gdn.rule_plan"]} == {
        ("pallas", 512, 15, 8, 64, "positions_last")}
    assert {(c["form"], c["block_channels"])
            for c in spans["rtpu.gdn.conv_plan"]} == {("pallas", 64)}
    text = lowered.as_text()
    for kernel in ("taps_silu_fwd", "taps_silu_bwd", "delta_rule_fwd",
                   "delta_rule_bwd", "flash_fwd", "flash_bwd_dq",
                   "flash_bwd_dkv"):
        assert kernel in text, kernel
    assert _vocab_products(text, 16_384, 12_544) == 3
    assert lowered.out_info[3].shape == ()


def test_scan_kernels_compile_and_the_granite_cells_step_names_them(
        S, one_chip, no_compile_cache, monkeypatch):
    """The selective scan at ``train-granite-1chip``'s shapes (1 x 32,768
    positions, 64 heads of 64, a state of 128, one group, chunk 256,
    bfloat16; ``dt`` and ``A`` float32) on a TPU backend: Mosaic takes the
    forward call alone, and the forward that keeps its states and the
    backward call of the gradient; nothing else of the program is a
    kernel. The cell's own step (``benchmark/configs/
    granite-4.0-h-micro-c1.json``, ``train_scan.make_step``), lowered for a
    v5e, names the scan's pair beside the taps' pair and the three flash
    kernels; the scan runs as its kernels, ``KERNEL_HEADS`` heads and
    ``KERNEL_CHUNKS`` chunks of 256 a grid step, a state kept a step; the
    plan reckons more than a v5e's budget at every layer's "full", so no
    rung is taken, and its need lies within 6% of the 15,429,915,136 bytes
    the compiler allots that step (``step_program.py``, PR 41: 5.2% over;
    a reckoning within 3% would lie under the budget and hand the
    attention layer its first rung, S3c's to do)."""
    import json

    import optax

    from benchmark.cells import train_scan
    from ray_tpu.models import granite, llama
    from ray_tpu.ops import ssm
    from ray_tpu.util import tracing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: V5E_LIMIT)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (S(1, 32768, 64, 64), f32(1, 32768, 64), f32(64),
            S(1, 32768, 1, 128), S(1, 32768, 1, 128))
    assert ssm.scan_plan(1, 32768, 64, 64, 128, 1, 256)["form"] == "pallas"

    def loss(*a):
        return jnp.square(ssm.ssd_scan(*a)[0].astype(jnp.float32)).sum()

    forward = jax.jit(ssm.ssd_scan).lower(*args).compile().as_text()
    assert [name for name, _ in _mosaic_calls(forward)] == ["ssd_scan_fwd"]
    gradient = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    names = [name for name, _ in _mosaic_calls(gradient)]
    assert len(names) == 2, names        # (named after the transformation)
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert any(kernel in name for name in names), (kernel, names)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-micro-c1.json")) as f:
        model, _, cfg = train_scan.load_model(json.load(f)["model_config"])
    assert model is granite and cfg.pattern.count("mamba") == 9
    tx = optax.adamw(optax.linear_schedule(0.0, 1e-4, 2000))
    params = jax.eval_shape(lambda k: granite.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 32769), jnp.int32,
                                            sharding=one_chip)}
    n0 = len(tracing.chrome_events())
    lowered = jax.jit(train_scan.make_step(granite, cfg, tx),
                      donate_argnums=(0, 1)).lower(
        _placed(params, one_chip), _placed(opt, one_chip), batch)
    spans = {}
    for e in tracing.chrome_events()[n0:]:
        spans.setdefault(e["name"], []).append(e["args"])
    (plan,) = spans["rtpu.train.remat_plan"]
    assert plan["level"] == {"mamba": "full", "attention": "full"}
    assert plan["need_bytes"] > (1 - llama.REMAT_RESERVE) * V5E_LIMIT
    assert 1.0 < plan["need_bytes"] / 15_429_915_136 < 1.06
    steps = 128 // ssm.KERNEL_CHUNKS
    assert {(r["form"], r["chunks"], r["heads_a_block"], r["chunks_a_call"],
             r["states_kept"], r["decay_bytes_in_hbm"])
            for r in spans["rtpu.ssm.scan_plan"]} == {
        ("pallas", 128, ssm.KERNEL_HEADS, ssm.KERNEL_CHUNKS, steps, 0)}
    text = lowered.as_text()
    for kernel in ("taps_silu_fwd", "taps_silu_bwd", "ssd_scan_fwd",
                   "ssd_scan_bwd", "flash_fwd", "flash_bwd_dq",
                   "flash_bwd_dkv"):
        assert kernel in text, kernel
    assert _vocab_products(text, 2_048, 100_352) == 3


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "one-key"])
def test_flash_kernels_compile_at_keys_of_192_and_values_of_128(
        S, no_compile_cache, shared):
    """Latent attention in its cell: one sequence of 8,192 positions, 32
    heads, keys of 128 + 64 rope dims, values of 128. Mosaic takes the
    forward and both backward kernels with the 64 rope dims as one key a
    position that all heads share (a second product of 64 lanes inside the
    kernel, what ``ops/mla.py`` runs) and as part of one 192-wide key a
    head; a head's whole rows lie in VMEM at 128 lanes a row or a multiple
    (12.6 MB and a tile's temporaries), so each call asks for its limit."""
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v, kx=None):
        return flash_attention(q, k, v, k_shared=kx, use_pallas=True,
                               sm_scale=0.114721).astype(jnp.float32).sum()

    args = ((S(1, 8192, 32, 192), S(1, 8192, 32, 128), S(1, 8192, 32, 128),
             S(1, 8192, 64)) if shared else
            (S(1, 8192, 32, 192), S(1, 8192, 32, 192), S(1, 8192, 32, 128)))
    text = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(
        *args).compile().as_text()
    calls = _mosaic_calls(text)
    names = {name for name, _ in calls}
    assert len(names) == 3
    for kernel in ("flash_kv_fwd", "flash_kv_bwd_dq", "flash_kv_bwd_dkv"):
        assert any(kernel in name for name in names), (kernel, names)
    # no value is padded to the keys' width: every operand of 192 lanes is
    # a query, a key or their gradient
    for _, line in calls:
        assert "8192,256]" not in line


@pytest.mark.parametrize("block", [128, 256], ids=["the-walks-block",
                                                   "the-checks-block"])
def test_index_score_kernels_compile_at_the_cells_shapes(
        S, one_chip, no_compile_cache, monkeypatch, block):
    """The index's scores at ``train-dots3-1chip``'s shapes (64 heads of
    128 against 16,384 keys, bfloat16; the head weights float32) for the
    walk's blocks of 128 queries and the check's of 256, on a TPU
    backend: Mosaic takes the forward call, and the backward call of the
    three gradients; nothing ``[block, 64, keys]`` is left in the
    program."""
    from ray_tpu.ops import dsa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dsa.scores_plan(block, 16384, 64, 128) == {
        "scores_form": "kernel", "scores_tile": dsa.SCORE_TILE}
    args = (S(block, 64, 128), S(16384, 128),
            jax.ShapeDtypeStruct((block, 64), jnp.float32,
                                 sharding=one_chip))
    forward = jax.jit(dsa.index_scores).lower(*args).compile().as_text()
    assert [name for name, _ in _mosaic_calls(forward)] == ["dsa_scores_fwd"]

    def loss(*a):
        return jnp.square(dsa.index_scores(*a)).sum()

    gradient = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    names = [name for name, _ in _mosaic_calls(gradient)]
    assert len(names) == 2, names        # (named after the transformation)
    for kernel in ("dsa_scores_fwd", "dsa_scores_bwd"):
        assert any(kernel in name for name in names), (kernel, names)
    for text in (forward, gradient):
        assert f"[{block},64,16384]" not in text


@pytest.mark.parametrize("block", [128, 256], ids=["the-walks-block",
                                                   "a-block-of-256"])
def test_attend_kernels_compile_at_the_cells_shapes(
        S, one_chip, no_compile_cache, monkeypatch, block):
    """Attention over the choice at ``train-dots3-1chip``'s shapes (16
    heads, keys of 128 beside the rope's 64, values of 128, 16,384 keys,
    bfloat16, heads first; the choice a bool mask), on a TPU backend:
    Mosaic takes the forward call, and the backward call of the four
    gradients; nothing float32 ``[16, block, keys]`` is left in the
    program (the values turned, bfloat16 ``[16, 128, keys]``, are)."""
    from ray_tpu.ops import dsa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = dsa.attend_plan(block, 16384, 128, 128)
    assert plan == {"attend_form": "kernel", "attend_tile": dsa.ATTEND_TILE}

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (S(16, block, 192), S(16, 16384, 128), S(16, 16384, 128),
            S(16384, 64), struct((block, 16384), jnp.bool_),
            struct((), jnp.int32))

    def attend(q, kn, v, kr, chosen, first):
        return dsa.attend_kernels(q, kn, v, kr, chosen, first, 192 ** -0.5,
                                  plan["attend_tile"])

    forward = jax.jit(attend).lower(*args).compile().as_text()
    assert [name for name, _ in _mosaic_calls(forward)] == ["dsa_attend_fwd"]

    def loss(*a):
        return jnp.square(attend(*a)[0].astype(jnp.float32)).sum()

    gradient = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *args).compile().as_text()
    names = [name for name, _ in _mosaic_calls(gradient)]
    assert len(names) == 2, names        # (named after the transformation)
    for kernel in ("dsa_attend_fwd", "dsa_attend_bwd"):
        assert any(kernel in name for name in names), (kernel, names)
    for text in (forward, gradient):
        assert f"f32[16,{block},16384]" not in text


# ---- train-qwen3-next-1chip's kernels at its shapes (PR 50)


def test_flash_kernels_compile_at_32k_positions_of_256(S, no_compile_cache):
    """Qwen3-Next's full layer in its cell: one sequence of 32,768
    positions, 16 query heads on 2 of 256. A head's whole k and v (forward,
    dQ) or q and dO (dK/dV) lie in VMEM twice: 67 MB at 256 lanes, the most
    any cell asks (``_dkv_vmem``), and Mosaic takes all three."""
    from ray_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, use_pallas=True).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        S(1, 32768, 16, 256), S(1, 32768, 2, 256),
        S(1, 32768, 2, 256)).compile().as_text()
    names = {name for name, _ in _mosaic_calls(text)}
    assert len(names) == 3
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(kernel in name for name in names), (kernel, names)


def test_delta_rule_kernels_compile_at_grouped_heads(S, no_compile_cache,
                                                    monkeypatch):
    """The mixer of ``train-qwen3-next-1chip`` (1 x 32,768 positions of
    2,048, 32 value heads reading 16 key heads, keys and values of 128):
    the plan joins the heads by the kernels' index map and takes 8 key
    heads with their 16 value heads a grid step; Mosaic takes the forward
    that keeps its states and the backward, q, k, ``dq`` and ``dk`` [1, 16
    x 128, 32,768] at the key heads, and neither a copy of q or k to two
    value heads a key head nor a head-major array is anywhere in the
    program."""
    from ray_tpu.ops import delta

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = delta.rule_plan(1, 32768, 32, 128, 128, 64, key_heads=16)
    assert (plan["form"], plan["heads_a_block"], plan["key_heads"],
            plan["joined"]) == ("pallas", 16, 16, "index_map")
    text = _mixer_compiles_with_nothing_around_the_rule(
        S, 2048, 32, 16, 128, 128)
    (bwd,) = [line for name, line in _mosaic_calls(text)
              if "delta_rule_bwd" in name]
    assert bwd.count("bf16[1,2048,32768]") >= 4      # q, k in; dq, dk out
    # the copy to two value heads a key head, and a head-major array
    for copied in ("32768,16,2,128]", "[1,32,32768,128]"):
        assert copied not in text, copied


# ---- train-nemotron3-super-1chip's kernels at its shapes (PR 52)


def test_scan_kernels_compile_at_eight_groups_and_the_nemotron_step_lowers(
        S, one_chip, no_compile_cache, monkeypatch):
    """The selective scan at ``train-nemotron3-super-1chip``'s shapes (1 x
    8,192 positions, 128 heads of 64 in 8 groups of B and C, a state of 128,
    a chunk of one lane tile of 128): Mosaic takes the forward that keeps
    its states and the backward, 16 heads (a group's) and 16 chunks a grid
    step. The cell's own step (``benchmark/configs/
    nemotron-3-super-120b-a12b-c1.json``, ``train_scan_moe.make_step``),
    lowered for a v5e at ``seq + 2`` ids a row, names the scan's and the
    taps' pairs, the flash kernels and megablox's; its plans are the kept
    spans (a latent of 1,024 under 4,096 with 8 of 512 experts held, 22 a
    token, 8,448 rows a pass; a module of depth 1 sharing the head); the
    remat plan reckons the module's two layers with the stack's eleven and
    fits a v5e; the head is walked twice."""
    import json

    import optax

    from benchmark.cells import train_scan_moe
    from ray_tpu.models import llama, nemotron_h
    from ray_tpu.ops import ssm
    from ray_tpu.util import tracing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: V5E_LIMIT)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (S(1, 8192, 128, 64), f32(1, 8192, 128), f32(128),
            S(1, 8192, 8, 128), S(1, 8192, 8, 128))
    plan = ssm.scan_plan(1, 8192, 128, 64, 128, 8, 128)
    assert (plan["form"], plan["heads_a_block"], plan["chunks_a_call"],
            plan["states_kept"]) == ("pallas", 16, 16, 4)

    def loss(*a):
        return jnp.square(ssm.ssd_scan(*a, chunk=128)[0].astype(
            jnp.float32)).sum()

    gradient = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    names = [name for name, _ in _mosaic_calls(gradient)]
    assert len(names) == 2, names        # (named after the transformation)
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert any(kernel in name for name in names), (kernel, names)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b-c1.json")) as f:
        model, _, cfg = train_scan_moe.load_model(
            json.load(f)["model_config"])
    assert model is nemotron_h and cfg.pattern.count("mamba") == 5
    tx = optax.adamw(optax.linear_schedule(0.0, 1e-4, 2000))
    params = jax.eval_shape(lambda k: nemotron_h.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, nemotron_h.trainable(params))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8194), jnp.int32,
                                            sharding=one_chip)}
    n0 = len(tracing.chrome_events())
    lowered = jax.jit(train_scan_moe.make_step(nemotron_h, cfg, tx),
                      donate_argnums=(0, 1)).lower(
        _placed(params, one_chip), _placed(opt, one_chip), batch)
    spans = {}
    for e in tracing.chrome_events()[n0:]:
        spans.setdefault(e["name"], []).append(e["args"])
    (plan,) = spans["rtpu.train.remat_plan"]
    assert plan["layers"] == {"moe": 6, "mamba": 5, "attention": 2}
    assert plan["need_bytes"] < (1 - llama.REMAT_RESERVE) * V5E_LIMIT
    assert {(p["hidden"], p["latent"], p["experts"], p["held"], p["top_k"],
             p["act"], p["rows_a_pass"])
            for p in spans["rtpu.moe.latent_plan"]} == {
        (4096, 1024, 512, 8, 22, "relu2", 8448)}
    (module,) = spans["rtpu.train.mtp_plan"]
    assert module["pattern"] == ["attention", "moe"]
    assert {(r["form"], r["groups"], r["chunk"], r["heads_a_block"])
            for r in spans["rtpu.ssm.scan_plan"]} == {("pallas", 8, 128, 16)}
    assert lowered.out_info[3]["expert_counts"].shape == (6, 512)
    text = lowered.as_text()
    for kernel in ("taps_silu_fwd", "taps_silu_bwd", "ssd_scan_fwd",
                   "ssd_scan_bwd", "flash_fwd", "flash_bwd_dq",
                   "flash_bwd_dkv", "gmm"):
        assert kernel in text, kernel
    assert _vocab_products(text, 4_096, 16_384) == 6
