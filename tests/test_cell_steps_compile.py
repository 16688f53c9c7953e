"""The cells' whole steps compiled for a described v5e chip
(``test_tpu_compile.py`` says how, and its fixtures are taken here):
Laguna's and LFM2's cell steps within the chip's memory, each plan against
the compiler; the scan a stack of one kind of layer always compiled to;
the short convolution, the Mamba-2 mixer and its scan kernels, and
Granite's and Nemotron-H's steps naming the kernels.

Two files and not four: libtpu's compiles want the whole CPU, and four
workers compiling for the chip at once took 523 to 543 test-seconds for
the cases that take 305 to 311 in one file (PR 54's whole runs), while
one file of them all would be 7% of a run. This file's name puts it early
in a run, far from the other: its two cell steps are the suite's longest
cases."""

import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from tests.test_tpu_compile import one_chip, no_compile_cache, S, _mosaic_calls, _placed, V5E_LIMIT, _planned, _vocab_products  # noqa: E402


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
    rung is taken (the scans' first, their in-projection's output, 557 MB
    a layer here, neither), and its need lies within 6% of the
    15,429,915,136 bytes the compiler allots that step
    (``step_program.py``, PR 41: 5.2% over; a reckoning within 3% would
    lie under the budget and hand the attention layer its first rung,
    S3c's to do)."""
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
    here = tracing.since()
    lowered = jax.jit(train_scan.make_step(granite, cfg, tx),
                      donate_argnums=(0, 1)).lower(
        _placed(params, one_chip), _placed(opt, one_chip), batch)
    spans = {}
    for e in here.events():
        spans.setdefault(e["name"], []).append(e["args"])
    (plan,) = spans["rtpu.train.remat_plan"]
    assert plan["level"] == {"mamba": "full", "attention": "full"}
    assert plan["saved_bytes_per_layer"] == {"mamba": 0, "attention": 0}
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
    remat plan reckons the module's two layers with the stack's eleven,
    gives the scans their first rung (the in-projection's output, 304 MB
    a layer) beside the mixtures' third and the attention layers' fourth,
    and fits a v5e; the head is walked twice."""
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
    here = tracing.since()
    lowered = jax.jit(train_scan_moe.make_step(nemotron_h, cfg, tx),
                      donate_argnums=(0, 1)).lower(
        _placed(params, one_chip), _placed(opt, one_chip), batch)
    spans = {}
    for e in here.events():
        spans.setdefault(e["name"], []).append(e["args"])
    (plan,) = spans["rtpu.train.remat_plan"]
    assert plan["layers"] == {"moe": 6, "mamba": 5, "attention": 2}
    # the scans keep their in-projection's output, 304 MB a layer
    assert plan["level"] == {"moe": "level3", "mamba": "level1",
                             "attention": "level4"}
    assert plan["saved_bytes_per_layer"]["mamba"] == 8192 * 18560 * 2
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
