"""Kernels of the main path compiled for a v5e chip that is described
and not attached, at published widths: what Mosaic refuses (a tile that
does not fit VMEM, a slice off the tiling) it refuses here, at no chip
time. Nothing runs, so nothing here is a result or a time. Here: the
routed experts, the remat plan at 7B widths, the flash kernels, the taps
kernels over a delta layer's channels, the delta rule's kernels at the
cells' shapes and at grouped heads, Olmo-Hybrid's step, and the index's
score and attend kernels; the cells' whole steps and the scan's kernels
are ``test_cell_steps_compile.py``, which takes this file's fixtures.

The topology is described inside a fixture and never at import. A
process that describes it loads libtpu, which one process at a time may
do unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the tier-1 command sets it: two
workers may each hold one of the two files). Without the variable a
second process's cases fail at the fixture, saying so; they are skipped
only where no libtpu or no such topology is to be had.
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
        if "lockfile" in str(e):
            # libtpu is here and another process holds it: these files'
            # cases must not go missing from the count as skips
            pytest.fail("another process holds libtpu: run these files in "
                        "one process, or under the tier-1 command's "
                        f"ALLOW_MULTIPLE_LIBTPU_LOAD=1 ({e})")
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


def _fused_mosaic_calls(text: str):
    """The kernel names of the Mosaic calls XLA folded into a fusion of
    its own (a device trace then names the fusion and not the call)."""
    inside, fused = None, []
    for line in text.splitlines():
        if line[:1] not in (" ", "}", ""):
            inside = line.lstrip("%").startswith("fused_computation")
        elif inside and 'custom_call_target="tpu_custom_call"' in line:
            fused.append(re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)",
                                  line).group(1))
    return fused


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

    here = tracing.since()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        placed(params), placed(opt), batch).compile()
    (plan,) = [e["args"] for e in here.events()
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

    here = tracing.since()
    compiled = lower().compile()
    (plan,) = [e["args"] for e in here.events()
               if e["name"] == "rtpu.train.remat_plan"]
    ma = compiled.memory_analysis()
    allotted = ma.argument_size_in_bytes + ma.temp_size_in_bytes \
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert allotted <= 1.03 * plan["need_bytes"] <= 1.03 * 1.1 * allotted, (
        allotted, plan["need_bytes"])
    assert allotted <= (1 - llama.REMAT_RESERVE) * V5E_LIMIT
    return compiled, plan


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


def _vocab_products(text: str, block: int, vocab: int) -> int:
    """The ``dot_general`` of a lowered step that read or write a
    ``[block, vocab]`` array: the blocked head's products, three a block
    since its loss takes a block's gradients while the logits stand
    (``ops/layers.blocked_head_loss``), four when a checkpointed block
    rebuilt them."""
    return sum("dot_general" in line and f"{block}x{vocab}x" in line
               for line in text.splitlines())


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
    here = tracing.since()
    lowered = jax.jit(train_delta.make_step(olmo_hybrid, cfg, tx),
                      donate_argnums=(0, 1)).lower(
        _placed(params, one_chip), _placed(opt, one_chip), batch)
    spans = {}
    for e in here.events():
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


@pytest.mark.parametrize("block", [128, 256], ids=["a-block-of-128",
                                                   "the-walks-block"])
def test_index_score_kernels_compile_at_the_cells_shapes(
        S, one_chip, no_compile_cache, monkeypatch, block):
    """The index's scores at ``train-dots3-1chip``'s shapes (64 heads of
    128 against 16,384 keys, bfloat16; the head weights float32) for
    blocks of 128 queries and the walk's and the check's of 256, on a TPU
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


@pytest.mark.parametrize("block", [128, 256], ids=["a-block-of-128",
                                                   "the-walks-block"])
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


def test_the_walk_compiles_at_the_cells_shapes(S, one_chip, no_compile_cache,
                                               monkeypatch):
    """One full layer's walk of ``train-dots3-1chip`` (16,384 positions of
    16 heads, 64 index heads, the top 2,048, four tiers) and its gradient
    to all seven inputs, on a TPU backend: the plan takes 256 queries a
    block as the model asks and says what the largest call holds, and every
    Mosaic call stays a call with its own VMEM limit and name beside the
    blocks' sums (each tier's calls: a forward and a backward of both the scores
    and the attention, and the scores' forward once more where the index
    term's gradient forms them; no attention forward in the backward)."""
    from ray_tpu.models.dots3 import Dots3Config
    from ray_tpu.ops import dsa
    from ray_tpu.util import tracing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s, f32 = 16384, jnp.float32
    args = (S(1, s, 16, 192), S(1, s, 16, 128), S(1, s, 16, 128),
            S(1, s, 64), S(1, s, 64, 128), S(1, s, 128),
            jax.ShapeDtypeStruct((1, s, 64), f32, sharding=one_chip))

    def loss(*a):
        out, kl, _ = dsa.sparse_attention(
            *a, scale=192 ** -0.5, topk=2048,
            block=Dots3Config.index_block, tiers=Dots3Config.index_tiers)
        return jnp.square(out.astype(f32)).sum() + kl.sum()

    here = tracing.since()
    text = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        *args).compile().as_text()
    (said,) = [e["args"] for e in here.events()
               if e["name"] == "rtpu.dsa.shapes"]
    assert (said["block"], said["block_asked"], said["tiers"],
            said["vmem_need_bytes"]) == (256, 256, 4, 44_302_336)
    assert (said["scores_form"], said["attend_form"]) == ("kernel", "kernel")
    names = [name for name, _ in _mosaic_calls(text)]
    for kernel, calls in (("dsa_scores_fwd", 8), ("dsa_scores_bwd", 4),
                          ("dsa_attend_fwd", 4), ("dsa_attend_bwd", 4)):
        assert sum(kernel in name for name in names) == calls, (kernel,
                                                                 names)
    # XLA folds none into the update of the stack its output is laid in
    # (``dsa._traced_once``'s barrier): each keeps its name in a trace
    assert not _fused_mosaic_calls(text)


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
