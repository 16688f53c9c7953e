"""Model tests: llama + gpt2 + the mixtures' forward/loss/grads, sharded
equivalence, HF checkpoint parity, int8 decode. The layer loop and the
blocked head: ``test_layer_patterns.py``; remat: ``test_remat.py``; the
models ``models/stack.py`` walks: ``tests/model_suite.py``."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import gpt2, llama  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh, named_sharding  # noqa: E402


@pytest.fixture(scope="module")
def llama_setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def test_llama_forward_shapes(llama_setup):
    cfg, params, tokens = llama_setup
    logits = llama.forward(cfg, params, tokens)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_llama_initial_loss_near_uniform(llama_setup):
    cfg, params, tokens = llama_setup
    loss = float(llama.loss_fn(cfg, params, {"tokens": tokens}))
    assert abs(loss - np.log(cfg.vocab_size)) < 1.5


def test_llama_grads_finite_and_nonzero(llama_setup):
    cfg, params, tokens = llama_setup
    grads = jax.jit(jax.grad(
        lambda p: llama.loss_fn(cfg, p, {"tokens": tokens})))(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves)


def test_llama_loss_mask(llama_setup):
    cfg, params, tokens = llama_setup
    mask = jnp.ones_like(tokens, jnp.float32)
    l_full = float(llama.loss_fn(cfg, params, {"tokens": tokens, "mask": mask}))
    l_nomask = float(llama.loss_fn(cfg, params, {"tokens": tokens}))
    np.testing.assert_allclose(l_full, l_nomask, rtol=1e-5)


def test_llama_training_reduces_loss(llama_setup):
    """Five SGD steps on one batch should reduce loss (end-to-end autodiff)."""
    cfg, params, tokens = llama_setup
    batch = {"tokens": tokens}
    lr = 0.5

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(
            lambda p_: llama.loss_fn(cfg, p_, batch))(p)
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return p, loss

    p = params
    first = None
    for _ in range(5):
        p, loss = step(p)
        if first is None:
            first = float(loss)
    assert float(loss) < first


def test_llama_sharded_matches_unsharded(llama_setup):
    cfg, params, tokens = llama_setup
    base = float(llama.loss_fn(cfg, params, {"tokens": tokens}))
    mesh = build_mesh(MeshSpec({"fsdp": 2, "tp": 4}))
    p_sharded = jax.device_put(params, llama.param_shardings(cfg, mesh))
    t_sharded = jax.device_put(tokens, named_sharding(mesh, "batch", None))
    f = jax.jit(lambda p, t: llama.loss_fn(cfg, p, {"tokens": t}))
    sharded = float(f(p_sharded, t_sharded))
    np.testing.assert_allclose(sharded, base, rtol=1e-4)


def test_llama_ring_attention_impl(llama_setup):
    """attn_impl='ring' over an sp mesh matches the reference impl."""
    from dataclasses import replace

    cfg, params, _ = llama_setup
    # seq after the next-token shift must divide the sp axis (8)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 33), 0,
                                cfg.vocab_size)
    base = float(llama.loss_fn(cfg, params, {"tokens": tokens}))
    mesh = build_mesh(MeshSpec({"sp": 8}))
    cfg_ring = replace(cfg, attn_impl="ring")
    f = jax.jit(lambda p, t: llama.loss_fn(cfg_ring, p, {"tokens": t},
                                           mesh=mesh))
    ring = float(f(params, tokens))
    np.testing.assert_allclose(ring, base, rtol=1e-4)


def test_llama_ulysses_attention_impl(llama_setup):
    """attn_impl='ulysses' (all-to-all sequence parallelism) over an sp
    mesh matches the reference impl; tiny's 4 heads over sp=4 puts one
    head per rank, and kv_heads=2 exercises KV replication."""
    from dataclasses import replace

    cfg, params, _ = llama_setup
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 33), 0,
                                cfg.vocab_size)
    base = float(llama.loss_fn(cfg, params, {"tokens": tokens}))
    mesh = build_mesh(MeshSpec({"sp": 4}), devices=jax.devices()[:4])
    cfg_u = replace(cfg, attn_impl="ulysses")
    f = jax.jit(lambda p, t: llama.loss_fn(cfg_u, p, {"tokens": t},
                                           mesh=mesh))
    got = float(f(params, tokens))
    np.testing.assert_allclose(got, base, rtol=1e-4)


def test_llama_8b_config_param_count():
    cfg = llama.LlamaConfig.llama3_8b()
    shapes = llama.init_shapes(cfg)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 7.5e9 < n < 8.5e9  # ~8.0B params


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def test_gpt2_forward_and_loss(gpt2_setup):
    cfg, params, tokens = gpt2_setup
    logits = gpt2.forward(cfg, params, tokens)
    assert logits.shape == (2, 32, cfg.vocab_size)
    loss = float(gpt2.loss_fn(cfg, params, {"tokens": tokens}))
    assert abs(loss - np.log(cfg.vocab_size)) < 1.5


def test_gpt2_125m_param_count():
    cfg = gpt2.GPT2Config.gpt2_125m()
    params_shapes = jax.eval_shape(
        lambda: gpt2.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape))
            for s in jax.tree_util.tree_leaves(params_shapes))
    assert 1.2e8 < n < 1.4e8  # ~124M


def test_gpt2_training_step(gpt2_setup):
    cfg, params, tokens = gpt2_setup
    loss, grads = jax.value_and_grad(
        lambda p: gpt2.loss_fn(cfg, p, {"tokens": tokens}))(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_gpt2_sharded(gpt2_setup):
    cfg, params, tokens = gpt2_setup
    base = float(gpt2.loss_fn(cfg, params, {"tokens": tokens}))
    mesh = build_mesh(MeshSpec({"fsdp": 2, "tp": 4}))
    p_sharded = jax.device_put(params, gpt2.param_shardings(cfg, mesh))
    f = jax.jit(lambda p, t: gpt2.loss_fn(cfg, p, {"tokens": t}))
    np.testing.assert_allclose(float(f(p_sharded, tokens)), base, rtol=1e-4)


def test_moe_forward_and_aux():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mixtral

    cfg = mixtral.MixtralConfig.tiny()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits, aux = mixtral.forward(cfg, params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(logits).all() and jnp.isfinite(aux)
    assert float(aux) >= 0.0
    loss = mixtral.loss_fn(cfg, params, {"tokens": tokens})
    # near-uniform at init (plus small aux)
    import math

    assert abs(float(loss) - math.log(cfg.vocab_size)) < 1.0


def test_moe_single_expert_equals_dense_mlp():
    """With E=1, k=1 the routed layer must reduce to a plain SwiGLU MLP
    — the numerics oracle for dispatch/combine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import mixtral
    from ray_tpu.ops.layers import swiglu

    cfg = mixtral.MixtralConfig.tiny(num_experts=1, top_k=1)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    p0 = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.hidden_size),
                          jnp.float32)
    out, aux = mixtral.moe_layer(cfg, p0, x)
    dense = swiglu(x, p0["e_gate"][0], p0["e_up"][0], p0["e_down"][0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-2, atol=2e-3)


def test_moe_expert_parallel_train_step():
    """Full train step with experts sharded over ep on the 8-device mesh
    (dp=2, ep=4): compiles, runs, loss finite and matches replicated."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import mixtral
    from ray_tpu.parallel import MeshSpec, build_mesh, named_sharding

    cfg = mixtral.MixtralConfig.tiny()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                cfg.vocab_size)
    base = float(mixtral.loss_fn(cfg, params, {"tokens": tokens}))

    mesh = build_mesh(MeshSpec({"dp": 2, "ep": 4}))
    p_sh = jax.device_put(params, mixtral.param_shardings(cfg, mesh))
    t_sh = jax.device_put(tokens, named_sharding(mesh, "batch", None))

    tx = optax.adamw(1e-3)
    opt = tx.init(p_sh)

    def step(p, o, t):
        loss, grads = jax.value_and_grad(
            lambda q: mixtral.loss_fn(cfg, q, {"tokens": t}))(p)
        upd, o = tx.update(grads, o, p)
        return optax.apply_updates(p, upd), o, loss

    p2, o2, loss = jax.jit(step)(p_sh, opt, t_sh)
    assert abs(float(loss) - base) < 1e-2
    # expert weights are actually partitioned over ep
    sh = p2["layers"]["e_gate"].sharding.spec
    assert "ep" in str(sh)


@pytest.fixture(scope="module")
def olmoe_setup():
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))          # benchmark/ lies beside tests/
    from benchmark.references import olmoe_ref
    from ray_tpu.models import olmoe

    cfg = olmoe.OlmoeConfig.tiny(attn_impl="reference")
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    # the norms start at 1: move them, or a dropped q/k norm goes unseen
    for i, name in enumerate(("q_norm", "k_norm", "attn_norm", "mlp_norm")):
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(10 + i), params["layers"][name].shape)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 17))
    return olmoe, olmoe_ref, cfg, params, tokens


def test_olmoe_forward_and_loss_terms_match_the_reference(olmoe_setup):
    """Logits, router logits, expert counts and all three terms of the
    loss against the plain float32 reference on seeded weights."""
    olmoe, olmoe_ref, cfg, params, tokens = olmoe_setup
    with jax.default_matmul_precision("highest"):
        logits, router = jax.jit(lambda p, t: olmoe.forward(
            cfg, p, t, keep_router_logits=True))(params, tokens[:, :-1])
        loss, terms = jax.jit(lambda p, t: olmoe.loss_terms(
            cfg, p, {"tokens": t}))(params, tokens)
    ref = olmoe_ref.token_nll(cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(olmoe_ref.logits(
            cfg, params, tokens[:, :-1])), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(router["logits"]),
                               ref["router_logits"], rtol=2e-5, atol=2e-5)
    E = cfg.num_experts
    want_counts = np.stack([np.bincount(c.ravel(), minlength=E)
                            for c in ref["chosen"]])
    assert (np.asarray(terms["expert_counts"]) == want_counts).all()
    assert int(want_counts.sum()) == cfg.num_layers * 32 * cfg.top_k
    for name in ("cross_entropy", "load_balance", "router_z"):
        assert abs(float(terms[name]) - ref["terms"][name]) < 2e-5, name
    assert abs(float(loss) - ref["terms"]["loss"]) < 2e-5
    assert ref["terms"]["load_balance"] > 1.0 and ref["terms"]["router_z"] > 0


def test_olmoe_gradients_match_the_reference(olmoe_setup):
    olmoe, olmoe_ref, cfg, params, tokens = olmoe_setup
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: olmoe.loss_fn(
            cfg, p, {"tokens": tokens})))(params)
    want = jax.jit(jax.grad(
        lambda p: olmoe_ref.loss(cfg, p, tokens)))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    assert len(flat) == 15
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(w).max()) > 1e-4, path     # it is reached
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-6, err_msg=str(path))


def test_olmoe_reference_forced_to_other_choices(olmoe_setup):
    """``forced_topk`` replaces the reference's choice of experts: its
    own choice gives its own result back, another choice another."""
    _, olmoe_ref, cfg, params, tokens = olmoe_setup
    own = olmoe_ref.token_nll(cfg, params, tokens)
    same = olmoe_ref.token_nll(cfg, params, tokens,
                               forced_topk=own["chosen"][:, :, ::-1])
    np.testing.assert_allclose(same["nll"], own["nll"], atol=1e-6)
    other = olmoe_ref.token_nll(cfg, params, tokens, forced_topk=(
        own["chosen"] + 1) % cfg.num_experts)
    assert np.abs(other["nll"] - own["nll"]).max() > 1e-3


def test_olmoe_hf_checkpoint_parity():
    """transformers' OlmoeForCausalLM at a tiny random config, through
    ``olmoe_from_hf``: the program's and the reference's logits, and the
    load-balancing term against transformers' own function. This is
    what ties the reference to the published model."""
    from dataclasses import replace

    import torch
    from transformers import OlmoeConfig as HFConfig, OlmoeForCausalLM
    from transformers.models.olmoe.modeling_olmoe import (
        load_balancing_loss_func)

    from benchmark.references import olmoe_ref
    from ray_tpu.models import olmoe
    from ray_tpu.models.hf_weights import from_hf

    torch.manual_seed(0)
    hf = OlmoeForCausalLM(HFConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        router_aux_loss_coef=0.01)).eval()
    with torch.no_grad():       # the norms start at 1: move them
        for name, w in hf.named_parameters():
            if "norm" in name:
                w.add_(0.3 * torch.randn_like(w))
    cfg, params = from_hf(hf, dtype=jnp.float32)
    assert isinstance(cfg, olmoe.OlmoeConfig) and cfg.num_experts == 8
    assert not cfg.norm_topk_prob and cfg.router_aux_coef == 0.01
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference", remat=False)
    tokens = np.random.default_rng(3).integers(0, 128, (2, 16))
    with torch.no_grad():
        out = hf(torch.tensor(tokens), output_router_logits=True)
    want = out.logits.numpy()
    with jax.default_matmul_precision("highest"):
        logits, router = olmoe.forward(cfg, params, jnp.asarray(tokens))
        balance, _ = olmoe.router_losses(cfg, router)
    assert np.abs(np.asarray(logits) - want).max() < 5e-5
    assert np.abs(np.asarray(olmoe_ref.logits(cfg, params, tokens))
                  - want).max() < 5e-5
    hf_balance = float(load_balancing_loss_func(
        out.router_logits, 8, 2))
    assert abs(float(balance) - hf_balance) < 1e-5
    full = np.concatenate([tokens, tokens[:, :1]], axis=1)
    ref = olmoe_ref.token_nll(cfg, params, full)
    assert abs(ref["terms"]["load_balance"] - hf_balance) < 1e-5


def test_olmoe_fsdp_train_step_matches_unsharded(olmoe_setup):
    """A train step with parameters sharded over fsdp=8 (every chip
    routes its own rows to all experts): the loss and the expert counts
    of the unsharded step."""
    import optax

    olmoe, _, cfg, params, _ = olmoe_setup
    tokens = jax.random.randint(jax.random.PRNGKey(5), (8, 17), 0,
                                cfg.vocab_size)
    base, base_terms = jax.jit(lambda p, t: olmoe.loss_terms(
        cfg, p, {"tokens": t}))(params, tokens)
    mesh = build_mesh(MeshSpec({"fsdp": 8}))
    p_sh = jax.device_put(params, olmoe.param_shardings(cfg, mesh))
    t_sh = jax.device_put(tokens, named_sharding(mesh, "batch", None))
    tx = optax.adamw(1e-3)

    def step(p, o, t):
        (loss, terms), grads = jax.value_and_grad(
            lambda q: olmoe.loss_terms(cfg, q, {"tokens": t}, mesh=mesh),
            has_aux=True)(p)
        upd, o = tx.update(grads, o, p)
        return optax.apply_updates(p, upd), o, loss, terms["expert_counts"]

    p2, _, loss, counts = jax.jit(step)(p_sh, tx.init(p_sh), t_sh)
    assert abs(float(loss) - float(base)) < 1e-4
    assert (np.asarray(counts)
            == np.asarray(base_terms["expert_counts"])).all()
    assert "fsdp" in str(p2["layers"]["e_gate"].sharding.spec)


def test_llama_hf_checkpoint_parity():
    """HF Llama weights load into our pytree and the logits MATCH the
    transformers implementation to float precision — our Llama is
    numerically the reference Llama (models/hf_weights.py)."""
    from dataclasses import replace

    import numpy as np
    import torch
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM

    from ray_tpu.models import llama
    from ray_tpu.models.hf_weights import llama_from_hf

    torch.manual_seed(0)
    hf = LlamaForCausalLM(HFConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=500000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False)).eval()

    cfg, params = llama_from_hf(hf, dtype=jnp.float32)
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 19))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(cfg, params, jnp.asarray(tokens)))
    assert np.abs(ours - ref).max() < 5e-6  # measured ~2e-7 in fp32


def test_gpt2_hf_checkpoint_parity():
    """HF GPT-2 weights (Conv1D [in,out] layout — 1:1 with ours) load and
    match transformers logits."""
    from dataclasses import replace

    import numpy as np
    import torch
    from transformers import GPT2Config as HFConfig, GPT2LMHeadModel

    from ray_tpu.models import gpt2
    from ray_tpu.models.hf_weights import gpt2_from_hf

    torch.manual_seed(0)
    hf = GPT2LMHeadModel(HFConfig(
        vocab_size=256, n_embd=64, n_layer=2, n_head=4,
        n_positions=128)).eval()
    cfg, params = gpt2_from_hf(hf, dtype=jnp.float32)
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    tokens = np.random.default_rng(2).integers(0, 256, (2, 23))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(gpt2.forward(cfg, params, jnp.asarray(tokens)))
    assert np.abs(ours - ref).max() < 2e-3


def test_mixtral_hf_checkpoint_parity():
    """HF Mixtral weights (per-expert w1/w3/w2 linears) load into our
    stacked [L, E, ...] expert tensors, and the sorted dropless MoE
    reproduces transformers' exact token-wise computation."""
    from dataclasses import replace

    import numpy as np
    import torch
    from transformers import MixtralConfig as HFConfig, MixtralForCausalLM

    from ray_tpu.models import mixtral
    from ray_tpu.models.hf_weights import mixtral_from_hf

    torch.manual_seed(0)
    hf = MixtralForCausalLM(HFConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rope_theta=10000.0,
        rms_norm_eps=1e-5)).eval()

    cfg, params = mixtral_from_hf(hf, dtype=jnp.float32)
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    tokens = np.random.default_rng(3).integers(0, 128, (2, 15))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    out = mixtral.forward(cfg, params, jnp.asarray(tokens))
    ours = np.asarray(out[0] if isinstance(out, tuple) else out)
    assert np.abs(ours - ref).max() < 5e-5


def test_llama3_rope_scaling_parity():
    """llama3-type rope_scaling (long-context frequency scaling) matches
    transformers bit-for-bit past the original context window — real
    Llama-3.1+ checkpoints load and run correctly."""
    from dataclasses import replace

    import numpy as np
    import torch
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM

    from ray_tpu.models import llama
    from ray_tpu.models.hf_weights import llama_from_hf

    torch.manual_seed(0)
    hf = LlamaForCausalLM(HFConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rope_theta=500000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 64})).eval()
    cfg, params = llama_from_hf(hf, dtype=jnp.float32)
    assert cfg.rope_scaling is not None
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    # sequence PAST the original 64-token context: scaling must engage
    tokens = np.random.default_rng(5).integers(0, 256, (2, 100))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(cfg, params, jnp.asarray(tokens)))
    assert np.abs(ours - ref).max() < 5e-6

    # unsupported scaling types still refuse loudly
    import pytest as _pytest
    hf.config.rope_scaling = {"rope_type": "longrope", "factor": 4.0}
    with _pytest.raises(ValueError, match="longrope"):
        llama_from_hf(hf)


@pytest.mark.parametrize("scaling", [
    {"rope_type": "linear", "factor": 4.0},
    {"rope_type": "yarn", "factor": 4.0,
     "original_max_position_embeddings": 64},
    {"rope_type": "yarn", "factor": 8.0, "beta_fast": 16.0,
     "beta_slow": 2.0, "attention_factor": 1.3,
     "original_max_position_embeddings": 64},
])
def test_linear_and_yarn_rope_scaling_parity(scaling):
    """linear (position-interpolation) and yarn (NTK-by-parts,
    arXiv:2309.00071) rope scaling match transformers bit-for-bit past
    the original context (reference parity: modeling_rope_utils
    _compute_linear_scaling_rope / _compute_yarn_parameters)."""
    from dataclasses import replace

    import numpy as np
    import torch
    from transformers import LlamaConfig as HFConfig, LlamaForCausalLM

    from ray_tpu.models import llama
    from ray_tpu.models.hf_weights import llama_from_hf

    torch.manual_seed(1)
    hf = LlamaForCausalLM(HFConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rope_theta=500000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False,
        rope_scaling=dict(scaling))).eval()
    cfg, params = llama_from_hf(hf, dtype=jnp.float32)
    assert cfg.rope_scaling is not None
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    tokens = np.random.default_rng(9).integers(0, 256, (2, 120))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(cfg, params, jnp.asarray(tokens)))
    assert np.abs(ours - ref).max() < 5e-6


def test_qwen2_hf_checkpoint_parity():
    """Qwen2 = the llama block + q/k/v biases: HF Qwen2 weights load via
    qwen2_from_hf (and the from_hf auto-dispatcher) and logits match
    transformers to float precision."""
    from dataclasses import replace

    import numpy as np
    import torch
    from transformers import Qwen2Config as HFConfig, Qwen2ForCausalLM

    from ray_tpu.models import llama
    from ray_tpu.models.hf_weights import from_hf, qwen2_from_hf

    torch.manual_seed(0)
    hf = Qwen2ForCausalLM(HFConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False)).eval()
    # qwen2 inits biases to zero; randomize them so the parity check
    # actually exercises the bias path
    with torch.no_grad():
        for layer in hf.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.normal_(0, 0.5)

    cfg, params = qwen2_from_hf(hf, dtype=jnp.float32)
    assert cfg.attn_qkv_bias and "bq" in params["layers"]
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 19))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(cfg, params, jnp.asarray(tokens)))
    assert np.abs(ours - ref).max() < 5e-6

    # the dispatcher resolves the same model by its model_type
    cfg2, _ = from_hf(hf, dtype=jnp.float32)
    assert cfg2.attn_qkv_bias

    # sharded serving: the sharding pytree must match the param
    # structure INCLUDING the bias leaves (tp placement of the engine)
    import jax as _jax
    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec({"tp": 2}), devices=_jax.devices()[:2])
    sh = llama.param_shardings(cfg, mesh)
    _jax.tree_util.tree_map(lambda a, s: None, params, sh)  # same shape


def test_int8_quantized_decode_matches_dequantized():
    """Weight-only int8 serving: running the decode path with quantized
    leaves must equal running it with the SAME weights manually
    dequantized (the fused dequant is a pure refactor of the math), and
    stay close to the original bf16/f32 logits (bounded quantization
    error)."""
    from ray_tpu.models import llama_decode

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    qparams = jax.jit(llama_decode.quantize_decode_params)(params)

    # manual dequant -> plain pytree
    deq = dict(qparams)
    deq["layers"] = {
        k: (v["q"].astype(jnp.float32) * v["s"]
            if isinstance(v, dict) else v)
        for k, v in qparams["layers"].items()}
    if isinstance(deq.get("lm_head"), dict):
        deq["lm_head"] = (qparams["lm_head"]["q"].astype(jnp.float32)
                          * qparams["lm_head"]["s"])

    cache_q = llama_decode.init_cache(cfg, 2, 32)
    cache_d = llama_decode.init_cache(cfg, 2, 32)
    toks = jnp.array([5, 9], jnp.int32)
    pos = jnp.array([3, 7], jnp.int32)
    act = jnp.ones((2,), bool)
    _, lq = llama_decode.decode_step(cfg, qparams, cache_q, toks, pos, act)
    _, ld = llama_decode.decode_step(cfg, deq, cache_d, toks, pos, act)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               atol=1e-5, rtol=1e-5)

    # bounded error vs the unquantized model
    cache_o = llama_decode.init_cache(cfg, 2, 32)
    _, lo = llama_decode.decode_step(cfg, params, cache_o, toks, pos, act)
    lo, lq = np.asarray(lo), np.asarray(lq)
    denom = np.maximum(np.abs(lo).max(), 1e-6)
    assert np.abs(lq - lo).max() / denom < 0.05, (
        np.abs(lq - lo).max(), denom)


def test_llm_engine_quantized_generates():
    """model_config quantize='int8' serves end-to-end."""
    from ray_tpu.serve.llm_engine import LLMEngine
    from tests.engines import SMALLEST, drain, private_engine

    # private: its weights are quantized at construction
    with private_engine(LLMEngine, **dict(SMALLEST, model_config={
            "preset": "tiny", "quantize": "int8"})) as eng:
        out = drain(eng, [("r1", [1, 2, 3, 4], {})])
    assert "r1" in out and len(out["r1"]["tokens"]) == 8


@pytest.mark.parametrize("hf_act,our_act", [
    ("gelu_pytorch_tanh", "gelu_tanh"),
    ("gelu", "gelu"),  # EXACT erf gelu — must not silently approximate
])
def test_gemma_hf_checkpoint_parity(hf_act, our_act):
    """Gemma = the llama block with GeGLU, sqrt(hidden)-scaled
    embeddings, (1+w) RMSNorm (folded at load) and tied head: HF Gemma
    weights load via gemma_from_hf (and the from_hf dispatcher) and
    logits match transformers to float precision — including the
    KV-cached decode path."""
    import numpy as np
    import torch
    from dataclasses import replace
    from transformers import GemmaConfig as HFConfig, GemmaForCausalLM

    from ray_tpu.models import llama, llama_decode
    from ray_tpu.models.hf_weights import from_hf, gemma_from_hf

    torch.manual_seed(0)
    hf = GemmaForCausalLM(HFConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24, max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, hidden_activation=hf_act)).eval()

    cfg, params = gemma_from_hf(hf, dtype=jnp.float32)
    assert cfg.mlp_act == our_act and cfg.tie_embeddings
    assert cfg.head_dim_ == 24 and cfg.embed_scale == 8.0
    cfg = replace(cfg, dtype=jnp.float32, attn_impl="reference",
                  remat=False)
    tokens = np.random.default_rng(2).integers(0, 256, (2, 17))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.forward(cfg, params, jnp.asarray(tokens)))
    assert np.abs(ours - ref).max() < 5e-6, np.abs(ours - ref).max()

    cfg2, _ = from_hf(hf, dtype=jnp.float32)
    assert cfg2.mlp_act == our_act

    # decode parity: prefill + per-token decode reproduces the full
    # forward's next-token logits at each position
    logits_pf, kv, _ = llama_decode.prefill(
        cfg, params, jnp.asarray(tokens[:1, :8]))
    np.testing.assert_allclose(np.asarray(logits_pf[7]), ref[0, 7],
                               atol=5e-5, rtol=1e-4)
    cache = llama_decode.init_cache(cfg, 1, 32)
    cache = llama_decode.insert_sequence(cache, kv, slot=0)
    toks = jnp.asarray(tokens[:1, 8])
    cache, lg = llama_decode.decode_step(
        cfg, params, cache, toks, jnp.array([8]), jnp.array([True]))
    np.testing.assert_allclose(np.asarray(lg[0]), ref[0, 8],
                               atol=5e-5, rtol=1e-4)
