"""The seam ``models/stack.py`` is: the parameters a table gives are the
ones its module gave before there was a walker, a table no shipped model
has trains (two parts a kind, one part a kind, a prediction module beside
the head), the options Qwen3-Next's table asks for leave the older tables
alone, and the counter of whole passes. The models themselves, each
against its reference, are ``tests/model_suite.py`` over
``tests/model_table.py``, one file ``tests/test_<model>.py`` a row."""

import hashlib
from importlib import import_module

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.parallel import MeshSpec, build_mesh  # noqa: E402
from tests.model_table import ROWS as TABLE  # noqa: E402
from tests.test_remat import _cell_config  # noqa: E402


@pytest.mark.parametrize("model, cell, tokens, chunk", [
    ("laguna", "laguna-s-2.1-c1", 16384, 11520),
    ("lfm2", "lfm2-8b-a1b-c1", 16384, 36864)])
@pytest.mark.parametrize("held_rows, passes", [
    ("none", 0), ("chunk", 1), ("chunk+1", 2), ("all", None)])
def test_rows_passed_counts_whole_passes_a_layer(model, cell, tokens, chunk,
                                                 held_rows, passes):
    """``rows_passed`` (the ``moe_rows_passed`` counter) from hand-made
    counts at a cell's shapes, two routed layers, the second balanced
    (one pass): no held row in the first is no pass, exactly a chunk
    one, one row more two, every pair as many as hold them; held over
    passed is how full the passes were. With every expert here nothing
    is passed: the rows routed."""
    from dataclasses import replace

    mod, cfg = _cell_config(cell)
    E, pairs = cfg.num_experts, tokens * cfg.top_k
    first, count = cfg.experts_held
    held = {"none": 0, "chunk": chunk, "chunk+1": chunk + 1,
            "all": pairs}[held_rows]
    counts = np.zeros((2, E), np.int64)
    counts[0, first], counts[0, first + count] = held, pairs - held
    counts[1] = pairs // E
    want = (-(-pairs // chunk) if passes is None else passes) + 1
    assert mod.rows_passed(cfg, counts) == want * chunk
    assert int(mod.rows_held(cfg, counts)) == held + pairs * count // E
    assert mod.rows_passed(replace(cfg, experts_held=None), counts) == \
        2 * pairs


def _tree_digest(tree, values):
    """sha256 over every leaf's path (in dict order), shape and dtype and,
    asked for, its bytes."""
    h = hashlib.sha256()

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
            return
        h.update(repr((path, tuple(node.shape), str(node.dtype))).encode())
        if values:
            h.update(np.asarray(node).tobytes())
    walk(tree, ())
    return h.hexdigest()[:16]


# computed on the parent of PR 42 (5b4a3bd), whose four modules each had an
# ``init_params`` of their own: a routed cell's passes depend on the
# parameters it starts from, leaf for leaf
@pytest.mark.parametrize("what, want", [
    ("laguna-tiny", "811ddefb9b7d8fb2"), ("lfm2-tiny", "06889508f837bb55"),
    ("granite-tiny", "2f7b1682a7ff3341"),
    ("olmo_hybrid-tiny", "0d0b276789c21b1b"),
    ("laguna-s-2.1-c1", "aa267dfd2b9492ea"),
    ("lfm2-8b-a1b-c1", "5b488da3ac81ab79"),
    ("granite-4.0-h-micro-c1", "f68ef20c666583ad"),
    ("olmo-hybrid-7b-c1", "1d547d419b6bc576")])
def test_init_params_is_the_tree_the_module_had_before_the_walker(what, want):
    """Names, dict order, shapes and dtypes of every cell's parameters
    (``jax.eval_shape``) and, of the ``tiny()`` presets, every byte: what
    ``Stack.init_params`` deals from the table is what the module's own
    ``init_params`` dealt, and the module has no other."""
    from ray_tpu.models import stack

    model, _, tiny = what.rpartition("-")
    if tiny == "tiny":
        mod = import_module("ray_tpu.models." + model)
        cfg = getattr(mod, TABLE[model].config).tiny()
        got = _tree_digest(mod.init_params(cfg, jax.random.PRNGKey(0)), True)
    else:
        mod, cfg = _cell_config(what)
        got = _tree_digest(jax.eval_shape(
            lambda k: mod.init_params(cfg, k), jax.random.PRNGKey(0)), False)
    assert got == want
    for name in ("init_params", "logical_axes", "loss_terms", "loss_fn"):
        assert getattr(mod, name).__func__ is getattr(stack.Stack, name)
        assert getattr(mod, name).__self__ is mod.STACK


def test_a_table_no_model_ships_trains_and_gets_a_plan(monkeypatch):
    """Mamba-2 mixers over a routed mixture, and a short convolution over
    OLMo 2's post-norm SwiGLU: no shipped model pairs either. The table is
    built here from the parts under ``ops/`` and a config that has their
    fields; nothing in ``llama.py`` knows it. It gives parameters, a loss
    with both parts' terms, an adamw step that lowers the loss, shardings,
    and a remat plan by kind."""
    from dataclasses import dataclass

    import optax

    from ray_tpu.models import granite, stack
    from ray_tpu.ops.conv import short_conv_part
    from ray_tpu.ops.layers import swiglu_part
    from ray_tpu.ops.moe import routed_part
    from ray_tpu.ops.ssm import mamba2_part
    from ray_tpu.util import tracing

    @dataclass(frozen=True)
    class Config(granite.GraniteConfig):
        conv_taps: int = 3
        num_experts: int = 8
        top_k: int = 2
        routed_scale: float = 1.0
        moe_intermediate_size: int = 32
        router_aux_coef: float = 0.01

        @property
        def pattern(self):
            return tuple("conv_post" if conv else "mamba_moe"
                         for conv in self.attention_layers)

    model = stack.Stack(
        {"mamba_moe": (mamba2_part(counter="scan_abs_max"),
                       routed_part(balance=True)),
         "conv_post": (short_conv_part(), swiglu_part(norm="post"))},
        reports="router")
    cfg = Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=64,
                 attention_layers=(False, True, False, False), ssm_heads=8,
                 ssm_head_dim=16, ssm_state=16, ssm_chunk=8,
                 dtype=jnp.float32, remat=False)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["mamba_moe", "conv_post"]
    assert params["layers"]["mamba_moe"]["e_gate"].shape == (3, 8, 64, 32)
    assert "mlp_post_norm" in params["layers"]["conv_post"]
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda axes, a: len(axes) == a.ndim, model.logical_axes(cfg), params,
        is_leaf=lambda x: isinstance(x, tuple))))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    logits, router = jax.jit(lambda p: model.forward(
        cfg, p, tokens[:, :-1]))(params)
    assert logits.shape == (2, 32, 256)
    assert router["counts"].shape == (3, 8)           # the routed layers'
    tx = optax.adamw(1e-2)

    @jax.jit
    def step(p, opt):
        (loss, terms), grads = jax.value_and_grad(
            lambda q: model.loss_terms(cfg, q, {"tokens": tokens}),
            has_aux=True)(p)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), opt, loss, terms

    opt, losses = tx.init(params), []
    for _ in range(3):
        params, opt, loss, terms = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1
    assert set(terms) == {"cross_entropy", "load_balance", "expert_counts",
                          "scan_abs_max"}
    assert float(terms["scan_abs_max"]) > 0
    # the plan: a level a kind, from what the kind's two parts say they
    # keep (the routed experts' and the SwiGLU's two products: the MLP
    # rung, and a scan's in-projection: the first; no rung names
    # anything in a convolution)
    from dataclasses import replace
    monkeypatch.setattr(llama, "_device_capacity", lambda mesh: 2 ** 30)
    here = tracing.since()
    jax.jit(lambda p: model.loss_fn(replace(cfg, remat=True), p,
                                    {"tokens": tokens}))(params)
    (plan,) = [e["args"] for e in here.events()
               if e["name"] == "rtpu.train.remat_plan"]
    assert plan["level"] == {"mamba_moe": "level3", "conv_post": "level3"}
    assert plan["layers"] == {"mamba_moe": 3, "conv_post": 1}
    assert all(v > 0 for v in plan["saved_bytes_per_layer"].values())
    mesh = build_mesh(MeshSpec({"fsdp": 4}), devices=jax.devices()[:4])
    assert jax.tree_util.tree_structure(
        model.param_shardings(cfg, mesh)) == \
        jax.tree_util.tree_structure(params)


def test_attention_block_with_an_elementwise_gate_in_wq():
    """``attention_part(gate="elementwise")``: ``wq`` gives a head its
    query and then its gate; the block is the ungated block on the query
    columns with ``sigmoid(gate)`` on each head's output before ``wo``."""
    from ray_tpu.ops.layers import Ctx

    cfg = llama.LlamaConfig.tiny(attn_impl="reference", head_dim=16)
    part = llama.attention_part(gate="elementwise", rope=None)
    plain = llama.attention_part(rope=None)
    leaves = part.leaves(cfg)
    assert leaves["wq"].shape == (64, 2 * 64) and "wg" not in leaves
    keys = jax.random.split(jax.random.PRNGKey(0), len(leaves) + 1)
    p = {n: (jnp.ones(l.shape) if l.start == "ones" else
             jax.random.normal(k, l.shape) / 8)
         for k, (n, l) in zip(keys, leaves.items())}
    x = jax.random.normal(keys[-1], (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        got, _ = part.body(cfg, x, p, Ctx(None, {}))
        by_head = p["wq"].reshape(64, 4, 2, 16)
        q_only = {**p, "wq": by_head[:, :, 0].reshape(64, 64)}
        # wo = identity: the ungated heads' outputs themselves
        heads, _ = plain.body(cfg, x, {**q_only, "wo": jnp.eye(64)},
                              Ctx(None, {}))
        u = llama.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(u @ by_head[:, :, 1].reshape(64, 64))
        want = x + ((heads - x) * gate) @ p["wo"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(gate - 0.5).max()) > 0.1
    assert part.keeps(cfg, {n: l.shape for n, l in leaves.items()}, 12,
                      None)["rungs"][0] == 12 * (64 * 4 + 4 * 4)


def test_routed_part_with_a_gated_shared_expert():
    """``routed_part(shared="gated")`` is ``shared=True`` with the shared
    expert's output times ``sigmoid(u . s_sigmoid)``, a number a token."""
    from ray_tpu.models.laguna import LagunaConfig
    from ray_tpu.ops.layers import Ctx, rms_norm, swiglu
    from ray_tpu.ops.moe import routed_part

    cfg = LagunaConfig.tiny()
    gated, plain = routed_part(shared="gated"), routed_part(shared=True)
    leaves = gated.leaves(cfg)
    assert list(leaves)[-1] == "s_sigmoid" and leaves["s_sigmoid"].shape == (
        64,)
    assert list(leaves)[:-1] == list(plain.leaves(cfg))
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves) + 1)
    p = {n: (jnp.ones(l.shape) if l.start == "ones" else
             jax.random.normal(k, l.shape) / 8)
         for k, (n, l) in zip(keys, leaves.items())}
    x = jax.random.normal(keys[-1], (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        got, said = gated.body(cfg, x, p, Ctx(None, {}))
        base, _ = plain.body(cfg, x, p, Ctx(None, {}))
        u = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        shared = swiglu(u, p["s_gate"], p["s_up"], p["s_down"])
        gate = jax.nn.sigmoid(u @ p["s_sigmoid"])[..., None]
    np.testing.assert_allclose(got, base - shared + gate * shared,
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(gate - 0.5).max()) > 0.1
    assert said["router"]["counts"].shape == (16,)


# (model, the held share) -> what the parent commit's forward and loss gave
# on these seeds, float32 on the CPU: the loss, three logits and the sum of
# all logits' sizes. On the machine this was written on, the logits and
# every leaf's gradient were bit-equal to the parent's (CHANGES.md, PR 50).
_PARENTS = {
    "olmo_hybrid": (6.14553165435791,
                    (-0.5216737985610962, 0.50602787733078,
                     -0.5190625190734863), 12633.048828125),
    "laguna": (5.79429292678833,
               (-1.1803277730941772, -0.7559819221496582,
                -1.6450860500335693), 12889.58984375)}


@pytest.mark.parametrize("model", sorted(_PARENTS))
def test_tables_that_take_no_new_option_give_what_they_gave(model):
    """Olmo-Hybrid's and Laguna's tables name none of the options
    Qwen3-Next's asks of ``gated_delta_part``, ``attention_part``,
    ``routed_part`` and ``Stack``: every option defaults to what the code
    did before."""
    mod = import_module("ray_tpu.models." + model)
    how = {"experts_held": (4, 8)} if model == "laguna" else {}
    cfg = getattr(mod, TABLE[model].config).tiny(attn_impl="reference", **how)
    assert not cfg.zero_centred_norm
    params = mod.init_params(cfg, jax.random.PRNGKey(7))
    assert float(params["final_norm"].min()) == 1.0
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 33))
    logits, _ = jax.jit(lambda p, t: mod.forward(cfg, p, t))(
        params, tokens[:, :-1])
    loss = jax.jit(lambda p: mod.loss_fn(cfg, p, {"tokens": jnp.asarray(
        tokens)}))(params)
    want_loss, want_logits, want_sum = _PARENTS[model]
    np.testing.assert_allclose(float(loss), want_loss, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(logits)[1, [0, 7, 31], 5],
                               want_logits, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(np.abs(np.asarray(logits)).sum()),
                               want_sum, rtol=1e-6)


def test_a_table_of_one_part_kinds_trains_and_moves_its_routers_biases():
    """A layer may be one sum: a table whose kinds are one part each (a
    scan, a dense SwiGLU, a routed mixture with a bias) beside one of two.
    It gives leaves and axes by part, a forward, a remat plan by kind, an
    adamw step that lowers the loss, and ``update_router_bias`` finds the
    routed layers whichever slot their part stands in."""
    from dataclasses import dataclass

    import optax

    from ray_tpu.models import granite, stack
    from ray_tpu.ops.layers import swiglu_part
    from ray_tpu.ops.moe import routed_part
    from ray_tpu.ops.ssm import mamba2_part

    @dataclass(frozen=True)
    class Config(granite.GraniteConfig):
        layer_kinds: tuple = ("scan", "mix", "scan", "routed", "both")
        num_experts: int = 8
        top_k: int = 2
        routed_scale: float = 1.0
        moe_intermediate_size: int = 32
        bias_update_rate: float = 0.01

        @property
        def pattern(self):
            return self.layer_kinds

    routed = routed_part(score="sigmoid", bias=True)
    model = stack.Stack(
        {"scan": (mamba2_part(),), "mix": (swiglu_part(),),
         "routed": (routed,), "both": (mamba2_part(), routed)},
        reports="router")
    cfg = Config(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=5, num_heads=4, num_kv_heads=2, max_seq_len=64,
                 attention_layers=(False,) * 5, ssm_heads=8, ssm_head_dim=16,
                 ssm_state=16, ssm_chunk=8, dtype=jnp.float32, remat=False)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    assert list(params["layers"]) == ["scan", "mix", "routed", "both"]
    assert set(params["layers"]["scan"]) == set(mamba2_part().leaves(cfg))
    assert set(params["layers"]["mix"]) == set(swiglu_part().leaves(cfg))
    assert "mtp" not in params
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda axes, a: len(axes) == a.ndim, model.logical_axes(cfg), params,
        is_leaf=lambda x: isinstance(x, tuple))))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    logits, router = jax.jit(lambda p: model.forward(
        cfg, p, tokens[:, :-1]))(params)
    assert logits.shape == (2, 32, 256)
    assert router["counts"].shape == (2, 8)     # "routed", then "both"
    described = llama.describe_stack(cfg, model.kinds, params["layers"], 64,
                                     pattern=cfg.pattern)
    assert set(described["kinds"]) == {"scan", "mix", "routed", "both"}
    both, scan, part = (described["kinds"][k]["working_bytes"]
                        for k in ("both", "scan", "routed"))
    assert both - scan == part - 64 * 4 * 4 * 64    # a kind's parts add up
    tx = optax.adamw(1e-2)

    @jax.jit
    def step(p, opt):
        trained = stack.trainable(p)
        (loss, terms), grads = jax.value_and_grad(
            lambda t: model.loss_terms(cfg, stack.with_trainable(p, t),
                                       {"tokens": tokens}),
            has_aux=True)(trained)
        updates, opt = tx.update(grads, opt, trained)
        p = stack.with_trainable(p, optax.apply_updates(trained, updates))
        return (model.update_router_bias(cfg, p, terms["expert_counts"]),
                opt, loss, terms["expert_counts"])

    opt = tx.init(stack.trainable(params))
    first = None
    for _ in range(4):
        before = params
        params, opt, loss, counts = step(params, opt)
        first = float(loss) if first is None else first
    assert float(loss) < first
    for row, kind in enumerate(("routed", "both")):
        moved = (params["layers"][kind]["router_bias"][0]
                 - before["layers"][kind]["router_bias"][0])
        c = np.asarray(counts[row], np.float32)
        np.testing.assert_allclose(moved, 0.01 * np.sign(c.mean() - c),
                                   atol=1e-7)


def test_a_prediction_module_is_named_by_the_config_and_reports_last():
    """``Stack(mtp=<field>)``: the module's kinds come from the config; its
    leaves lie under ``params["mtp"]``, dealt from keys of their own (the
    stack's leaves are what they are without a module); a row carries ``seq
    + 2`` ids and the routed layers' counts gain the module's row."""
    from ray_tpu.models import nemotron_h as mod

    cfg = mod.Nemotron_hConfig.tiny()
    bare = mod.Nemotron_hConfig.tiny(mtp_layer_pattern="")
    params = mod.init_params(cfg, jax.random.PRNGKey(0))
    without = mod.init_params(bare, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()),
        {k: v for k, v in params.items() if k != "mtp"}, without))
    assert list(params["mtp"]["layers"]) == ["attention", "moe"]
    assert params["mtp"]["layers"]["moe"]["router"].shape == (1, 64, 16)
    assert not bool((params["mtp"]["layers"]["moe"]["router"][0]
                     == params["layers"]["moe"]["router"][0]).all())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 34), 0, 256)
    nll, more, said = jax.jit(lambda p: mod.token_nlls(cfg, p, tokens))(
        params)
    assert nll.shape == more.shape == (2, 32)
    assert said["router"]["counts"].shape == (3, 16)
    main, _ = jax.jit(lambda p: mod.token_nll(bare, p, tokens[:, :33]))(
        without)
    np.testing.assert_allclose(nll, main, atol=1e-6)
    _, terms = jax.jit(lambda p: mod.loss_terms(
        cfg, p, {"tokens": tokens, "mask": jnp.ones_like(tokens)}))(params)
    assert abs(float(terms["cross_entropy"]) - float(nll.mean())) < 1e-5
    assert abs(float(terms["mtp_cross_entropy"]) - float(more.mean())) < 1e-5
