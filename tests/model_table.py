"""The models ``models/stack.py`` walks, one row each: what
``tests/model_suite.py`` needs to hold a model to its plain reference. A
ninth model is a row here, a file ``tests/test_<model>.py`` that says
``ROWS = ("<model>",)`` and imports the suite, and in that file the tests
of what only the new model has.

A row names the module (``ray_tpu.models.<name>``, its reference
``benchmark.references.<name>_ref``), the config class, the held shares it
is checked at (id -> what ``tiny()`` is given), the leaves its fixture
moves off their starts (a norm applied twice, or dropped, would go
unseen), what ``tiny()`` must say, and where the references differ in
what they take or hand back, a function that says how."""

import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


def count(mod, cfg) -> int:
    """The parameters ``init_params`` would deal, from their shapes."""
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda k: mod.init_params(cfg, k),
                       jax.random.PRNGKey(0))))


@dataclass(frozen=True)
class Row:
    name: str
    config: str
    shares: Dict[str, Dict[str, Any]]
    moved: Tuple[Tuple[str, float], ...]       # (leaf, how far)
    says: Callable                             # (cfg, params): tiny() is this
    groups: Dict[str, int]                     # "top", each kind: its leaves
    tiny: Dict[str, Any] = field(
        default_factory=lambda: {"attn_impl": "reference"})
    tokens: Tuple[int, Tuple[int, int], Any] = (1, (2, 33), np.int64)
    ahead: int = 1                  # ids a row carries past its positions
    also_moved: Optional[Callable] = None      # (params) -> params
    # (mod, cfg, p, t, **what ``batch`` brings): not ``forward``
    forward: Optional[Callable] = None
    batch: Optional[Callable] = None   # (case) -> a batch's further entries
    # what the reference is told to choose, from what the program said,
    # and which of its functions take it: "logits", "nll", "weighted" (a
    # row's own ``want_terms`` and ``gradients`` read ``case.forced``)
    forced: Callable = lambda case: {}
    forced_in: Tuple[str, ...] = ()
    logits_tol: Tuple[float, float] = (1e-5, 1e-5)
    # what of the layers' reports is held to ``ref.token_nll``, each with
    # its atol where it is not exact: the "router" logits, the "choice"
    # (with "moved": not the plain top k), the "counts", the last "state"
    # (and the per-position loss with it)
    reports: Dict[str, Optional[float]] = field(default_factory=dict)
    state: Optional[Tuple[Optional[str], str, Callable]] = None
    reports_also: Optional[Callable] = None    # (case)
    want_terms: Optional[Callable] = None      # (case) -> name -> value
    # a term of the loss, "" any not named: (rtol, atol), held to the
    # larger of ``atol`` and ``rtol * |the reference's|``
    term_tol: Dict[str, Tuple[float, float]] = field(
        default_factory=lambda: {"": (0.0, 1e-5)})
    terms_also: Optional[Callable] = None      # (case, loss, terms)
    # every leaf's gradient: rtol, atol over the leaf's largest entry, the
    # floor of that entry, and what counts as reached; or, where
    # ``grad_l2`` is given, the leaf's relative L2 gap under it
    grad_tol: Tuple[float, float, float, float] = (1e-4, 1e-5, 1e-2, 1e-5)
    grad_l2: Optional[float] = None
    gradients: Optional[Callable] = None       # (case) -> (got, want)
    gradient_shares: Optional[Tuple[str, ...]] = None     # not at every one
    weighted: Optional[int] = None             # the weights' seed
    # a row of ``benchmark.cells.<cell>``, whose loss goes through the
    # blocked head: the cell, the variants that must agree, how near the
    # first step's moment and state lie, and an fsdp step's parameters
    blocked: Optional[Dict[str, Any]] = None
    expert_shares: Optional[Dict[str, Any]] = None
    head_shares: Dict[str, Tuple[int, str]] = field(default_factory=dict)
    ref_attention: Optional[Callable] = None   # (ref, cfg, p, x, kind)
    presets: Dict[str, Callable] = field(default_factory=dict)
    plan: Optional[Dict[str, Any]] = None
    hand_counts: Optional[Callable] = None


# ---- what tiny() must say


def _laguna_says(cfg, params):
    assert cfg.pattern == ("full_dense", "sliding_moe", "sliding_moe",
                           "sliding_moe", "full_moe")
    assert params["layers"]["sliding_moe"]["wq"].shape == (3, 64, 6 * 16)
    assert params["layers"]["full_moe"]["wq"].shape == (1, 64, 4 * 16)
    assert params["layers"]["full_moe"]["e_gate"].shape[1] == (
        8 if cfg.experts_held else 16)


def _lfm2_says(cfg, params):
    assert cfg.pattern == ("conv_dense", "attn_moe", "conv_moe", "conv_moe",
                           "conv_moe")
    assert params["layers"]["conv_moe"]["w_in"].shape == (3, 64, 192)
    assert params["layers"]["attn_moe"]["q_norm"].shape == (1, 16)
    assert params["layers"]["conv_moe"]["e_gate"].shape[1] == (
        4 if cfg.experts_held else 8)
    assert "lm_head" not in params                      # tied


def _granite_says(cfg, params):
    assert cfg.pattern == ("mamba", "mamba", "attention", "mamba")
    assert params["layers"]["mamba"]["m_in"].shape == (3, 64, 128 + 160 + 8)
    assert params["layers"]["mamba"]["m_conv"].shape == (3, 160, 4)
    assert "lm_head" not in params                      # tied
    # Mamba-2's published initialisation
    A = np.exp(np.asarray(params["layers"]["mamba"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["layers"]["mamba"]["dt_bias"])))
    assert 1.0 <= A.min() and A.max() <= 16.0
    assert 0.001 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6


def _olmo_hybrid_says(cfg, params):
    assert cfg.pattern == ("linear", "linear", "linear", "full")
    linear = params["layers"]["linear"]
    assert linear["g_in"].shape == (3, 64, 128 + 256 + 8)
    assert linear["g_conv"].shape == (3, 256, 4)
    assert params["lm_head"].shape == (64, 256)          # untied
    assert not {"attn_norm", "op_norm", "mlp_norm"} & (
        set(linear) | set(params["layers"]["full"]))     # OLMo 2's order
    # the delta-net's published initialisation
    A = np.exp(np.asarray(linear["g_A_log"]))
    dt = np.log1p(np.exp(np.asarray(linear["g_dt_bias"])))
    assert 0.0 <= A.min() and A.max() <= 16.0
    assert 0.001 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6


def _deepseek_v2_says(cfg, params):
    assert cfg.pattern == ("mla_dense", "mla_moe", "mla_moe")
    H, moe = cfg.num_heads, params["layers"]["mla_moe"]
    assert moe["wq_b"].shape == (2, 32, H * 24)
    assert moe["wkv_b"].shape == (2, 24, H * 28)
    assert moe["wo"].shape == (2, H * 12, 64)
    assert moe["wkv_a"].shape == (2, 64, 24 + 8)
    assert moe["e_gate"].shape[1] == (8 if cfg.experts_held else 16)


def _dots3_says(cfg, params):
    assert cfg.pattern == ("full_dense", "full_moe", "sliding_moe",
                           "sliding_moe")
    full, win = params["layers"]["full_moe"], params["layers"]["sliding_moe"]
    assert full["wq_b"].shape == (1, 32, cfg.num_heads * 24)
    assert win["wq_b"].shape == (2, 32, cfg.swa_num_heads * 32)
    assert win["wkv_a"].shape == (2, 64, 32 + 8)
    assert full["wi_q"].shape == (1, 32, 4 * 16)      # the index is whole
    assert "wi_q" not in win and "wg" in win
    assert full["e_gate"].shape[1] == (8 if cfg.experts_held else 16)


def _qwen3_next_says(cfg, params):
    from ray_tpu.models import qwen3_next

    assert cfg.pattern == ("linear", "linear", "linear", "full")
    lin, full = params["layers"]["linear"], params["layers"]["full"]
    # z 4 x 16 | q and k 2 x 16 each, v 4 x 16 | a and b 4 each
    assert lin["g_in"].shape == (3, 64, 64 + 128 + 8)
    assert lin["g_conv"].shape == (3, 128, 4)
    assert full["wq"].shape == (1, 64, 2 * 4 * 16)
    assert full["e_gate"].shape == (1, cfg.experts_here, 64, 32)
    assert full["s_sigmoid"].shape == (1, 64)
    assert not float(jnp.abs(qwen3_next.init_params(
        cfg, jax.random.PRNGKey(0))["final_norm"]).max())


def _nemotron_h_says(cfg, params):
    assert cfg.pattern + cfg.mtp_pattern == (
        "mamba", "moe", "mamba", "attention", "moe", "attention", "moe")
    assert params["layers"]["moe"]["e_up"].shape == (
        2, 4 if cfg.experts_held else 16, 32, 48)
    assert "e_gate" not in params["layers"]["moe"]     # two-matrix experts
    assert set(params["mtp"]) == {"embed_norm", "hidden_norm", "join",
                                  "layers", "final_norm"}


def _ling3_says(cfg, params):
    assert cfg.pattern == ("kda+dense", "kda+moe", "mla+moe", "kda+moe")
    kda, mla = params["layers"]["kda+moe"], params["layers"]["mla+moe"]
    # q k v 3 x 4 x 16 | the decay's f 4 x 16 | b and the gate 4 each
    assert kda["k_in"].shape == (2, 64, 192 + 64 + 8)
    assert kda["k_conv"].shape == (2, 192, 4)
    assert kda["k_dt_bias"].shape == (2, 64)       # a bias a key channel
    assert kda["k_A_log"].shape == (2, 4)          # a rate a head
    assert mla["wq"].shape == (1, 64, 4 * 24)      # no query latent
    assert "wq_a" not in mla and "q_a_norm" not in mla
    assert mla["wg"].shape == (1, 64, 4)           # a gate a head
    assert mla["router_bias"].dtype == jnp.float32
    assert mla["e_gate"].shape == (1, cfg.experts_here, 64, 32)
    assert "router" not in params["layers"]["kda+dense"]


# ---- where a reference takes or hands back something of its own


def _keye_vl2_says(cfg, params):
    assert cfg.pattern == ("sparse_moe",) * 3
    layer = params["layers"]["sparse_moe"]
    assert layer["wq"].shape == (3, 64, 4 * 16)
    assert layer["wk"].shape == (3, 64, 2 * 16)           # grouped keys
    assert layer["q_norm"].shape == (3, 16)               # a head's norm
    assert layer["wi_q"].shape == (3, 64, 4 * 8)          # from the input
    assert layer["e_gate"].shape[1] == (4 if cfg.experts_held else 16)
    assert "s_gate" not in layer and "router_bias" not in layer
    assert params["lm_head"].shape == (64, 256)           # untied


# a document of text runs and image spans at a tiny size, as the cell's
# generator lays them out
KEYE_TRAFFIC = {"text_run": [2, 6], "grids": [[2, 2], [2, 3], [3, 3]],
                "image_share": 0.5}


def keye_vl2_batch(case):
    """``positions [3, 2, 48]`` in three streams and the text ``mask [2,
    49]`` of two seeded interleaved documents."""
    from benchmark.generators import train_batches_mrope as gen

    rng = np.random.default_rng(11)
    docs = [gen.document(rng, case.tokens.shape[1], KEYE_TRAFFIC)
            for _ in case.tokens]
    positions = np.stack([d["positions"][:, :-1] for d in docs], 1)
    assert (positions[0] != positions[2]).any()          # streams differ
    # (numpy: a case may make its batch while a program is being traced)
    return {"positions": positions,
            "mask": np.stack([1.0 - d["image"] for d in docs]
                             ).astype(np.float32)}


def _keye_vl2_forward(mod, cfg, p, t, positions, mask):
    return mod.forward_reports(cfg, p, t, positions)


def _keye_vl2_forced(case):
    said = case.program[1]
    return {"positions": case.batch["positions"],
            "forced_topk": jax.lax.top_k(said["router"]["logits"],
                                         case.cfg.top_k)[1],
            "forced_keys": said["dsa"]["choice"]}


def _keye_vl2_reports(case):
    assert case.program[1]["dsa"]["choice"].shape == (3, 2, 48, 6)


def _keye_vl2_reference(case):
    """((cross entropy over the text targets, index loss, balancing term),
    every leaf's gradient of the whole loss) of the reference on the
    program's choices, from one compiled function; kept on the case."""
    if "reference" not in case.__dict__:
        forced, mask = case.forced, case.batch["mask"]

        def whole(p):
            ce, l_i, bal = case.ref.loss_terms(case.cfg, p, case.tokens,
                                               mask=mask, **forced)
            return (ce + case.cfg.index_loss_coef * l_i
                    + case.cfg.router_aux_coef * bal, (ce, l_i, bal))

        (loss, terms), grads = jax.jit(
            jax.value_and_grad(whole, has_aux=True))(case.params)
        case.reference = (loss,) + terms, grads
    return case.reference


def _keye_vl2_want_terms(case):
    loss, ce, l_i, bal = _keye_vl2_reference(case)[0]
    return {"cross_entropy": ce, "dsa_index_loss": l_i, "load_balance": bal,
            "loss": loss}


def _keye_vl2_terms(case, loss, terms):
    assert float(terms["dsa_index_loss"]) > 0.05
    # 48 positions, 8 keys each past the first 8: (36 + 40 x 8) / 1176
    np.testing.assert_allclose(terms["dsa_pairs_chosen_share"],
                               (36 + 40 * 8) / (48 * 49 / 2), rtol=1e-6)
    assert terms["expert_counts"].shape == (3, 16)


def _keye_vl2_gradients(case):
    return case._loss_and_gradient[1], _keye_vl2_reference(case)[1]


def _dots3_forward(mod, cfg, p, t):
    return mod.forward_reports(cfg, p, t)


def _dots3_forced(case):
    said = case.program[1]
    return {"forced_topk": said["router"]["chosen"],
            "forced_keys": said["dsa"]["choice"]}


def _dots3_reports(case):
    assert case.program[1]["dsa"]["choice"].shape == (2, 2, 48, 6)


def _dots3_reference(case):
    """((cross entropy, index loss), every leaf's gradient of their sum)
    of the reference on the program's choices, from one compiled function;
    kept on the case. The reference has no ``loss``."""
    if "reference" not in case.__dict__:
        forced = case.forced

        def both(p):
            ce, l_i = case.ref.loss_terms(case.cfg, p, case.tokens, **forced)
            return ce + l_i, (ce, l_i)

        (_, terms), grads = jax.jit(jax.value_and_grad(both, has_aux=True))(
            case.params)
        case.reference = terms, grads
    return case.reference


def _dots3_want_terms(case):
    ce, l_i = _dots3_reference(case)[0]
    return {"cross_entropy": ce, "dsa_index_loss": l_i, "loss": ce + l_i}


def _dots3_terms(case, loss, terms):
    assert float(terms["dsa_index_loss"]) > 0.05
    # 48 positions, 8 keys each past the first 8: (36 + 40 x 8) / 1176
    np.testing.assert_allclose(terms["dsa_pairs_chosen_share"],
                               (36 + 40 * 8) / (48 * 49 / 2), rtol=1e-6)
    assert terms["expert_counts"].shape == (3, 16)


def _dots3_gradients(case):
    return case._loss_and_gradient[1], _dots3_reference(case)[1]


def _qwen3_next_forced(case):
    said = case.program[1]
    return {"forced_topk": jax.lax.top_k(jax.nn.softmax(
        said["router"]["logits"], -1), case.cfg.top_k)[1]}


def _qwen3_next_reports(case):
    counts = case.program[1]["router"]["counts"]
    assert counts.shape == (4, 16)
    assert int(counts.sum()) == 4 * 2 * 32 * case.cfg.top_k


def _qwen3_next_gradients(case):
    """Of the first row's loss: the reference's router term is a row's."""
    cfg, params, tokens = case.cfg, case.params, case.tokens
    chosen = case.forced["forced_topk"][:, :32]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: case.mod.loss_fn(
            cfg, p, {"tokens": jnp.asarray(tokens[:1])})))(params)
    want = jax.jit(jax.grad(lambda p: case.ref.loss(
        cfg, p, tokens[:1], forced_topk=chosen)))(params)
    return got, want


def _ling3_reports(case):
    """No token's choices span more than ``topk_group`` groups, and the
    smallest log decay stays above the bound."""
    cfg, said = case.cfg, case.program[1]
    chosen = np.asarray(said["router"]["chosen"])
    per_group = cfg.num_experts // cfg.n_group
    assert max(len(set(row // per_group)) for row in chosen.reshape(
        -1, cfg.top_k)) == cfg.topk_group
    least = np.asarray(said["kda"]["log_decay_min"])
    assert least.shape == (3,) and (least > cfg.kda_lower_bound).all()
    np.testing.assert_allclose(least.min(), case.want["log_decay_min"],
                               rtol=1e-5)


def _ling3_rungs(kinds):
    # the latent layer keeps its kv latent alone on the first rung (no
    # query latent) beside the flash output and log-sum-exp
    assert kinds["mla+moe"]["rungs"][0] == 64 * (4 * 12 * 2 + 4 * 4
                                                 + (24 + 8) * 2)
    # a KDA layer's one rung that keeps anything is its MLP's
    assert kinds["kda+moe"]["rungs"][:2] == (0, 0)
    assert kinds["kda+dense"]["rungs"][:2] == (0, 0)


def _ling3_hand_counts():
    """``benchmark/lib/kda_moe_flops.py`` at the configuration file's sizes
    against the issue's arithmetic."""
    from benchmark.lib import kda_moe_flops as lib
    from benchmark.lib import spec

    m = spec.model_sizes(json.load(open(os.path.join(
        spec.ROOT, "benchmark/configs/ling-3.0-flash-vl-c1.json"))))
    assert (lib.count(m, "kda"), lib.count(m, "mla"), lib.count(m, "dense"),
            lib.count(m, "moe")) == (6, 1, 1, 6)
    # a KDA mixer's matrices: W_q, W_k, W_v, W_f, W_b, W_g, W_o
    assert lib.kda_proj_params(m) == 5 * 2560 * 4096 + 2 * 2560 * 32 \
        == 52_648_608 - 2560 - 3 * 4096 * 4 - 32 - 4096 - 128
    assert lib.mla_proj_params(m) == 31_968_256 - 2560 - 512
    assert lib.dense_params(m) == 47_188_480 - 2560
    assert lib.router_params(m) + lib.shared_params(m) == 7_211_520 - 2560
    assert lib.expert_params(m) == 5_898_240
    assert lib.head_params(m) == 2560 * 19_648
    assert lib.conv_dim(m) == 12_288
    # 96 TFLOP of token matmuls; the latent layer's causal attention 11 a
    # forward, 33 at the MFU's three times, 39.6 as the kernels' seven
    # products
    assert 95e12 < 6.0 * lib.token_matmul_params(m) * 32768 < 97e12
    fwd = 32 * (2 * 192 + 2 * 128) * 32768 * 32769 / 2
    assert lib.attention_flops_fwd(m, 1, 32768) == fwd
    assert lib.flash_flops_per_step(m, 1, 32768) == 3.6 * fwd
    assert lib.experts_train_flops(m, 6 * 4096) == 6.0 * 5_898_240 * 24_576
    # the rule: lib/delta_flops.py's count at 32 heads of 128 / 128
    C, H, K, V = 64, 32, 128, 128
    fwd = 32768 / C * H * (C * (C + 1) / 2 * (6 * K + 4 * V) + C ** 3 / 3
                           + 6 * C * K * V)
    assert lib.rule_flops_per_step(m, 1, 32768) == 3 * 6 * fwd
    # q, k, v, g and beta in, o out; the backward those, do, five gradients
    ins, out = (3 * 4096 + 4096 + 32) * 2, 4096 * 2
    assert lib.rule_bytes_per_step(m, 32768) == 6 * 32768 * (
        (ins + out) + (2 * ins + out))


def _lfm2_terms(case, loss, terms):
    assert float(loss) == float(terms["cross_entropy"])


def _laguna_terms(case, loss, terms):
    # the share changes the result: what the absent experts add is left out
    assert case.want["terms"]["load_balance"] > 1.0


def _deepseek_v2_reports(case):
    # no token's choices span more than ``topk_group`` groups
    cfg = case.cfg
    chosen = np.asarray(case.program[1]["chosen"])
    per_group = cfg.num_experts // cfg.n_group
    assert max(len(set(row // per_group)) for row in chosen.reshape(
        -1, cfg.top_k)) == cfg.topk_group


def _deepseek_v2_terms(case, loss, terms):
    # two routed layers, each a sequence's sum_e f_e P_e near 1 at a
    # near-uniform router
    assert 1.8 < case.want["terms"]["load_balance"] < 4.0


def _nemotron_h_moved(params):
    """Router biases that take part in the choice, the module's too."""
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 2))
    for layers in (params["layers"], params.get("mtp", {}).get("layers", {})):
        if "moe" in layers:
            b = layers["moe"]["router_bias"]
            layers["moe"]["router_bias"] = 0.05 * jax.random.normal(
                next(keys), b.shape, b.dtype)
    return params


def _nemotron_h_forward(mod, cfg, p, t):
    return mod.forward(cfg, p, t)


def _nemotron_h_forced(case):
    return {"forced_topk": np.asarray(jax.jit(
        lambda p: case.mod.token_nlls(
            case.cfg, p, case.tokens, keep_router_logits=True))(
                case.params)[2]["router"]["chosen"])}


def _nemotron_h_reports(case):
    said = case.program[1]
    assert said["ssm_state"].shape == (2, 2, 8, 16, 16)
    assert said["router"]["counts"].shape == (2, 16)


def nemotron_h_reference_grads(case):
    """(cross entropy, the module's, the gradient of each with respect to
    every leaf) from one compiled function: the gradient of ``ce + w *
    more`` is linear in ``w``. Kept on the case: the suite's gradients and
    ``tests/test_nemotron_h.py``'s test of the module's term read it."""
    if not hasattr(case, "reference_grads"):
        forced = case.forced

        def weighted(p, w):
            _, (ce, more) = case.ref.loss(case.cfg, p, case.tokens, **forced)
            return ce + w * more, (ce, more)

        both = jax.jit(jax.value_and_grad(weighted, has_aux=True))
        (_, (ce, more)), g0 = both(case.params, 0.0)
        _, g1 = both(case.params, 1.0)
        case.reference_grads = (float(ce), float(more), g0,
                                jax.tree_util.tree_map(jnp.subtract, g1, g0))
    return case.reference_grads


def nemotron_h_program_grads(case, scale):
    """((loss, terms), the trained leaves' gradients) of the program with
    the module's term weighed by ``scale``; kept on the case."""
    import dataclasses

    made = case.__dict__.setdefault("program_grads", {})
    if scale not in made:
        c = dataclasses.replace(case.cfg, mtp_loss_scale=scale)
        made[scale] = jax.jit(jax.value_and_grad(
            lambda t: case.mod.loss_terms(
                c, case.mod.with_trainable(case.params, t),
                {"tokens": case.tokens}), has_aux=True))(
                    case.mod.trainable(case.params))
    return made[scale]


def _nemotron_h_want_terms(case):
    if case.share == "whole":
        ce, more, _, _ = nemotron_h_reference_grads(case)
    else:
        forced = case.forced
        _, (ce, more) = jax.jit(lambda p: case.ref.loss(
            case.cfg, p, case.tokens, **forced))(case.params)
    return {"cross_entropy": ce, "mtp_cross_entropy": more,
            "loss": ce + case.cfg.mtp_loss_scale * more}


def _nemotron_h_terms(case, loss, terms):
    chosen = case.forced["forced_topk"]
    assert chosen.shape == (3, 64, 4)                 # the module's last
    assert terms["expert_counts"].shape == (3, 16)
    # the reference's own choice is the program's
    own = case.ref.token_nll(case.cfg, case.params, case.tokens)
    assert (np.sort(own["chosen"], -1) == np.sort(chosen, -1)).all()
    if case.cfg.experts_held:
        assert int(case.mod.rows_held(case.cfg, terms["expert_counts"])) \
            == int(terms["expert_counts"][:, 4:8].sum())


def _nemotron_h_gradients(case):
    _, _, g_ce, g_more = nemotron_h_reference_grads(case)
    scale = case.cfg.mtp_loss_scale
    return case._loss_and_gradient[1], jax.tree_util.tree_map(
        lambda a, b: a + scale * b, g_ce, g_more)


# ---- the presets against the model cards


def _lfm2_preset(lfm2):
    """24 layers, 18 conv and 6 attention, 8.34 B parameters; the cell's
    cut: layer 0 and the first period, 16 of 32, half the rows."""
    import pytest

    cfg = lfm2.Lfm2Config.lfm2_8b_a1b()
    assert cfg.pattern.count("conv_moe") == 16
    assert cfg.pattern.count("attn_moe") == 6
    assert cfg.pattern[:2] == ("conv_dense", "conv_dense")
    assert cfg.head_dim_ == 64
    assert abs(count(lfm2, cfg) / 8.34e9 - 1) < 0.001
    cut = lfm2.Lfm2Config.lfm2_8b_a1b(
        num_layers=5, vocab_size=32768, num_dense_layers=1,
        attention_layers=(False, True, False, False, False),
        experts_held=(0, 16))
    assert abs(count(lfm2, cut) / 893.7e6 - 1) < 0.001
    with pytest.raises(ValueError, match="attention_layers names"):
        lfm2.Lfm2Config.lfm2_8b_a1b(num_layers=5)


def _granite_preset(granite):
    """40 layers, 36 of them Mamba-2, 3.19 B parameters with the embedding
    tied; one period with the whole vocabulary is the cell's
    951,991,232."""
    import pytest

    cfg = granite.GraniteConfig.granite_4_0_h_micro(
        param_dtype=jnp.bfloat16)
    assert cfg.pattern.count("mamba") == 36 and cfg.pattern[5] == "attention"
    assert abs(count(granite, cfg) / 3.19e9 - 1) < 0.01
    period = granite.GraniteConfig.granite_4_0_h_micro(
        num_layers=10, attention_layers=cfg.attention_layers[:10])
    assert count(granite, period) == 951_991_232
    with pytest.raises(ValueError, match="attention_layers names"):
        granite.GraniteConfig.granite_4_0_h_micro(num_layers=10)


def _olmo_hybrid_preset(olmo_hybrid):
    """32 layers, every fourth full attention, 7.43 B parameters with an
    untied head; one period with an eighth of the vocabulary is the cell's
    928,862,196 (928.7 M by the issue's rounded addends)."""
    import pytest

    cfg = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(
        param_dtype=jnp.bfloat16)
    assert cfg.pattern.count("linear") == 24
    assert cfg.pattern[:4] == ("linear", "linear", "linear", "full")
    assert cfg.head_dim_ == 128 and cfg.linear_conv_dim == 11_520
    assert count(olmo_hybrid, cfg) == 7_430_870_688
    period = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(
        num_layers=4, vocab_size=12_544)
    assert period.pattern == cfg.pattern[:4]
    assert count(olmo_hybrid, period) == 928_862_196
    assert abs(count(olmo_hybrid, period) / 928.7e6 - 1) < 5e-4
    with pytest.raises(ValueError, match="attention_layers names"):
        olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b(
            num_layers=4, attention_layers=cfg.attention_layers)


def _deepseek_v2_preset(deepseek_v2):
    """The published sizes give 236 B parameters, 21 B of them active a
    token; the benchmark's cut (5 layers, 32 heads, 8 experts, 12,800
    rows) the 1,493,959,680 the configuration file states."""
    from ray_tpu.ops import mla

    cfg = deepseek_v2.DeepseekV2Config.deepseek_v2()
    assert cfg.pattern == ("mla_dense",) + ("mla_moe",) * 59
    assert 235e9 < count(deepseek_v2, cfg) < 237e9
    assert abs(mla.softmax_scale(cfg) - 0.114721) < 1e-5
    cut = deepseek_v2.DeepseekV2Config.deepseek_v2(
        num_layers=5, vocab_size=12_800, num_heads=32, heads_of=128,
        experts_held=(0, 8))
    assert count(deepseek_v2, cut) == 1_493_959_680


def _dots3_preset(dots3):
    """46 layers, full at 0, 1, 5, 9, ...: 13 full and 33 window, the
    first dense; 279.6 B parameters in the language model."""
    cfg = dots3.Dots3Config.dots3_note_prev()
    assert cfg.pattern.count("sliding_moe") == 33
    assert cfg.pattern[:6] == ("full_dense", "full_moe", "sliding_moe",
                               "sliding_moe", "sliding_moe", "full_moe")
    assert cfg.pattern[-1] == "full_moe"
    assert abs(count(dots3, cfg) / 279.6e9 - 1) < 0.005


def _nemotron_h_published(model):
    import pytest

    cfg = model.Nemotron_hConfig.nemotron_3_super_120b_a12b()
    assert len(cfg.pattern) == 88
    assert (cfg.pattern.count("mamba"), cfg.pattern.count("moe"),
            cfg.pattern.count("attention")) == (40, 40, 8)
    assert cfg.pattern[26:37] == tuple(
        {"E": "moe", "M": "mamba", "*": "attention"}[c]
        for c in "EMEMEMEMEM*")
    assert cfg.mtp_pattern == ("attention", "moe")
    assert (cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_chunk, cfg.top_k,
            cfg.num_experts, cfg.moe_latent_size, cfg.routed_scale) == (
        128, 8, 128, 22, 512, 1024, 5.0)
    with pytest.raises(ValueError, match="a layer is M, E or"):
        model.Nemotron_hConfig.tiny(layer_pattern="MEMXE")
    with pytest.raises(ValueError, match="num_layers is 5"):
        model.Nemotron_hConfig.tiny(layer_pattern="ME")


def _nemotron_h_cell(model):
    cfg = model.Nemotron_hConfig.nemotron_3_super_120b_a12b(
        layer_pattern="EMEMEMEMEM*", vocab_size=16384, experts_held=(0, 8))
    shapes = jax.eval_shape(lambda k: model.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert count(model, cfg) == 1_378_724_736
    assert "e_gate" not in shapes["layers"]["moe"]
    assert shapes["layers"]["moe"]["e_up"].shape == (5, 8, 1024, 2688)
    assert shapes["layers"]["mamba"]["m_in"].shape == (5, 4096, 18560)
    assert shapes["mtp"]["join"].shape == (8192, 4096)


# ---- the plan's kinds


def _keye_vl2_preset(keye_vl2):
    """48 layers of one kind, 128 experts of 768 in each: 30.5 B parameters
    in the language model, 3.3 B of them active a token."""
    cfg = keye_vl2.KeyeVL2Config.keye_vl2_30b_a3b()
    assert cfg.pattern == ("sparse_moe",) * 48
    total = count(keye_vl2, cfg)
    assert abs(total / 30.6e9 - 1) < 0.01
    expert = 3 * 2048 * 768
    assert abs((total - 48 * 120 * expert) / 3.4e9 - 1) < 0.02
    # the cell's cut: six layers, 32 experts, a quarter of the vocabulary
    cut = keye_vl2.KeyeVL2Config.keye_vl2_30b_a3b(
        num_layers=6, experts_held=(0, 32), vocab_size=37_984)
    assert count(keye_vl2, cut) == 1_189_966_080


def _keye_vl2_rungs(kinds):
    # an index layer keeps what its walk keeps on the first rung (the
    # output, the heads' log-sum-exp float32 and the choice packed over
    # each of four tiers' keys: 12 rows of 2, 3, 5 and 6 bytes) and its q,
    # k, v on the second
    assert kinds["sparse_moe"]["rungs"][0] == (
        48 * 4 * (16 * 2 + 4) + 12 * (2 + 3 + 5 + 6))
    assert kinds["sparse_moe"]["rungs"][1] == 48 * (4 + 2 * 2) * 16 * 2


def _keye_vl2_hand_counts():
    """``benchmark/lib/sparse_gqa_flops.py`` on the cell's configuration
    file, against counts written out by hand."""
    from benchmark.lib import sparse_gqa_flops as sg

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-c1.json")) as f:
        m = json.load(f)
    assert sg.layers(m) == 6
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    index = 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert sg.attn_proj_params(m) == attn == 18_874_368
    assert sg.index_proj_params(m) == index == 2_260_992
    T = 16_384
    assert sg.proj_flops_per_step(m, T) == T * 6 * (6 * attn + 4 * index)
    chosen = 2048 * 2049 / 2 + (T - 2048) * 2048
    assert sg.kept_pairs(T, 2048) == chosen == 31_458_304
    assert sg.causal_pairs(T) == 134_225_920
    # the index: 2,048 a causal pair forward, twice that a chosen pair back
    assert sg.index_flops_per_step(m, 1, T) == 6 * 2 * 16 * 64 * (
        T * (T + 1) / 2 + 2 * chosen)
    # attention: chosen pairs x 32 heads x (8 x 128 + 6 x 128), six layers
    assert sg.sparse_flash_flops_per_step(m, 1, T) == \
        6 * 32 * (8 * 128 + 6 * 128) * chosen
    # q and o at 32 heads, k and v once a group of 8: 4 heads' worth
    assert sg.flash_bytes_per_step(m, T) == 6 * T * 2 * (
        2 * (32 * 128 + 2 * 4 * 128) + 2 * 32 * 128)
    assert sg.head_params(m) == 2048 * 37_984
    assert sg.router_params(m) == 6 * 2048 * 128
    rows = 6 * T * 8 / 4
    assert sg.train_flops_per_step(m, 1, T, rows) == (
        T * 6 * (6 * attn + 4 * index)
        + 6 * (6 * 2048 * 128 + 2048 * 37_984) * T
        + 6 * 3 * 2048 * 768 * rows
        + 6 * 2 * 16 * 64 * (T * (T + 1) / 2 + 2 * chosen)
        + 3 * 6 * 32 * 4 * 128 * chosen)


def _dots3_rungs(kinds):
    # an index layer keeps its two latents alone on the first rung (the
    # walk has no flash output to keep); a window layer its flash output
    # and log-sum-exp besides
    assert kinds["full_moe"]["rungs"][0] == 48 * (32 + 16 + 8) * 2
    assert kinds["sliding_moe"]["rungs"][0] == 48 * (
        2 * 12 * 2 + 2 * 4 + (32 + 32 + 8) * 2)
    assert kinds["full_dense"]["working_bytes"] > \
        kinds["sliding_moe"]["working_bytes"]


def _qwen3_next_rungs(kinds):
    # a full layer keeps its flash output and log-sum-exp on the first rung
    # at its own 4 heads of 16, not at ``wq``'s width, which holds the gate
    assert kinds["full"]["rungs"][0] == 64 * (64 * 2 + 4 * 4)
    assert kinds["full"]["rungs"][1] == 64 * (64 + 2 * 32) * 2
    # a linear layer's one rung that keeps anything is the shared SwiGLU's
    assert kinds["linear"]["rungs"] == (0, 0, 2 * 64 * 32 * 2, 0)


# ---- the cells' FLOPs and bytes against hand counts


def _deepseek_v2_hand_counts():
    """``benchmark/lib/latent_flops.py`` at the configuration file's sizes
    against the issue's arithmetic: no roofline or MFU counts more than
    the mathematics needs."""
    from benchmark.lib import latent_flops, spec

    m = spec.model_sizes(json.load(open(os.path.join(
        spec.ROOT, "benchmark/configs/deepseek-v2-c1.json"))))
    proj = (5120 * 1536 + 1536 * 32 * 192 + 5120 * 576 + 512 * 32 * 256
            + 32 * 128 * 5120)
    assert proj == 45_416_448 == latent_flops.mla_proj_params(m)
    assert (latent_flops.layers(m), latent_flops.routed_layers(m)) == (5, 4)
    assert latent_flops.mlp_params(m) == 188_743_680 + 4 * 47_185_920
    assert latent_flops.head_params(m) == 65_536_000
    assert latent_flops.token_matmul_params(m) == (
        5 * proj + 188_743_680 + 4 * (47_185_920 + 819_200) + 65_536_000)
    pairs = 8192 * 8193 / 2
    fwd = 5 * 32 * (2 * 192 + 2 * 128) * pairs
    assert latent_flops.attention_flops_fwd(m, 1, 8192) == fwd
    flash = latent_flops.flash_flops_per_step(m, 1, 8192)
    assert flash == 5 * 32 * (3 * 2 * 192 + 2 * 2 * 128 + 2 * 192 + 2 * 128
                              ) * pairs
    assert abs(flash / fwd - 3.6) < 1e-9
    # q, k (the 64 shared dims once), v, o, dO, dq, dk, dv in bf16
    q, k, v = 32 * 192, 32 * 128 + 64, 32 * 128
    assert latent_flops.flash_bytes_per_step(m, 8192) == (
        5 * 8192 * 2 * (q + k + v + v + v + q + k + v))
    rows = 4 * 8192 * 6 * 8 / 160
    step = latent_flops.train_flops_per_step(m, 1, 8192, rows)
    # the issue's count: 701.5 M multiply-adds a token and 1.03e13 of
    # attention, 4.5e13 a step
    assert abs((step - 3 * fwd) / 6 / 8192 / 701.5e6 - 1) < 0.005
    assert abs(3 * fwd / 1.03e13 - 1) < 0.01 and 4.4e13 < step < 4.6e13
    # the flash kernels' floor is compute's: 15.7 ms of FLOPs a step
    # against 1.2 ms of bytes on a v5e
    assert flash / 197e12 > 10 * latent_flops.flash_bytes_per_step(
        m, 8192) / 819e9


def _dots3_hand_counts():
    """``benchmark/lib/sparse_flops.py`` on the cell's configuration file,
    against counts written out by hand, and the parameters the program
    holds against the same."""
    from benchmark.lib import sparse_flops as sf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots3-note-prev-c1.json")) as f:
        m = json.load(f)
    assert (sf.full_layers(m), sf.window_layers(m), sf.routed_layers(m)) \
        == (2, 3, 4)
    full = (5120 * 1024 + 1024 * 16 * 192 + 5120 * 576 + 512 * 16 * 256
            + 16 * 128 * 5120 + 5120 * 16)
    win = (5120 * 1024 + 1024 * 8 * 256 + 5120 * 1088 + 1024 * 8 * 320
           + 8 * 128 * 5120 + 5120 * 8)
    index = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    assert sf.mla_proj_params(m) == full == 24_002_560
    assert sf.mla_proj_params(m, "swa_") == win == 20_815_872
    assert sf.index_proj_params(m) == index == 9_371_648
    T = 16_384
    assert sf.proj_flops_per_step(m, T) == T * (
        6 * (2 * full + 3 * win) + 4 * 2 * index)
    assert sf.causal_pairs(T) == T * (T + 1) / 2 == 134_225_920
    chosen = 2048 * 2049 / 2 + (T - 2048) * 2048
    band = 513 * 514 / 2 + (T - 513) * 513
    assert sf.kept_pairs(T, 2048) == chosen == 31_458_304
    assert sf.kept_pairs(T, 513) == band
    assert sf.kept_pairs(48, 2048) == 48 * 49 / 2
    # the index: 16,384 a causal pair forward, twice that a chosen pair back
    assert sf.index_flops_per_step(m, 1, T) == 2 * 2 * 64 * 128 * (
        T * (T + 1) / 2 + 2 * chosen)
    assert sf.sparse_flash_flops_per_step(m, 1, T) == \
        2 * 16 * (8 * 192 + 6 * 128) * chosen
    assert sf.window_flash_flops_per_step(m, 1, T) == \
        3 * 8 * (8 * 256 + 6 * 128) * band
    assert sf.flash_bytes_per_step(m, T) == 2 * T * 2 * (
        2 * (16 * 192 + 16 * 128 + 64 + 16 * 128) + 2 * 16 * 128)
    assert sf.flash_bytes_per_step(m, T, "swa_") == 3 * T * 2 * (
        2 * (8 * 256 + 8 * 192 + 64 + 8 * 128) + 2 * 8 * 128)
    assert sf.mlp_params(m) == 3 * 5120 * (13_824 + 4 * 1536)
    assert sf.head_params(m) == 5120 * 19_008
    rows = 4 * T * 8 * 8 / 256
    whole = sf.train_flops_per_step(m, 1, T, rows)
    by_hand = (
        T * (6 * (2 * full + 3 * win) + 8 * index)
        + 6 * T * (3 * 5120 * (13_824 + 4 * 1536) + 4 * 5120 * 256
                   + 5120 * 19_008)
        + 6 * 3 * 5120 * 1536 * rows
        + 2 * 2 * 64 * 128 * (T * (T + 1) / 2 + 2 * chosen)
        + 3 * (2 * 16 * 640 * chosen + 3 * 8 * 768 * band))
    assert whole == by_hand
    # the index is a fifth of what the step needs at 16,384 positions
    assert 0.1 < sf.index_flops_per_step(m, 1, T) / whole < 0.25
    # and the program holds what the file says it does
    from benchmark.cells.train_hybrid import load_model

    model, _, cfg = load_model(m["model_config"])
    held = count(model, cfg)
    norms = 5 * 2 * 5120 + 5120 + 5 * 1024 + 2 * 512 + 3 * 1024 + 2 * 256
    assert held == (2 * (full + index) + 3 * win + 3 * 5120 * 13_824
                    + 4 * (5120 * 256 + 256 + 3 * 5120 * 1536 * 9)
                    + 2 * 5120 * 19_008 + norms)
    assert str(held) in m["deployment"].replace(",", "")


# ---- the table


def _norm(cfg, p, x):
    from ray_tpu.ops.layers import rms_norm

    return rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)


def _zero_centred_norm(cfg, p, x):
    from ray_tpu.ops.layers import rms_norm

    return rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps, True)


def _routed_layers_share(ref, cfg, p, u):
    """What every chip computes alike: the reference's layer less its
    routed part."""
    return ref.routed_layer(cfg, p, u) - ref.routed_layer(cfg, p, u,
                                                          shared=False)


def _relu2_share(ref, cfg, p, u):
    from ray_tpu.ops.layers import relu2_mlp

    return relu2_mlp(u, p["s_up"], p["s_down"])


_STATES = {
    "ling3": (("kda", "state"), "kda_state_abs_max", lambda c: (4, 16, 16)),
    "granite": (None, "ssm_state_abs_max", lambda c: (
        c.ssm_heads, c.ssm_head_dim, c.ssm_state)),
    "olmo_hybrid": (None, "gdn_state_abs_max", lambda c: (
        c.linear_heads, c.linear_value_dim, c.linear_key_dim)),
    "qwen3_next": ("gdn_state", "gdn_state_abs_max", lambda c: (4, 16, 16)),
}

# the four of PR 42's seam: the loss to 1e-5 of itself where it is over
# 1, a term to 1e-5
_STACK_TERMS = {"": (0.0, 1e-5), "loss": (1e-5, 1e-5)}
_VARIANTS = ("remat-full", "unrolled", "bf16")
# a bias, or a limit to groups, takes part in the choice
_ROUTE = {"router": 1e-5, "choice": None, "moved": None, "counts": None}

ROWS = {row.name: row for row in (
    Row("laguna", "LagunaConfig",
        shares={"all-experts": {"experts_held": None},
                "held-4..11": {"experts_held": (4, 8)}},
        moved=(("attn_norm", 0.3), ("mlp_norm", 0.3)),
        says=_laguna_says,
        groups={"top": 3, "full_dense": 10, "sliding_moe": 14,
                "full_moe": 14},
        reports={"router": 1e-5, "counts": None}, term_tol=_STACK_TERMS,
        terms_also=_laguna_terms, weighted=3),
    Row("lfm2", "Lfm2Config",
        shares={"all-experts": {"experts_held": None},
                "held-0..3": {"experts_held": (0, 4)}},
        moved=(("attn_norm", 0.3), ("op_norm", 0.3), ("mlp_norm", 0.3),
               ("q_norm", 0.3), ("k_norm", 0.3), ("router_bias", 0.1)),
        says=_lfm2_says,
        groups={"top": 2, "conv_dense": 8, "attn_moe": 12, "conv_moe": 9},
        forced=lambda case: {"forced_topk": np.asarray(
            case.program[1]["chosen"])},
        forced_in=("weighted",),
        reports=_ROUTE, term_tol=_STACK_TERMS, terms_also=_lfm2_terms,
        weighted=2,
        presets={"": _lfm2_preset}),
    Row("granite", "GraniteConfig", shares={"": {}},
        moved=(("attn_norm", 0.3), ("op_norm", 0.3), ("mlp_norm", 0.3),
               ("m_norm", 0.3), ("D", 0.3), ("m_conv_bias", 0.3)),
        says=_granite_says, groups={"top": 2, "mamba": 13, "attention": 9},
        reports={"state": 1e-5}, state=_STATES["granite"],
        term_tol=_STACK_TERMS,
        grad_tol=(1e-4, 1e-5, 1e-2, 1e-6), weighted=2,
        blocked={"cell": "train_scan", "variants": _VARIANTS,
                 "step_tol": 1e-5, "fsdp_atol": 1e-5},
        presets={"": _granite_preset}),
    Row("olmo_hybrid", "OlmoHybridConfig", shares={"": {}},
        moved=(("attn_post_norm", 0.3), ("op_post_norm", 0.3),
               ("mlp_post_norm", 0.3), ("g_norm", 0.3), ("q_norm", 0.3),
               ("k_norm", 0.3)),
        says=_olmo_hybrid_says, groups={"top": 3, "linear": 11, "full": 11},
        # a block that norms every sublayer's output to unit size damps no
        # rounding (the gap to the reference grows threefold a layer, 5e-6
        # after one and 3e-5 after three, and two chunk sizes differ by
        # 1e-5 between themselves), where Granite's residual weights of
        # 0.22 do; tests/test_ssm_ops.py holds the rule and the mixer
        # alone to 1e-5
        logits_tol=(1e-5, 5e-5),
        reports={"state": 5e-5}, state=_STATES["olmo_hybrid"],
        term_tol=_STACK_TERMS,
        grad_tol=(1e-4, 5e-5, 1e-2, 1e-6), weighted=2,
        # adamw's first step is the rate times the gradient's sign,
        # nearly: an entry whose gradient is within a rounding of zero may
        # move by a part of 1e-3 more or less under fsdp (one of 75,264
        # did, by 1.7e-4)
        blocked={"cell": "train_delta",
                 "variants": _VARIANTS + ("chunk-4", "chunk-16"),
                 "step_tol": 1e-4, "fsdp_atol": 3e-4},
        presets={"": _olmo_hybrid_preset}),
    Row("deepseek_v2", "DeepseekV2Config",
        shares={"whole": {"experts_held": None},
                "2-of-4-heads-experts-4..11": {
                    "experts_held": (4, 8), "num_heads": 2, "heads_of": 4}},
        moved=(("attn_norm", 0.3), ("q_a_norm", 0.3), ("kv_a_norm", 0.3),
               ("mlp_norm", 0.3)),
        says=_deepseek_v2_says,
        groups={"top": 3, "mla_dense": 12, "mla_moe": 16},
        logits_tol=(1e-5, 2e-5),
        reports=_ROUTE, reports_also=_deepseek_v2_reports, terms_also=_deepseek_v2_terms,
        grad_tol=(0.0, 2e-4, 0.0, 0.0),
        expert_shares={"kind": "mla_moe", "tiny": {
            "num_experts": 20, "n_group": 4, "topk_group": 2, "top_k": 3},
            "each": 1, "norm": _norm, "shared": _routed_layers_share},
        head_shares={"mla_moe": (4, "")},
        ref_attention=lambda ref, cfg, p, x, kind: ref.attention_layer(
            cfg, p, x),
        presets={"": _deepseek_v2_preset},
        hand_counts=_deepseek_v2_hand_counts),
    Row("dots3", "Dots3Config", tokens=(1, (2, 49), np.int64),
        shares={"whole": {"experts_held": None},
                "half-the-heads-experts-4..11": {
                    "experts_held": (4, 8), "num_heads": 2, "heads_of": 4,
                    "swa_num_heads": 1, "swa_heads_of": 2}},
        moved=(("attn_norm", 0.3), ("q_a_norm", 0.3), ("kv_a_norm", 0.3),
               ("mlp_norm", 0.3), ("wi_k_norm", 0.3), ("wi_k_bias", 0.3),
               ("router_bias", 0.05)),
        says=_dots3_says,
        groups={"top": 3, "full_dense": 18, "full_moe": 22,
                "sliding_moe": 17},
        forward=_dots3_forward, forced=_dots3_forced,
        forced_in=("logits",),
        logits_tol=(2e-4, 2e-4), reports_also=_dots3_reports,
        want_terms=_dots3_want_terms,
        term_tol={"": (1e-5, 0.0), "dsa_index_loss": (1e-4, 0.0)},
        terms_also=_dots3_terms,
        gradients=_dots3_gradients,
        grad_tol=(2e-3, 2e-4, 0.0, 0.0),
        expert_shares={"kind": "full_moe", "tiny": {}, "each": 1,
                       "norm": _norm, "shared": _routed_layers_share,
                       "bias": 0.05},
        head_shares={"full_moe": (4, ""), "sliding_moe": (2, "swa_")},
        ref_attention=lambda ref, cfg, p, x, kind: ref.attention_layer(
            cfg, p, x, kind)[0],
        presets={"": _dots3_preset},
        plan={"tiny": {}, "tokens": 48,
              "runs": (("full_dense", 1), ("full_moe", 1),
                       ("sliding_moe", 2)), "rungs": _dots3_rungs},
        hand_counts=_dots3_hand_counts),
    Row("keye_vl2", "KeyeVL2Config", tokens=(1, (2, 49), np.int64),
        shares={"whole": {"experts_held": None},
                "held-4..7": {"experts_held": (4, 4)}},
        moved=(("attn_norm", 0.3), ("mlp_norm", 0.3), ("q_norm", 0.3),
               ("k_norm", 0.3), ("wi_k_norm", 0.3), ("wi_k_bias", 0.3)),
        says=_keye_vl2_says, groups={"top": 3, "sparse_moe": 17},
        forward=_keye_vl2_forward, forced=_keye_vl2_forced,
        forced_in=("logits",), batch=keye_vl2_batch,
        logits_tol=(2e-4, 2e-4), reports_also=_keye_vl2_reports,
        want_terms=_keye_vl2_want_terms,
        term_tol={"": (1e-5, 0.0), "dsa_index_loss": (1e-4, 0.0)},
        terms_also=_keye_vl2_terms,
        gradients=_keye_vl2_gradients,
        grad_tol=(2e-3, 2e-4, 0.0, 0.0),
        expert_shares={"kind": "sparse_moe", "tiny": {}, "each": 4,
                       "norm": _norm},
        presets={"": _keye_vl2_preset},
        plan={"tiny": {}, "tokens": 48, "runs": (("sparse_moe", 3),),
              "rungs": _keye_vl2_rungs},
        hand_counts=_keye_vl2_hand_counts),
    Row("qwen3_next", "Qwen3NextConfig",
        shares={"all-experts": {"experts_held": None},
                "held-4..7": {"experts_held": (4, 4)}},
        # the norms are drawn as zeros, the rule's own as ones: a ``1 + w``
        # applied as ``w`` or twice would go unseen
        moved=(("attn_norm", 0.3), ("op_norm", 0.3), ("mlp_norm", 0.3),
               ("q_norm", 0.3), ("k_norm", 0.3), ("g_norm", 0.3)),
        also_moved=lambda params: {**params, "final_norm": 0.3 * (
            jax.random.normal(jax.random.PRNGKey(77),
                              params["final_norm"].shape))},
        says=_qwen3_next_says, groups={"top": 3, "linear": 16, "full": 16},
        forced=_qwen3_next_forced, forced_in=("logits", "nll"),
        # (5e-5 as Olmo-Hybrid's: three rule layers hand their rounding on)
        logits_tol=(1e-5, 5e-5), term_tol={"": (1e-5, 0.0)},
        reports={"router": 5e-5, "choice": None, "counts": None,
                 "state": 1e-5},
        state=_STATES["qwen3_next"], reports_also=_qwen3_next_reports,
        gradients=_qwen3_next_gradients, gradient_shares=("held-4..7",),
        grad_tol=(1e-4, 5e-5, 1e-2, 1e-6),
        expert_shares={"kind": "full", "tiny": {}, "each": 4,
                       "norm": _zero_centred_norm,
                       "shared": _routed_layers_share,
                       "moved": ("mlp_norm", 0.3)},
        plan={"tiny": {"experts_held": (0, 4)}, "tokens": 64,
              "runs": (("linear", 3), ("full", 1)),
              "rungs": _qwen3_next_rungs}),
    Row("ling3", "Ling3Config",
        shares={"all-experts": {"experts_held": None},
                "held-4..7": {"experts_held": (4, 4)}},
        # the norms are drawn as ones, the biases as zeros: a weight left
        # out, or a bias left out of the choice, would go unseen
        moved=(("attn_norm", 0.3), ("op_norm", 0.3), ("mlp_norm", 0.3),
               ("kv_a_norm", 0.3), ("k_norm", 0.3), ("router_bias", 0.05)),
        says=_ling3_says,
        groups={"top": 3, "kda+dense": 11, "kda+moe": 15, "mla+moe": 15},
        forced=lambda case: {"forced_topk": np.asarray(
            case.program[1]["router"]["chosen"])},
        forced_in=("weighted",),
        # (5e-5 as Olmo-Hybrid's: three rule layers hand their rounding on)
        logits_tol=(1e-5, 5e-5), term_tol={"": (1e-5, 0.0)},
        reports={**_ROUTE, "router": 5e-5, "state": 1e-5},
        state=_STATES["ling3"], reports_also=_ling3_reports,
        gradient_shares=("held-4..7",),
        # (a KDA layer at tiny()'s decays, many at the bound, sums products
        # of factors near exp(75) and exp(-75): 2e-4 of a leaf's largest)
        grad_tol=(1e-3, 2e-4, 1e-2, 1e-6), weighted=2,
        expert_shares={"kind": "kda+moe", "tiny": {}, "each": 4,
                       "norm": _norm, "shared": _routed_layers_share,
                       "bias": 0.05},
        plan={"tiny": {"experts_held": (0, 4)}, "tokens": 64,
              "runs": (("kda+dense", 1), ("kda+moe", 1), ("mla+moe", 1),
                       ("kda+moe", 1)), "rungs": _ling3_rungs},
        hand_counts=_ling3_hand_counts),
    Row("nemotron_h", "Nemotron_hConfig", tiny={},
        tokens=(0, (2, 34), np.int32), ahead=2,
        shares={"whole": {"experts_held": None},
                "held-4..7": {"experts_held": (4, 4)}},
        moved=(), also_moved=_nemotron_h_moved, says=_nemotron_h_says,
        groups={"top": 3, "mamba": 9, "moe": 8, "attention": 5, "mtp": 17},
        forward=_nemotron_h_forward, forced=_nemotron_h_forced,
        logits_tol=(1e-7, 2e-5), reports_also=_nemotron_h_reports,
        want_terms=_nemotron_h_want_terms, terms_also=_nemotron_h_terms,
        gradients=_nemotron_h_gradients, gradient_shares=("whole",),
        grad_l2=2e-5,
        expert_shares={"kind": "moe", "tiny": {}, "each": 4, "norm": _norm,
                       "shared": _relu2_share, "uncut": lambda ref, cfg, p,
                       u: ref.mixture(cfg, p, u), "tol": (1e-7, 2e-5)},
        presets={"published": _nemotron_h_published,
                 "cell": _nemotron_h_cell}),
)}
