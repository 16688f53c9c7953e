"""What only dots3-note-prev's language model (``models/dots3.py``) has
and no seeded model is needed for (its row of the conformance suite is
``tests/test_dots3.py``): the exact choice with ties, the window
``flash_kv`` kernels at unequal widths, the bias no optimizer owns, a mesh
that walks each chip's own rows, and the step's scopes, span and
counters."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.references import dots3_ref as ref  # noqa: E402
from ray_tpu.models import dots3  # noqa: E402
from ray_tpu.models.dots3 import Dots3Config  # noqa: E402
from ray_tpu.ops import dsa  # noqa: E402
from ray_tpu.ops.attention import (attention_reference,  # noqa: E402
                                   flash_attention, with_shared_key)


@pytest.mark.parametrize("first,ties", [(0, False), (32, False), (0, True),
                                        (16, True), (32, True)])
def test_choose_is_exact_with_ties_to_the_lower_position(first, ties):
    scores = jax.random.normal(jax.random.PRNGKey(first + ties), (16, 48))
    if ties:        # a few distinct values, zeros of both signs among them
        scores = jnp.round(scores * 1.5) / 1.5 * jnp.where(
            jnp.arange(48) % 5 == 0, -0.0, 1.0)
    got = np.asarray(dsa.choose(scores, first, 8))
    want = np.asarray(ref.plain_top_k(scores, first, 8))
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(first + np.arange(16) + 1, 8)).all()


def test_choose_takes_every_key_a_query_sees_when_asked_for_more():
    scores = jax.random.normal(jax.random.PRNGKey(0), (8, 24))
    got = np.asarray(dsa.choose(scores, 16, 64))
    assert (got == np.tril(np.ones((24, 24), bool))[16:]).all()


@pytest.mark.parametrize("window", [5, 100, 129])
def test_window_flash_kernels_at_unequal_widths(window):
    """Forward, dQ, dK, dV and the shared key's gradient of the
    ``flash_kv_*`` kernels under a band, in ``interpret`` mode, at keys of
    48 + 16 shared and values of 24, against ``attention_reference``."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(keys[0], (1, 256, 2, 64))
    k = jax.random.normal(keys[1], (1, 256, 2, 48))
    v = jax.random.normal(keys[2], (1, 256, 2, 24))
    kx = jax.random.normal(keys[3], (1, 256, 16))
    w = jax.random.normal(keys[4], (1, 256, 2, 24))

    def kernel(q, k, v, kx):
        return (flash_attention(q, k, v, use_pallas=True, interpret=True,
                                block_q=64, block_k=64, k_shared=kx,
                                window=window, sm_scale=0.0625) * w).sum()

    def plain(q, k, v, kx):
        return (attention_reference(q, with_shared_key(k, kx), v,
                                    sm_scale=0.0625, window=window)
                * w).sum()

    got = jax.value_and_grad(kernel, (0, 1, 2, 3))(q, k, v, kx)
    want = jax.value_and_grad(plain, (0, 1, 2, 3))(q, k, v, kx)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_bias_moves_by_the_rule_and_no_optimizer_owns_it():
    import optax

    cfg = Dots3Config.tiny()
    params = dots3.init_params(cfg, jax.random.PRNGKey(0))
    owned = dots3.trainable(params)
    assert all("router_bias" not in leaves
               for leaves in owned["layers"].values())
    assert "router_bias" in params["layers"]["sliding_moe"]
    tokens = np.random.default_rng(2).integers(0, 256, (2, 49))
    tx = optax.adamw(1e-2)
    (_, aux), grads = jax.jit(jax.value_and_grad(
        lambda t: dots3.loss_terms(cfg, dots3.with_trainable(params, t),
                                   {"tokens": tokens}), has_aux=True))(owned)
    updates, _ = tx.update(grads, tx.init(owned), owned)
    stepped = dots3.with_trainable(params, optax.apply_updates(owned,
                                                               updates))
    for kind in ("full_moe", "sliding_moe"):
        assert not np.asarray(stepped["layers"][kind]["router_bias"]).any()
        assert float(jnp.abs(stepped["layers"][kind]["router"]
                             - params["layers"][kind]["router"]).max()) > 0
    counts = aux["expert_counts"]
    moved = dots3.update_router_bias(cfg, stepped, counts)
    got = ref.router_biases(cfg, moved)
    want = ref.updated_bias(cfg, np.zeros((3, 16), np.float32),
                            np.asarray(counts))
    np.testing.assert_array_equal(got, want)
    assert float(dots3.router_bias_abs_max(moved)) == pytest.approx(0.001)


def test_a_mesh_walks_each_chips_own_rows():
    """Under a data-parallel mesh the index, the choice and the attention
    over it run each chip's rows of the batch: the loss terms are the
    unsharded program's."""
    from ray_tpu.parallel import MeshSpec, build_mesh

    cfg = Dots3Config.tiny(attn_impl="reference")
    params = dots3.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(4).integers(0, 256, (4, 49))
    mesh = build_mesh(MeshSpec({"dp": 4}), devices=jax.devices()[:4])
    want = jax.jit(lambda p, t: dots3.loss_terms(
        cfg, p, {"tokens": t})[1])(params, tokens)
    got = jax.jit(lambda p, t: dots3.loss_terms(
        cfg, p, {"tokens": t}, mesh=mesh)[1])(params, tokens)
    for name in ("cross_entropy", "dsa_index_loss",
                 "dsa_pairs_chosen_share"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-5)


# train-dots3-1chip's full layers: 16 heads of 128 | 64 and 128, 64 index
# heads of 128, bfloat16
CELL = dsa.Widths(16, 128, 64, 128, 64, 128, jnp.bfloat16)


def test_the_plan_takes_256_queries_a_block_at_the_cells_shapes(monkeypatch):
    """16,384 positions in four tiers on a TPU backend: 256 queries a block
    as asked, each of the four calls' blocks and scratch reckoned as the
    calls are built, the scores' backward the largest at 44 MB of the
    ceiling's 64 MiB; asked for 512, whose scores' backward would hold 88
    MB, the plan steps down to 256."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dsa.walk_plan(16384, 256, 4, CELL) == (256, 4)
    needs = dsa.walk_needs(256, 4096, CELL)
    assert needs == {"dsa_scores_fwd": 18_350_080,
                     "dsa_scores_bwd": 44_302_336,
                     "dsa_attend_fwd": 18_939_904,
                     "dsa_attend_bwd": 33_882_112}
    assert max(needs.values()) <= dsa.VMEM_CEILING == 64 << 20
    assert dsa.walk_needs(512, 4096, CELL)["dsa_scores_bwd"] == 87_818_240
    assert dsa.walk_plan(16384, 512, 4, CELL) == (256, 4)
    assert dsa.walk_plan(16384, 128, 4, CELL) == (128, 4)


@pytest.mark.parametrize("widths,took,fits", [
    (CELL._replace(index_heads=128), 128, True),
    (CELL._replace(heads=32), 256, True),
    (CELL._replace(heads=40), 128, True),
    (CELL._replace(heads=64), 128, False),
    (CELL._replace(dtype=jnp.float32), 256, True)],
    ids=["128-index-heads", "32-heads-fit", "40-heads", "no-block-fits",
         "float32"])
def test_the_plan_steps_the_block_down_at_wider_shapes(widths, took, fits,
                                                      monkeypatch):
    """Made-up wider models at the cell's 16,384 positions: where a call of
    256 queries a block would hold more than the ceiling the walk takes
    128, the smallest block of whole lanes where even that does not fit (64
    heads: the compiler's to refuse), and 256 where all four fit."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dsa.walk_plan(16384, 256, 4, widths) == (took, 4)
    assert (max(dsa.walk_needs(took, 4096, widths).values())
            <= dsa.VMEM_CEILING) == fits
    if took < 256:
        assert max(dsa.walk_needs(256, 4096, widths).values()
                   ) > dsa.VMEM_CEILING


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("seq", [16384, 12288, 16512, 4096, 1000, 384, 49,
                                 48, 7])
def test_the_plan_returns_divisors_alone(seq, backend, monkeypatch):
    """Whatever the sequence, the block asked and the ceiling: the block
    divides the sequence, the tiers divide the blocks, neither is more than
    was asked, and without a kernel form (the CPU; a block off the lanes)
    nothing is reckoned and the largest divisor stands."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for ceiling in (64 << 20, 24 << 20, 1):
        monkeypatch.setattr(dsa, "VMEM_CEILING", ceiling)
        for asked in (16, 128, 256, 512, 4096):
            block, tiers = dsa.walk_plan(seq, asked, 4, CELL)
            assert seq % block == 0 and seq // block % tiers == 0
            assert 1 <= block <= asked and 1 <= tiers <= 4
            plain = dsa.walk_plan(seq, asked, 4)
            assert block <= plain[0]
            if backend == "cpu" or not dsa.walk_needs(
                    plain[0], seq // plain[1], CELL):
                assert (block, tiers) == plain
            if block < plain[0]:
                assert block % dsa.KERNEL_LANES == 0


def test_the_steps_scopes_span_and_counters():
    """The compiled train step carries the index's, the gate's and the
    window's scopes; tracing the op writes ``rtpu.dsa.shapes``; the
    counters' names are ``STEP_COUNTERS``'."""
    import re

    import optax

    from benchmark.cells import train_sparse
    from ray_tpu.train.session import STEP_COUNTERS
    from ray_tpu.util import tracing

    cfg = Dots3Config.tiny(vocab_size=128, attn_impl="reference", remat=True,
                           experts_held=(4, 4))
    params = jax.eval_shape(lambda k: dots3.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, dots3.trainable(params))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 49), jnp.int32)}
    here = tracing.since()
    lowered = jax.jit(train_sparse.make_step(dots3, cfg, tx),
                      donate_argnums=(0, 1)).lower(params, opt, batch)
    spans = [e["args"] for e in here.events()
             if e["name"] == "rtpu.dsa.shapes"]
    assert spans and all(
        (a["index_heads"], a["index_head_dim"], a["topk"], a["positions"],
         a["pairs_scored"], a["pairs_chosen"])
        == (4, 16, 8, 48, 2 * 48 * 49 // 2, 2 * (36 + 40 * 8))
        for a in spans)
    # on the CPU the scores are XLA's form (tests/test_dsa_kernels.py has
    # the kernels')
    assert all((a["block"], a["tiers"], a["scores_form"], a["scores_tile"])
               == (16, 3, "xla", None) for a in spans)
    # XLA's forms hold nothing in VMEM: the guard has nothing to step
    assert all((a["block_asked"], a["vmem_need_bytes"]) == (16, 0)
               for a in spans)
    assert lowered.out_info[3].shape == (3, 16)
    assert set(lowered.out_info[4]) == {
        "cross_entropy", "dsa_index_loss", "dsa_pairs_chosen_share",
        "moe_router_bias_abs_max"}
    assert {"dsa_index_loss", "dsa_pairs_chosen_share",
            "moe_router_bias_abs_max"} <= set(STEP_COUNTERS)
    text = lowered.compile().as_text()
    for scope in ("embed", "mla_q", "mla_kv", "mla_rope", "mla_out",
                  "attn_gate", "dsa_proj", "dsa_scores", "dsa_select",
                  "flash_sparse", "dsa_loss", "flash_window", "mlp",
                  "moe_route", "moe_shared", "moe_bias_update", "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope
