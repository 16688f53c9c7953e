"""Keye-VL-2.0-30B-A3B's language model (``models/keye_vl2.py``): its row of
the conformance suite (``tests/model_suite.py``: the program at ``tiny()``
against ``benchmark/references/keye_vl2_ref.py`` on the program's own
choices of experts and keys, on two interleaved image-text documents whose
positions run in three streams, whole and at experts 4..7; the four shares'
routed parts adding up to the uncut layer), and what only this model has:
the rope's tables from the batch's positions, the positions reaching the
layers, and which term of the loss trains which leaf. The walk under grouped
keys is ``tests/test_dots3_ops.py``'s and ``tests/test_dsa_kernels.py``'s."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402
from ray_tpu.models import keye_vl2  # noqa: E402
from ray_tpu.ops import dsa  # noqa: E402
from ray_tpu.ops.layers import (apply_rope, mrope_frequencies,  # noqa: E402
                                rope_frequencies)
from ray_tpu.util import tracing  # noqa: E402

ROWS = ("keye_vl2",)
globals().update(model_suite.tests_of(ROWS))


_WHOLE = model_suite.cases(ROWS)[:1]


def test_three_stream_tables_against_a_loop_over_positions():
    """Pair ``i`` of a position's row turns by the stream its section
    names: every entry against ``cos(pos[stream(i)] theta ** (-2 i / d))``
    written as a loop; the kept span says the plan."""
    rng = np.random.default_rng(0)
    positions = rng.integers(0, 500, (3, 2, 7))
    here = tracing.since()
    cos, sin = mrope_frequencies(16, jnp.asarray(positions), (2, 3, 3),
                                 theta=10_000.0)
    plan = [e["args"] for e in here.events()
            if e["name"] == "rtpu.mrope.plan"]
    assert plan and plan[0]["streams"] == 3
    assert plan[0]["sections"] == [2, 3, 3]
    assert plan[0]["table_shape"] == [2, 7, 8]
    assert cos.shape == sin.shape == (2, 7, 8)
    for b in range(2):
        for s in range(7):
            for i in range(8):
                stream = 0 if i < 2 else 1 if i < 5 else 2
                angle = np.float32(positions[stream, b, s]) * np.float32(
                    1.0 / 10_000.0 ** (2 * i / 16))
                np.testing.assert_allclose(cos[b, s, i], np.cos(angle),
                                           rtol=0, atol=2e-6)
                np.testing.assert_allclose(sin[b, s, i], np.sin(angle),
                                           rtol=0, atol=2e-6)
    with pytest.raises(ValueError, match="do not split"):
        mrope_frequencies(16, jnp.asarray(positions), (2, 3, 2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_text_positions_give_the_plain_ropes_tables_bit_for_bit(dtype):
    """All three streams ``0 .. s - 1``: the tables are
    ``rope_frequencies``' and a head rotates to the same bits."""
    tokens = jnp.zeros((2, 24), jnp.int32)
    cos3, sin3 = mrope_frequencies(
        16, keye_vl2.text_positions(tokens), (2, 3, 3), 10_000.0, dtype=dtype)
    cos, sin = rope_frequencies(16, 24, 10_000.0, dtype=dtype)
    assert (np.asarray(cos3) == np.asarray(cos)[None]).all()
    assert (np.asarray(sin3) == np.asarray(sin)[None]).all()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16)).astype(dtype)
    assert (np.asarray(apply_rope(x, cos3, sin3))
            == np.asarray(apply_rope(x, cos, sin))).all()


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_the_batchs_positions_reach_every_layer(case):
    """The loss moves when one stream's positions move (the height's on an
    image span alone), and text positions handed in give the loss without
    any."""
    mod, _, cfg, params, tokens = case
    batch = case.batch
    loss = jax.jit(lambda pos: mod.loss_fn(
        cfg, params, {"tokens": tokens, "positions": pos}))
    base = float(loss(batch["positions"]))
    moved = batch["positions"].copy()
    moved[1] += 3 * (batch["mask"][:, :-1] == 0)
    assert abs(float(loss(moved)) - base) > 1e-4
    text = keye_vl2.text_positions(tokens[:, :-1])
    assert float(loss(text)) == float(jax.jit(lambda: mod.loss_fn(
        cfg, params, {"tokens": tokens}))())
    assert abs(float(loss(text)) - base) > 1e-4


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_the_choice_is_the_references_plain_top_k(case):
    """``S_t`` in every layer: the reference's ``lax.top_k`` over the causal
    scores in sequence order, whatever the positions say."""
    _, ref, cfg, params, tokens = case
    _, said = case.program
    got = np.asarray(dsa.unpack_choice(said["dsa"]["choice"], 48))
    want = np.asarray(ref.chosen_keys(cfg, params, tokens[:, :-1],
                                      case.batch["positions"]))
    assert got.shape == want.shape == (3, 2, 48, 48)
    assert (got == want).all()
    causal = np.tril(np.ones((48, 48), bool))
    assert (got[..., :8, :] == causal[:8]).all()
    assert (got.sum(-1)[..., 8:] == 8).all() and not (got & ~causal).any()


@pytest.mark.parametrize("term", ["cross_entropy", "dsa_index_loss"])
@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_each_term_trains_its_own_leaves_alone(case, term):
    """The cross entropy gives the index's leaves exactly zero; ``L_I``
    gives every leaf outside the index exactly zero."""
    mod, _, cfg, params, tokens = case
    if not hasattr(case, "term_grads"):
        case.term_grads = jax.jit(lambda p: {
            name: jax.grad(lambda q: mod.loss_terms(
                cfg, q, {"tokens": tokens, **case.batch})[1][name])(p)
            for name in ("cross_entropy", "dsa_index_loss")})(params)
    grads = case.term_grads[term]
    flat = {f"sparse_moe.{name}": g
            for name, g in grads["layers"]["sparse_moe"].items()}
    flat.update({k: v for k, v in grads.items() if k != "layers"})
    index = {k for k in flat if k.split(".")[-1] in dsa.INDEX_LEAVES}
    assert len(index) == 5
    zero = index if term == "cross_entropy" else set(flat) - index
    for name, g in flat.items():
        assert bool(np.asarray(g).any()) == (name not in zero), name


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_image_targets_carry_no_loss(case):
    """The cross entropy is the mean over the text targets: ids at image
    positions may change as targets without moving it."""
    mod, _, cfg, params, tokens = case
    batch = case.batch
    ce = jax.jit(lambda t: mod.loss_terms(
        cfg, params, {"tokens": t, **batch})[1]["cross_entropy"])
    assert float(batch["mask"].min()) == 0.0
    # the last id is a target alone: move one row's, then the other's, and
    # the loss moves exactly where the mask says text
    for row in range(2):
        other = np.array(tokens)
        other[row, -1] = (other[row, -1] + 7) % 256
        same = float(ce(jnp.asarray(tokens))) == float(ce(jnp.asarray(other)))
        assert same == (batch["mask"][row, -1] == 0)
