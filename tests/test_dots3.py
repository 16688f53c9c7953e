"""dots3-note-prev's language model (``models/dots3.py``): its row of the
conformance suite (``tests/model_suite.py``: the program at ``tiny()``
against ``benchmark/references/dots3_ref.py`` on the program's own choices
of experts and keys, whole and at half the heads with experts 4..11), and
of what only dots3 has, what reads the same parameters: the exact choice
against ``lax.top_k`` and which term of the loss trains which leaf. The
rest of what only dots3 has is ``tests/test_dots3_ops.py``."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tests import model_suite  # noqa: E402
from ray_tpu.models import dots3  # noqa: E402
from ray_tpu.ops import dsa  # noqa: E402

ROWS = ("dots3",)
globals().update(model_suite.tests_of(ROWS))


_WHOLE = model_suite.cases(ROWS)[:1]


@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_the_choice_is_the_references_plain_top_k(case):
    """``S_t`` in both full layers: the reference's ``lax.top_k`` over the
    causal scores, and every causal key where ``t < topk``."""
    _, ref, cfg, params, tokens = case
    _, said = case.program
    got = np.asarray(dsa.unpack_choice(said["dsa"]["choice"], 48))
    want = np.asarray(ref.chosen_keys(cfg, params, tokens[:, :-1]))
    assert got.shape == want.shape == (2, 2, 48, 48)
    assert (got == want).all()
    causal = np.tril(np.ones((48, 48), bool))
    assert (got[..., :8, :] == causal[:8]).all()
    assert (got.sum(-1)[..., 8:] == 8).all() and not (got & ~causal).any()


@pytest.mark.parametrize("term", ["cross_entropy", "dsa_index_loss"])
@pytest.mark.parametrize("case", _WHOLE, indirect=True)
def test_each_term_trains_its_own_leaves_alone(case, term):
    """The cross entropy gives the index's leaves exactly zero; ``L_I``
    gives every leaf outside the index exactly zero (both terms' gradients
    from one compiled function)."""
    _, _, cfg, params, tokens = case
    if not hasattr(case, "term_grads"):
        case.term_grads = jax.jit(lambda p: {
            name: jax.grad(lambda q: dots3.loss_terms(
                cfg, q, {"tokens": tokens})[1][name])(p)
            for name in ("cross_entropy", "dsa_index_loss")})(params)
    grads = case.term_grads[term]
    flat = {f"{kind}.{name}": g for kind, leaves in grads["layers"].items()
            for name, g in leaves.items()}
    flat.update({k: v for k, v in grads.items() if k != "layers"})
    index = {k for k in flat if k.split(".")[-1] in dots3.INDEX_LEAVES}
    assert len(index) == 10
    zero = index if term == "cross_entropy" else set(flat) - index
    for name, g in flat.items():
        assert bool(np.asarray(g).any()) == (
            name not in zero and not name.endswith("router_bias")), name
