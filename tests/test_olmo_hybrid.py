"""Olmo-Hybrid (``models/olmo_hybrid.py``): its row of the conformance
suite (``tests/model_suite.py``: the program at ``tiny()`` against
``benchmark/references/olmo_hybrid_ref.py``, with the blocked head's tests:
the training loss against ``token_nll``, the first adamw step, the
variants, fsdp), and what only Olmo-Hybrid has: OLMo 2's order of norm and
sum with a q-k norm over whole vectors."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402

ROWS = ("olmo_hybrid",)
globals().update(model_suite.tests_of(ROWS))

from ray_tpu.models import llama  # noqa: E402


@pytest.mark.parametrize("case", model_suite.cases(ROWS), indirect=True)
def test_attention_block_in_olmo_order_with_a_whole_vector_qk_norm(
        case):
    """A layer with ``attn_post_norm`` and no ``attn_norm``: the block's
    input is not normed, its output is, before the sum; q and k are normed
    over their whole vectors and not rotated: against
    ``olmo_hybrid_ref.attention`` on one layer's weights."""
    _, ref_mod, cfg, params, _ = case
    p = {k: v[0] for k, v in params["layers"]["full"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    sz = ref_mod._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got = llama.attention_block(cfg, x, p, None, None)
        want = jnp.stack([row + ref_mod._rms_norm(
            ref_mod.attention(row, p, sz), p["attn_post_norm"],
            cfg.rms_norm_eps) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
