"""Granite 4.0-H (``models/granite.py``): its row of the conformance
suite (``tests/model_suite.py``: the program at ``tiny()`` against
``benchmark/references/granite_ref.py``, with the blocked head's tests: the
training loss against ``token_nll``, the first adamw step, the variants,
fsdp), and what only Granite has: attention without rotation at a stated
scale."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402

ROWS = ("granite",)
globals().update(model_suite.tests_of(ROWS))

from ray_tpu.models import llama  # noqa: E402


@pytest.mark.parametrize("case", model_suite.cases(ROWS), indirect=True)
def test_attention_block_without_rope_at_a_stated_scale(case):
    """``cos=None`` leaves q and k unrotated, ``sm_scale`` replaces the
    head size's scale and ``resid_scale`` weighs the block's output:
    against ``granite_ref.attention`` on one layer's weights."""
    granite, granite_ref, cfg, params, _ = case
    p = {k: v[0] for k, v in params["layers"]["attention"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.hidden_size))
    sz = granite_ref._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        got = llama.attention_block(cfg, x, p, None, None,
                                    sm_scale=cfg.attention_multiplier,
                                    resid_scale=cfg.residual_multiplier)
        want = jnp.stack([row + cfg.residual_multiplier
                          * granite_ref.attention(granite_ref._rms_norm(
                              row, p["attn_norm"], cfg.rms_norm_eps), p, sz)
                          for row in x])
        plain = llama.attention_block(cfg, x, p, None, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    # the scale is in the result: head_dim ** -0.5 is 1/4 here, not 1/16
    assert float(jnp.abs(plain - got).max()) > 1e-3
    with pytest.raises(ValueError, match="stated scale"):
        from dataclasses import replace
        llama.attention_block(replace(cfg, attn_impl="ring"), x, p, None,
                              None, sm_scale=0.1)
