"""Laguna (``models/laguna.py``): its row of the conformance suite
(``tests/model_suite.py``: the program at ``tiny()`` against
``benchmark/references/laguna_ref.py``, every expert here and at a chip's
share), and what only Laguna has: a window, a per-head gate, a routed scale
and a partial rotation that are each in the result."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402

ROWS = ("laguna",)
globals().update(model_suite.tests_of(ROWS))


def test_laguna_window_and_gate_are_in_the_result():
    """Leaving out the window mask, the per-head gate or the routed
    scale changes the logits: none of them is a no-op at these sizes."""
    from dataclasses import replace

    from ray_tpu.models import laguna

    cfg = laguna.LagunaConfig.tiny(attn_impl="reference")
    params = laguna.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    base = laguna.forward(cfg, params, tokens)[0]
    for other in (replace(cfg, sliding_window=None),
                  replace(cfg, sliding_window=4),
                  replace(cfg, routed_scale=1.0),
                  replace(cfg, partial_rotary_factor=1.0)):
        assert float(jnp.abs(laguna.forward(other, params, tokens)[0]
                             - base).max()) > 1e-3, other
    # a window of the whole sequence is causal attention
    np.testing.assert_allclose(
        np.asarray(laguna.forward(replace(cfg, sliding_window=32), params,
                                  tokens)[0]),
        np.asarray(laguna.forward(replace(cfg, sliding_window=None), params,
                                  tokens)[0]), rtol=1e-5, atol=1e-5)
