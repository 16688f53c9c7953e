"""Ling-3.0-flash-VL's language model (``models/ling3.py``): its row of the
conformance suite (``tests/model_suite.py``: the program at ``tiny()``
against ``benchmark/references/ling3_ref.py``, every expert here and at
experts 4..7; the parts four chips give add up to the uncut layer; the
remat plan's kinds; the cell's hand counts), and what only Ling-3.0 has:
the KDA mixer against the token-by-token recurrence, the latent part with
no query latent against the reference, the held list that skips a layer,
and the published preset."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests import model_suite  # noqa: E402
from benchmark.references import ling3_ref as ref  # noqa: E402
from ray_tpu.models import ling3  # noqa: E402
from ray_tpu.models.ling3 import Ling3Config  # noqa: E402
from ray_tpu.ops import delta, mla  # noqa: E402
from ray_tpu.ops.layers import Ctx  # noqa: E402

ROWS = ("ling3",)
globals().update(model_suite.tests_of(ROWS))


def _layer(kind, at=0, key=4):
    cfg = Ling3Config.tiny()
    params = ling3.init_params(cfg, jax.random.PRNGKey(key))
    return cfg, {k: v[at] for k, v in params["layers"][kind].items()}


@pytest.mark.parametrize("chunk", [4, 16])
def test_the_kda_mixer_is_the_recurrence(chunk):
    """The mixer's chunked walk of the per-channel rule, its bounded gate,
    its L2 norms and its head-wise gated norm against the reference's
    recurrence a position at a time; the plan says the decay is a
    channel's and which form ran."""
    cfg, p = _layer("kda+moe", 1)
    p["k_norm"] = p["k_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(5), p["k_norm"].shape)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 64))
    with jax.default_matmul_precision("highest"):
        out, said = delta.kda_mixer(u, p, heads=4, key_dim=16, value_dim=16,
                                    chunk=chunk)
    want, want_S = ref.mixer(cfg, p, u[0])
    np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(said["state"][0], want_S, rtol=1e-5,
                               atol=1e-5)
    assert -5.0 < float(said["log_decay_min"]) < 0.0
    plan = delta.rule_plan(1, 40, 4, 16, 16, chunk, decay="channel")
    assert (plan["decay"], plan["form"]) == ("channel", "xla_walk")
    assert delta.rule_plan(1, 40, 4, 16, 16, chunk)["decay"] == "head"
    # a head whose decay is the mean of its channels is another model
    honest = delta._channel_gates
    try:
        def mean(f, b_, p_, lower):
            g, beta = honest(f, b_, p_, lower)
            return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta

        delta._channel_gates = mean
        with jax.default_matmul_precision("highest"):
            wrong, _ = delta.kda_mixer(u, p, heads=4, key_dim=16,
                                       value_dim=16, chunk=chunk)
    finally:
        delta._channel_gates = honest
    assert float(jnp.abs(wrong[0] - want).max()) > 1e-2


def test_the_latent_part_without_a_query_latent_is_the_references():
    """``q_lora_rank`` None: one ``wq`` from the layer's normed input, no
    latent norm, the head-wise gate; the part's body against the
    reference's latent layer, and the span says so."""
    from ray_tpu.util import tracing

    cfg, p = _layer("mla+moe")
    part = ling3.LAYER_KINDS["mla+moe"][0]
    assert set(part.leaves(cfg)) == {"attn_norm", "wq", "wkv_a", "kv_a_norm",
                                     "wkv_b", "wo", "wg"}
    tokens = np.zeros((1, 40), np.int32)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 40, 64))
    cfg = Ling3Config.tiny(attn_impl="reference")
    here = tracing.since()
    with jax.default_matmul_precision("highest"):
        out, _ = part.body(cfg, x, p, Ctx(
            None, {part.once: part.once(cfg, tokens)}))
    from ray_tpu.ops.layers import rms_norm
    want = ref.latent_layer(cfg, p, rms_norm(x, p["attn_norm"], 1e-6)[0])
    np.testing.assert_allclose((out - x)[0], want, rtol=1e-5, atol=1e-5)
    said = [e["args"] for e in here.events()
            if e["name"] == "rtpu.mla.shapes"]
    assert said and said[0]["q_lora_rank"] is None and said[0]["gate"] is True
    # DeepSeek-V2's own part still has its query latent
    from ray_tpu.models import deepseek_v2
    dcfg = deepseek_v2.DeepseekV2Config.tiny()
    assert "wq_a" in deepseek_v2.LAYER_KINDS["mla_moe"][0].leaves(dcfg)
    assert mla.sizes(cfg).q_rank is None and mla.sizes(dcfg).q_rank == 32


def test_a_held_list_skips_a_layer_and_the_preset_counts():
    """The cell's cut: layers 0 and 2-7 of 42 (the leading dense layers
    counted once, then one whole period), and the published stack: 35 KDA
    layers to 7 latent ones, the first two dense."""
    cut = Ling3Config.ling_3_flash(layer_ids=(0, 2, 3, 4, 5, 6, 7),
                                   vocab_size=19_648, experts_held=(0, 8))
    assert cut.num_layers == 7 and cut.pattern == (
        "kda+dense", "kda+moe", "kda+moe", "kda+moe", "mla+moe", "kda+moe",
        "kda+moe")
    shapes = jax.eval_shape(lambda k: ling3.init_params(cut, k),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    # the issue's arithmetic at the floor of 8 experts held (the first
    # permitted retreat) and the six routers' biases
    assert count == 822_033_344 + 6 * 512
    kda = {k: int(np.prod(v.shape[1:])) for k, v in
           shapes["layers"]["kda+dense"].items() if k.startswith(
               ("k_", "op_"))}
    assert sum(kda.values()) == 52_648_608
    mla_ = {k: int(np.prod(v.shape[1:])) for k, v in
            shapes["layers"]["mla+moe"].items() if k.startswith(
                ("w", "attn_", "kv_"))}
    assert sum(mla_.values()) == 31_968_256
    whole = Ling3Config.ling_3_flash()
    assert whole.pattern.count("kda+moe") == 33
    assert whole.pattern.count("mla+moe") == 7
    assert whole.pattern[:2] == ("kda+dense", "kda+dense")
    assert [i for i, k in enumerate(whole.pattern) if k.startswith("mla")] \
        == [5, 11, 17, 23, 29, 35, 41]
    with pytest.raises(ValueError, match="layer_ids names"):
        Ling3Config.tiny(layer_ids=(0, 1))
