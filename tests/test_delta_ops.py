"""The gated delta rule (``ops/delta.py``): the chunked rule against the
recurrence token by token, with faults planted, its plan, and the mixer
against Olmo-Hybrid's reference, in XLA's forms and the kernels'
(``interpret`` mode; the rule's kernels alone: ``test_delta_kernels.py``)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests.test_ssm_ops import _forms  # noqa: E402


def _rule_inputs(b=2, s=32, H=3, K=8, V=16, beta_from=0.0, seed=0):
    """q, k, v as the taps leave them, ``g <= 0`` and ``beta`` in
    ``(beta_from, 2)``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, s, H, K)),
            jax.random.normal(k[1], (b, s, H, K)),
            jax.random.normal(k[2], (b, s, H, V)),
            -jax.nn.softplus(jax.random.normal(k[3], (b, s, H)) - 1.0),
            beta_from + (2.0 - beta_from) * jax.nn.sigmoid(
                2.0 * jax.random.normal(k[4], (b, s, H))))


def _rule(q, k, v, g, beta, chunk):
    """``gated_delta_rule`` on q and k normed as the mixer norms them."""
    from ray_tpu.ops.delta import gated_delta_rule
    from ray_tpu.ops.layers import l2_norm

    return gated_delta_rule(l2_norm(q, scale=q.shape[-1] ** -0.5),
                            l2_norm(k), v, g, beta, chunk=chunk)


def _delta_recurrence(q, k, v, g, beta):
    """``olmo_hybrid_ref.recurrence`` (token by token, norming q and k
    itself) a row of the batch at a time."""
    from benchmark.references import olmo_hybrid_ref

    out = [olmo_hybrid_ref.recurrence(q[i], k[i], v[i], g[i], beta[i])
           for i in range(q.shape[0])]
    return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])


@pytest.mark.parametrize("beta_from", [0.0, 1.0],
                         ids=["beta-0-to-2", "beta-above-1"])
@pytest.mark.parametrize("chunk,walk,base", [
    (4, 8, 16), (8, 2, 2), (32, 1, 16), (32, 1, 4)],
    ids=["chunk4", "chunk8-walk2-base2", "whole-sequence", "whole-base4"])
def test_gated_delta_rule_matches_the_recurrence(chunk, walk, base,
                                                 beta_from, monkeypatch):
    """The chunked rule against the recurrence one position after another
    (float32, 1e-5): outputs, the last state and every input's gradient,
    at three chunk sizes, one of them the whole sequence, with ``beta``
    over (0, 2) and above 1 alone (eigenvalues below zero): the result
    depends neither on the chunk, nor on how many a step of the walk takes
    (``WALK_BYTES``), nor on where the triangular inverse stops
    substituting and joins blocks (``INVERSE_BASE``)."""
    from ray_tpu.ops import delta

    args = _rule_inputs(beta_from=beta_from)
    b, s, H, K = args[0].shape
    V = args[2].shape[-1]
    monkeypatch.setattr(delta, "INVERSE_BASE", base)
    monkeypatch.setattr(delta, "WALK_BYTES",
                        walk * b * H * 4 * (4 * chunk * chunk + V * K))
    assert delta.rule_plan(b, s, H, K, V, chunk)["walk"] == walk

    def scalar(fn):
        def f(*a):
            o, S = fn(*a)
            return (jnp.sin(o) * o).sum() + (S * S).sum()
        return f

    with jax.default_matmul_precision("highest"):
        o, S = jax.jit(lambda *a: _rule(*a, chunk))(*args)
        want_o, want_S = _delta_recurrence(*args)
        got = jax.jit(jax.grad(scalar(lambda *a: _rule(*a, chunk)),
                               argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(scalar(_delta_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_gated_delta_rule_pads_a_ragged_sequence_and_keeps_rows_apart():
    """A sequence that is not whole chunks is padded with ``g = 0`` and
    ``beta = 0``, which move neither output nor state; a row of the batch
    never sees another's state."""
    args = _rule_inputs(s=30)
    with jax.default_matmul_precision("highest"):
        o, S = _rule(*args, 8)
        want_o, want_S = _delta_recurrence(*args)
        alone, _ = _rule(*(a[1:] for a in args), 8)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    # (a batch of two and of one sum in another order: float32's last bit)
    np.testing.assert_allclose(np.asarray(o[1:]), np.asarray(alone),
                               rtol=1e-6, atol=1e-6)


def test_unit_lower_inverse_is_exact_on_repeated_keys():
    """64 equal keys at ``beta = 2``: ``A`` is all twos under the diagonal,
    its powers pass 1e17 and a sum of them cancels to nothing in float32;
    forward substitution and the joins give the inverse, whose entries
    are 1 and 2 in turn, to float32's last bits."""
    from ray_tpu.ops.delta import _unit_lower_inverse

    n = 64
    A = jnp.tril(jnp.full((n, n), 2.0, jnp.float32), -1)
    T = _unit_lower_inverse(A[None])[0]
    np.testing.assert_allclose(
        np.asarray(T @ (jnp.eye(n) + A)), np.eye(n), atol=1e-5)
    assert float(jnp.abs(T).max()) == 2.0


@pytest.mark.parametrize("fault,inside_first_chunk", [
    ("without_carry", True), ("with_half_beta", False),
    ("with_first_order_inverse", False), ("without_qk_norm", False)])
def test_gated_delta_rule_with_a_planted_fault_is_another_function(
        fault, inside_first_chunk):
    """The faults ``benchmark/tests/delta_limits.py`` plants in
    ``ops/delta.py`` (the state not carried, ``beta`` without its two, ``I
    - A`` for the inverse, q and k not normed) leave the honest rule's
    output by far more than a rounding (the first agrees inside the first
    chunk alone); the program has no option for any of them, and
    afterwards the module is what it was."""
    from benchmark.tests import delta_limits
    from ray_tpu.ops import delta

    p = {"g_A_log": jnp.zeros((3,)), "g_dt_bias": jnp.zeros((3,))}
    q, k, v, a, b_ = _rule_inputs()

    def rule():
        g, beta = delta._gates(a, b_, p)
        return delta.gated_delta_rule(
            delta.l2_norm(q, scale=8 ** -0.5), delta.l2_norm(k), v, g, beta,
            chunk=8)[0]

    honest = {n: getattr(delta, n) for n in (
        "_walk_step", "_gates", "_unit_lower_inverse", "l2_norm",
        "WALK_BYTES")}
    o = rule()
    cut = getattr(delta_limits, fault)(rule)
    assert all(getattr(delta, n) is v_ for n, v_ in honest.items())
    if inside_first_chunk:
        np.testing.assert_allclose(np.asarray(cut[:, :8]),
                                   np.asarray(o[:, :8]), rtol=1e-6, atol=1e-6)
        cut, o = cut[:, 8:], o[:, 8:]
    assert float(jnp.linalg.norm(cut - o) / jnp.linalg.norm(o)) > 0.05
    np.testing.assert_array_equal(np.asarray(rule()[:, 8:]),
                                  np.asarray(o[:, -24:]))


def test_gated_delta_rule_with_bfloat16_decays_is_another_function():
    """The other fault ``delta_limits.py`` plants: running sums, decays and
    the carried state rounded to bfloat16's eight bits. Output and last
    state leave the honest rule's by a bfloat16 rounding and more, a
    hundred times the 1e-5 the honest rule keeps to the recurrence."""
    from benchmark.tests import delta_limits
    from ray_tpu.ops import delta

    args = _rule_inputs()
    o, S = _rule(*args, 8)
    honest = delta._walk_step
    cut_o, cut_S = delta_limits.with_bfloat16_decays(lambda: _rule(*args, 8))
    assert delta.jnp is jnp and delta._walk_step is honest

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert 1e-3 < rel(cut_o, o) < 0.1
    assert 1e-3 < rel(cut_S, S) < 0.1


def test_rule_plan_walks_within_its_bytes():
    """At the published shapes (30 heads, keys of 96, values of 192) a
    step of the walk takes 8 chunks of 64, 33 MB of float32 pair
    matrices and carried states where all 512 chunks at once would be 2.1
    GB; a short sequence is one chunk; the walk always divides the
    chunks."""
    from ray_tpu.ops import delta

    plan = delta.rule_plan(1, 32768, 30, 96, 192, 64)
    one = 30 * 4 * (4 * 64 * 64 + 192 * 96)
    assert (plan["chunks"], plan["walk"], plan["steps"]) == (512, 8, 64)
    assert plan["float32_bytes_in_hbm"] == 8 * one <= delta.WALK_BYTES
    assert plan["float32_bytes_all_chunks"] == 512 * one
    # the CPU runs XLA's walk, and so does any call under a mesh
    assert plan["form"] == "xla_walk" and plan["heads_a_block"] is None
    assert plan["chunks_a_call"] == 8 and plan["states_kept"] == 64
    small = delta.rule_plan(2, 30, 4, 8, 16, 64)
    assert (small["chunk"], small["chunks"], small["walk"]) == (30, 1, 1)
    # one chunk's matrices past the budget: still one chunk a step
    assert delta.rule_plan(64, 32768, 30, 96, 192, 64)["walk"] == 1
    # 12 chunks, room for 9: the largest divisor within it
    odd = delta.rule_plan(1, 768, 30, 96, 192, 64)
    assert (odd["chunks"], odd["walk"], odd["steps"]) == (12, 6, 2)


def test_rule_plan_of_the_kernels_keeps_states_and_no_pair_matrix(
        monkeypatch):
    """On a TPU backend without a mesh the published shapes run as the
    kernels: 15 heads a block, 8 chunks a grid step, the state before each
    of the 64 steps kept for the backward (141 MB of the 149 MB of float32
    the form puts in HBM, where a step of XLA's walk put 33 MB of pair
    matrices and all chunks at once 2.1 GB); under a mesh, on the CPU, for
    a chunk that is not whole tiles or a sequence shorter than a chunk,
    XLA's walk."""
    from ray_tpu.ops import delta

    shapes = (1, 32768, 30, 96, 192, 64)
    assert delta.rule_plan(*shapes)["form"] == "xla_walk"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = delta.rule_plan(*shapes)
    assert (plan["form"], plan["heads_a_block"], plan["chunks_a_call"],
            plan["steps"], plan["states_kept"], plan["walk"]) == (
        "pallas", 15, 8, 64, 64, None)
    state = 30 * 192 * 96 * 4
    assert plan["float32_bytes_in_hbm"] == 65 * state + 3 * 30 * 32768 * 4
    assert plan["float32_bytes_all_chunks"] == 512 * 30 * 4 * (
        4 * 64 * 64 + 192 * 96)
    assert delta.rule_plan(*shapes, mesh=object())["form"] == "xla_walk"
    # 22 heads: the largest divisor within 16; 3 chunks: all in one step,
    # padded to 4 (a step's positions are whole registers of 128 lanes)
    odd = delta.rule_plan(2, 192, 22, 96, 192, 64)
    assert (odd["form"], odd["heads_a_block"], odd["chunks_a_call"],
            odd["steps"], odd["operands"]) == (
        "pallas", 11, 4, 1, "positions_last")
    # 16 key heads under 32: a step takes whole key heads with the two
    # value heads of each, 8 and 16 within 16; the kernels read q and k at
    # the key heads, the walk (under a mesh) reads copies
    grouped = delta.rule_plan(1, 32768, 32, 128, 128, 64, key_heads=16)
    assert (grouped["form"], grouped["heads_a_block"], grouped["joined"]
            ) == ("pallas", 16, "index_map")
    walked = delta.rule_plan(1, 32768, 32, 128, 128, 64, mesh=object(),
                             key_heads=16)
    assert (walked["form"], walked["joined"], walked["operands"]) == (
        "xla_walk", "repeat", None)
    assert plan["joined"] is None
    # a head that is not whole sublane tiles: the walk
    assert delta.rule_plan(1, 256, 4, 12, 16, 64)["form"] == "xla_walk"
    # 9 chunks: two steps of 8, the second padded
    assert delta.rule_plan(1, 520, 30, 96, 192, 64)["steps"] == 2
    for seq, chunk in ((30, 64), (256, 24), (256, 48)):
        assert delta.rule_plan(1, seq, 30, 96, 192, chunk)["form"] == (
            "xla_walk"), (seq, chunk)


def test_l2_norm_and_gated_rms_norm_match_their_definitions():
    from ray_tpu.ops.layers import gated_rms_norm, l2_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    gate = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, 16))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    xs = np.asarray(x)
    np.testing.assert_allclose(
        np.asarray(l2_norm(x, scale=0.25)),
        0.25 * xs / np.sqrt((xs ** 2).sum(-1, keepdims=True) + 1e-6),
        rtol=1e-5, atol=1e-6)
    want = (xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-6)
            * np.asarray(w) * np.asarray(jax.nn.silu(gate)))
    np.testing.assert_allclose(np.asarray(gated_rms_norm(x, gate, w)), want,
                               rtol=1e-5, atol=1e-6)
    assert l2_norm(x.astype(jnp.bfloat16)).dtype == jnp.bfloat16
    assert gated_rms_norm(x.astype(jnp.bfloat16), gate, w
                          ).dtype == jnp.bfloat16


@pytest.mark.parametrize("form,rule", [
    ("xla_taps", "xla_walk"), ("pallas", "xla_walk"), ("pallas", "pallas")],
    ids=["xla_taps", "pallas", "pallas-rule"])
def test_gated_delta_mixer_matches_the_reference(form, rule, monkeypatch):
    """The mixer (in-projection, taps and silu, L2 norms, the rule, the
    gated norm of each head, out-projection) against
    ``olmo_hybrid_ref.delta_mixer``: output, the last state and every
    leaf's gradient, float32 at 1e-5; once as the CPU runs it, once
    through the taps' kernels with their zero bias, and once with the rule
    through its kernels too, as a TPU does (the interpreter in Mosaic's
    place; the tiny chunk of 8 is whole tiles of 4 rows there)."""
    import functools

    from benchmark.references import olmo_hybrid_ref
    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops import conv, delta, ssm
    from ray_tpu.ops.delta import gated_delta_mixer
    from ray_tpu.util import tracing

    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ssm, "taps_silu", functools.partial(
            conv.taps_silu, interpret=True))
    if rule == "pallas":
        monkeypatch.setattr(delta, "KERNEL_BASE", 4)
        monkeypatch.setattr(delta, "rule_kernels", functools.partial(
            delta.rule_kernels, interpret=True))
    here = tracing.since()
    cfg = olmo_hybrid.OlmoHybridConfig.tiny()
    p = {k: v[0] for k, v in olmo_hybrid.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["linear"].items()}
    p["g_norm"] = p["g_norm"] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(4), p["g_norm"].shape)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.hidden_size))
    kw = dict(heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
              value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk,
              eps=cfg.rms_norm_eps)
    sz = olmo_hybrid_ref._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        out, last = jax.jit(lambda u, p: gated_delta_mixer(u, p, **kw))(u, p)
        want, S = olmo_hybrid_ref.mixer(cfg, p, u[0])
        got_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            gated_delta_mixer(u, p, **kw)[0]).sum(), argnums=(0, 1)))(p, u)
        want_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            olmo_hybrid_ref.delta_mixer(u[0], p, sz)[0]).sum(),
            argnums=(0, 1)))(p, u)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(S),
                               rtol=1e-5, atol=1e-5)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree_util.tree_leaves(want_g)):
        if path[0].idx == 0 and not path[1].key.startswith("g_"):
            continue                      # the layer's other leaves: zeros
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=str(path))
    assert _forms(here, "rtpu.gdn.conv_plan") == {form}
    assert _forms(here, "rtpu.gdn.rule_plan") == {rule}


@pytest.mark.parametrize("rule", ["xla_walk", "pallas"])
def test_gated_delta_mixer_is_float32_inside_and_names_its_scopes(
        rule, monkeypatch):
    """bf16 activations in and out, the state float32; in both forms of
    the rule every running sum and every decay is formed in float32
    (each ``cumsum`` and ``exp`` of the traced program, the kernels'
    bodies among them); the optimized program names the five scopes under
    ``gdn``, forward and backward."""
    import functools
    import re

    from ray_tpu.models import olmo_hybrid
    from ray_tpu.ops import conv, delta, ssm
    from ray_tpu.ops.delta import gated_delta_mixer
    from ray_tpu.util import tracing

    if rule == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ssm, "taps_silu", functools.partial(
            conv.taps_silu, interpret=True))
        monkeypatch.setattr(delta, "KERNEL_BASE", 4)
        monkeypatch.setattr(delta, "rule_kernels", functools.partial(
            delta.rule_kernels, interpret=True))
    cfg = olmo_hybrid.OlmoHybridConfig.tiny()
    p = {k: v[0].astype(jnp.bfloat16) for k, v in olmo_hybrid.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["linear"].items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.hidden_size),
                          jnp.bfloat16)
    kw = dict(heads=cfg.linear_heads, key_dim=cfg.linear_key_dim,
              value_dim=cfg.linear_value_dim, chunk=cfg.rule_chunk)
    here = tracing.since()
    out, last = gated_delta_mixer(u, p, **kw)
    assert out.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert last.shape == (1, cfg.linear_heads, cfg.linear_value_dim,
                          cfg.linear_key_dim)
    assert _forms(here, "rtpu.gdn.rule_plan") == {rule}

    def loss(p, u):
        return jnp.square(gated_delta_mixer(u, p, **kw)[0].astype(
            jnp.float32)).sum()

    formed = re.findall(r"(\w+)\[[^\]]*\] = (?:exp|cumsum)\b",
                        str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
                            p, u)))
    assert len(formed) >= 5 and set(formed) == {"f32"}, formed
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, u).as_text(
        debug_info=True)
    for scope in ("gdn_in", "gdn_conv", "gdn_rule", "gdn_norm", "gdn_out"):
        assert f"jvp(gdn)/{scope}" in text, scope
        assert f"transpose(jvp(gdn))/{scope}" in text, scope
