"""Mamba-2 (``ops/ssm.py``): the chunked scan against the recurrence, its
plan, and the mixer against Granite's reference, in XLA's forms and the
kernels' (``interpret`` mode; the scan kernels alone:
``test_scan_kernels.py``)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _scan_inputs(b=2, s=32, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, s, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, H)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, s, G, N)),
            jax.random.normal(k[4], (b, s, G, N)))


def _recurrence(x, dt, A, B, C):
    """``granite_ref.recurrence`` (token by token) a row of the batch at a
    time."""
    from benchmark.references import granite_ref

    out = [granite_ref.recurrence(x[i], dt[i], A, B[i], C[i])
           for i in range(x.shape[0])]
    return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])


@pytest.mark.parametrize("chunk,walk", [(4, 8), (8, 2), (32, 1)],
                         ids=["chunk4", "chunk8-walk2", "whole-sequence"])
def test_ssd_scan_matches_the_recurrence(chunk, walk, monkeypatch):
    """The chunked scan against the recurrence one position after another
    (float32, 1e-5): outputs, the last state and every input's gradient,
    at three chunk sizes, one of them the whole sequence: the result does
    not depend on the chunk nor on how many a step of the walk takes (its
    bytes, ``WALK_BYTES``, are the one way to set that)."""
    from ray_tpu.ops import ssm
    from ray_tpu.ops.ssm import ssd_scan

    args = _scan_inputs()
    b, s, H, P = args[0].shape
    monkeypatch.setattr(ssm, "WALK_BYTES", walk * b * H * chunk * chunk * 4)
    assert ssm.scan_plan(b, s, H, P, 16, 2, chunk)["walk"] == walk

    def scalar(fn):
        def f(*a):
            y, S = fn(*a)
            return (jnp.sin(y) * y).sum() + (S * S).sum()
        return f

    with jax.default_matmul_precision("highest"):
        y, S = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))(*args)
        want_y, want_S = _recurrence(*args)
        got = jax.jit(jax.grad(scalar(lambda *a: ssd_scan(
            *a, chunk=chunk)), argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(scalar(_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_ssd_scan_pads_a_ragged_sequence_and_keeps_rows_apart():
    """A sequence that is not whole chunks is padded with ``dt = 0``, which
    moves neither output nor state; a row of the batch never sees
    another's state."""
    from ray_tpu.ops.ssm import ssd_scan

    x, dt, A, B, C = _scan_inputs(s=30)
    with jax.default_matmul_precision("highest"):
        y, S = ssd_scan(x, dt, A, B, C, chunk=8)
        want_y, want_S = _recurrence(x, dt, A, B, C)
        alone, _ = ssd_scan(x[1:], dt[1:], A, B[1:], C[1:], chunk=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(y[1:]), np.asarray(alone))


def test_ssd_scan_without_its_carried_state_is_another_function(monkeypatch):
    """The fault ``benchmark/tests/scan_limits.py`` plants (one chunk a
    step of the walk, each started from zeros) agrees with the scan inside
    the first chunk alone; the program has no option for it."""
    from ray_tpu.ops import ssm

    args = _scan_inputs()
    y, _ = ssm.ssd_scan(*args, chunk=8)
    honest = ssm._walk_step
    monkeypatch.setattr(ssm, "WALK_BYTES", 0)
    monkeypatch.setattr(ssm, "_walk_step",
                        lambda S, *a: honest(jnp.zeros_like(S), *a))
    cut, _ = ssm.ssd_scan(*args, chunk=8)
    np.testing.assert_allclose(np.asarray(cut[:, :8]), np.asarray(y[:, :8]),
                               rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(cut[:, 8:] - y[:, 8:]).max()) > 0.1


def test_ssd_scan_with_bfloat16_decays_is_another_function():
    """The other fault ``scan_limits.py`` plants in ``ops/ssm.py``: running
    sums, decays and the carried state rounded to bfloat16's eight bits.
    Output and last state leave the honest scan's by a bfloat16 rounding
    and more, a hundred times the 1e-5 the honest scan keeps to the
    recurrence; afterwards the module is what it was."""
    from benchmark.tests import scan_limits
    from ray_tpu.ops import ssm

    args = _scan_inputs()
    y, S = ssm.ssd_scan(*args, chunk=8)
    honest = ssm._walk_step
    cut_y, cut_S = scan_limits.with_bfloat16_decays(
        lambda: ssm.ssd_scan(*args, chunk=8))
    assert ssm.jnp is jnp and ssm._walk_step is honest

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    assert 1e-3 < rel(cut_y, y) < 0.1
    assert 1e-3 < rel(cut_S, S) < 0.1
    again, _ = ssm.ssd_scan(*args, chunk=8)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(y))


@pytest.mark.parametrize("form", ["xla_walk", "pallas"])
def test_scan_plan_walks_within_its_bytes(form, monkeypatch):
    """At the published shapes a step of XLA's walk takes 8 chunks, 128 MB
    of decay matrices where all 128 chunks at once would be 2.1 GB; a
    short sequence is one chunk; the walk always divides the chunks. The
    kernels (a TPU backend, no mesh, a chunk of whole lane tiles) put no
    decay matrix in HBM: ``KERNEL_CHUNKS`` chunks a grid step, the largest
    divisor of a group's heads within ``KERNEL_HEADS`` a block, a state
    kept a step; under a mesh and for a chunk of 30 the plan is the
    walk's."""
    from ray_tpu.ops import ssm

    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = ssm.scan_plan(1, 32768, 64, 64, 128, 1, 256)
    assert plan["form"] == form
    assert plan["decay_bytes_all_chunks"] == 2 ** 31
    small = ssm.scan_plan(2, 30, 4, 8, 16, 2, 256)
    assert (small["form"], small["chunk"], small["chunks"],
            small["walk"]) == ("xla_walk", 30, 1, 1)
    if form == "pallas":
        n = ssm.KERNEL_CHUNKS
        assert (plan["chunks"], plan["walk"], plan["chunks_a_call"],
                plan["steps"], plan["states_kept"]) == (
                    128, None, n, 128 // n, 128 // n)
        assert plan["heads_a_block"] == ssm.KERNEL_HEADS
        assert plan["decay_bytes_in_hbm"] == 0
        # the kept and the last states; dt, the sums, their gradients and
        # the skip's; dB and dC
        assert plan["float32_bytes_in_hbm"] == (
            (128 // n + 1) * 64 * 64 * 128 * 4
            + (5 * 64 + 2 * 128) * 32768 * 4)
        # two groups of 6 heads: a block lies within a group
        monkeypatch.setattr(ssm, "KERNEL_HEADS", 4)
        assert ssm.scan_plan(1, 1024, 12, 64, 128, 2, 256)[
            "heads_a_block"] == 3
        # a short sequence is one grid step of all its chunks
        short = ssm.scan_plan(1, 1000, 64, 64, 128, 1, 128)
        assert (short["chunks"], short["chunks_a_call"], short["steps"]) == (
            8, min(8, n), -(-8 // n))
        sharded = ssm.scan_plan(1, 32768, 64, 64, 128, 1, 256, object())
        assert (sharded["form"], sharded["walk"]) == ("xla_walk", 8)
        return
    assert (plan["chunks"], plan["walk"], plan["steps"]) == (128, 8, 16)
    assert (plan["chunks_a_call"], plan["states_kept"],
            plan["heads_a_block"]) == (8, 16, None)
    assert plan["decay_bytes_in_hbm"] == plan["float32_bytes_in_hbm"] \
        == 8 * 64 * 256 * 256 * 4 <= ssm.WALK_BYTES
    # one chunk's matrices past the budget: still one chunk a step
    assert ssm.scan_plan(64, 32768, 64, 64, 128, 1, 256)["walk"] == 1
    # 12 chunks, room for 8: the largest divisor within it
    odd = ssm.scan_plan(1, 3072, 64, 64, 128, 1, 256)
    assert (odd["chunks"], odd["walk"], odd["steps"]) == (12, 6, 2)


@pytest.mark.parametrize("form,scan", [
    ("xla_taps", "xla_walk"), ("pallas", "xla_walk"), ("pallas", "pallas")])
def test_mamba2_mixer_matches_the_reference(form, scan, monkeypatch):
    """The mixer (in-projection, taps with bias and silu, scan, skip,
    gated norm, out-projection) against ``granite_ref.mamba_mixer``:
    output, the last state and every leaf's gradient, float32 at 1e-5;
    once as the CPU runs it, once through the taps' kernels with XLA's
    walk after them (a TPU with a chunk that is not whole lane tiles) and
    once through the taps' and the scan's kernels, the skip ``D x`` inside
    them, as a TPU runs the cell (the interpreter in Mosaic's place)."""
    import functools

    from benchmark.references import granite_ref
    from ray_tpu.models import granite
    from ray_tpu.ops import conv, ssm
    from ray_tpu.ops.ssm import mamba2_mixer
    from ray_tpu.util import tracing

    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ssm, "taps_silu", functools.partial(
            conv.taps_silu, interpret=True))
    if scan == "pallas":
        monkeypatch.setattr(ssm, "scan_kernels", functools.partial(
            ssm.scan_kernels, interpret=True))
        monkeypatch.setattr(ssm, "KERNEL_LANES", 8)
        monkeypatch.setattr(ssm, "KERNEL_CHUNKS", 2)
    here = tracing.since()
    cfg = granite.GraniteConfig.tiny()
    p = {k: v[0] for k, v in granite.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["mamba"].items()}
    p["m_conv_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                               p["m_conv_bias"].shape)
    p["D"] = p["D"] + 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                              p["D"].shape)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.hidden_size))
    kw = dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
              state=cfg.ssm_state, groups=cfg.ssm_groups,
              chunk=cfg.ssm_chunk, eps=cfg.rms_norm_eps)
    sz = granite_ref._sizes(cfg)
    with jax.default_matmul_precision("highest"):
        out, last = jax.jit(lambda u, p: mamba2_mixer(u, p, **kw))(u, p)
        want, S = granite_ref.mixer(cfg, p, u[0])
        got_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            mamba2_mixer(u, p, **kw)[0]).sum(), argnums=(0, 1)))(p, u)
        want_g = jax.jit(jax.grad(lambda p, u: jnp.square(
            granite_ref.mamba_mixer(u[0], p, sz)[0]).sum(),
            argnums=(0, 1)))(p, u)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(S),
                               rtol=1e-5, atol=1e-5)
    names = set(p) - {"op_norm", "mlp_norm", "w_gate", "w_up", "w_down"}
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree_util.tree_leaves(want_g)):
        if path[0].idx == 0 and path[1].key not in names:
            continue                      # the layer's other leaves: zeros
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=str(path))
    assert _forms(here, "rtpu.ssm.conv_plan") == {form}
    assert _forms(here, "rtpu.ssm.scan_plan") == {scan}


def _forms(here, span):
    """The forms that the spans of this name, written since ``here``
    (a ``tracing.since()``), say were taken."""
    return {e["args"]["form"] for e in here.events() if e["name"] == span}


def test_mamba2_mixer_is_float32_inside_and_names_its_scopes():
    """bf16 activations in and out, the state float32; the optimized
    program names the five scopes under ``ssm``, forward and backward."""
    from ray_tpu.models import granite
    from ray_tpu.ops.ssm import mamba2_mixer

    cfg = granite.GraniteConfig.tiny()
    p = {k: v[0].astype(jnp.bfloat16) for k, v in granite.init_params(
        cfg, jax.random.PRNGKey(0))["layers"]["mamba"].items()}
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.hidden_size),
                          jnp.bfloat16)
    kw = dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
              state=cfg.ssm_state, chunk=cfg.ssm_chunk)
    out, last = mamba2_mixer(u, p, **kw)
    assert out.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert last.shape == (1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    text = jax.jit(jax.grad(lambda p, u: jnp.square(mamba2_mixer(
        u, p, **kw)[0].astype(jnp.float32)).sum(),
        argnums=(0, 1))).lower(p, u).as_text(
        debug_info=True)
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out"):
        assert f"jvp(ssm)/{scope}" in text, scope
        assert f"transpose(jvp(ssm))/{scope}" in text, scope


def test_mamba2_mixer_norms_a_group_at_a_time():
    """``norm_groups``: each group's channels divided by the root of their
    own mean square; one group is the function it was."""
    from ray_tpu.ops import ssm

    k = jax.random.split(jax.random.PRNGKey(0), 8)
    H, P, N, G, hid = 4, 8, 16, 2, 32
    d, conv = H * P, H * P + 2 * G * N
    p = {"m_in": jax.random.normal(k[0], (hid, d + conv + H)) / 6,
         "m_conv": jax.random.normal(k[1], (conv, 4)) / 2,
         "m_conv_bias": jnp.zeros((conv,)),
         "dt_bias": jnp.zeros((H,)), "A_log": jnp.zeros((H,)),
         "D": jnp.ones((H,)),
         "m_norm": 1.0 + 0.1 * jax.random.normal(k[2], (d,)),
         "m_out": jnp.eye(d, hid)}
    u = jax.random.normal(k[3], (1, 16, hid))
    sizes = dict(heads=H, head_dim=P, state=N, groups=G, chunk=8)
    one, _ = ssm.mamba2_mixer(u, p, **sizes)
    same, _ = ssm.mamba2_mixer(u, p, norm_groups=1, **sizes)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(same))
    two, _ = ssm.mamba2_mixer(u, p, norm_groups=2, **sizes)
    # undo the weights: each half of the channels has a unit mean square
    normed = np.asarray(two[0]) / np.asarray(p["m_norm"])[:hid]
    # (eps 1e-5 beside a mean square that may be small)
    np.testing.assert_allclose(np.square(normed[:, :16]).mean(-1), 1.0,
                               rtol=2e-2)
    np.testing.assert_allclose(np.square(normed[:, 16:]).mean(-1), 1.0,
                               rtol=2e-2)
    assert abs(np.square(np.asarray(one[0]) / np.asarray(p["m_norm"])[:hid]
                         )[:, :16].mean(-1) - 1.0).max() > 5e-2
