"""The selective scan's kernel path (``ops/ssm.scan_kernels``: the Mosaic
calls ``ssd_scan_fwd`` and ``ssd_scan_bwd``) through the Pallas
interpreter, against the recurrence token by token, against XLA's walk,
and with the controls' two faults planted in its seams. A file of its own
beside ``test_ops.py``, which holds the XLA form's cases and is the longest
file a worker takes as it is."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests.test_ssm_ops import _recurrence, _scan_inputs  # noqa: E402


@pytest.fixture
def scan_kernels(monkeypatch):
    """``ssd_scan`` takes its kernel path, the Pallas interpreter in
    Mosaic's place: ``scan_kernels(heads, chunks, lanes)`` sets the
    kernels' two constants and the positions a chunk has to be whole tiles
    of (128 on the chip)."""
    from ray_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssm, "scan_kernels", functools.partial(
        ssm.scan_kernels, interpret=True))

    def constants(heads, chunks, lanes=8):
        monkeypatch.setattr(ssm, "KERNEL_HEADS", heads)
        monkeypatch.setattr(ssm, "KERNEL_CHUNKS", chunks)
        monkeypatch.setattr(ssm, "KERNEL_LANES", lanes)

    return constants


def _scalar(fn):
    def f(*a):
        y, S = fn(*a)
        return (jnp.sin(y) * y).sum() + (S * S).sum()
    return f


@pytest.mark.parametrize(
    "heads,chunks,lanes,chunk,shape,block,kept,tol", [
        (2, 2, 8, 8, dict(s=32), 2, 2, 1e-5),
        (1, 1, 8, 8, dict(s=24, H=3, G=1), 1, 3, 1e-5),
        (2, 8, 8, 16, dict(s=32), 2, 1, 1e-5),
        (4, 2, 8, 8, dict(s=30, H=3, G=1), 3, 2, 1e-5),
        (2, 2, 8, 8, dict(s=32, H=8), 2, 2, 1e-5),
        (8, 8, 8, 256, dict(s=16), 2, 1, 1e-5),
        (2, 2, 128, 128, dict(s=384, b=1, H=2, G=1), 2, 2, 1e-5),
        (2, 2, 128, 256, dict(s=600, b=1, H=2, G=1), 2, 2, 2e-4),
        (16, 2, 128, 128, dict(s=256, b=1, H=16, G=8), 2, 1, 1e-5)],
    ids=["two-heads-two-chunks", "one-head-one-chunk",
         "whole-sequence-a-call", "ragged-three-heads",
         "two-blocks-a-group", "shorter-than-a-chunk", "chunk-128",
         "chunk-256-ragged", "chunk-128-eight-groups"])
def test_scan_kernels_match_the_recurrence(heads, chunks, lanes, chunk, shape,
                                           block, kept, tol, scan_kernels):
    """The kernel path (forward and backward) against the recurrence one
    position after another, float32 at 1e-5 (gradients 1e-4): outputs, the
    last state and the gradient of every operand (x, dt, A, B, C; the last
    state's cotangent is not zero), over the heads a block (all of a
    group's, a part of them, two blocks that add to one dB and dC), the
    chunks a grid step, the states the backward keeps, two groups and one,
    a batch of two, a sequence that is not whole steps (nor whole chunks),
    one shorter than a chunk, and the published chunk of 256 and one of
    128 at the chip's own lane tiles (running sums of 256 float32 terms:
    2e-4, where XLA's walk at that chunk reads 8.8e-5 on these inputs), the
    last at ``nemotron_h``'s eight groups of B and C (two heads a group)."""
    from ray_tpu.ops import ssm

    scan_kernels(heads, chunks, lanes)
    args = _scan_inputs(**shape)
    b, s, H, P = args[0].shape
    G, N = args[3].shape[2:]
    plan = ssm.scan_plan(b, s, H, P, N, G, chunk)
    assert (plan["form"], plan["heads_a_block"], plan["states_kept"]) == (
        "pallas", block, kept)
    assert plan["decay_bytes_in_hbm"] == 0 and plan["walk"] is None

    def scan(*a):
        return ssm.ssd_scan(*a, chunk=chunk)

    with jax.default_matmul_precision("highest"):
        y, S = jax.jit(scan)(*args)
        want_y, want_S = _recurrence(*args)
        got = jax.jit(jax.grad(_scalar(scan), argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(_scalar(_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=tol, atol=tol)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=10 * tol,
            atol=tol * float(jnp.abs(w).max()), err_msg=name)


def test_scan_kernels_match_the_walk_and_keep_rows_apart(scan_kernels):
    """Both forms on the same inputs, a ragged batch of two: outputs, last
    state and gradients agree to float32's rounding, each call's kept span
    names its form, and a row of the batch never sees another's state."""
    from ray_tpu.ops import ssm
    from ray_tpu.util import tracing

    scan_kernels(2, 2)
    args = _scan_inputs(s=30, seed=3)

    def forms(mesh):
        def scan(*a):
            return ssm.ssd_scan(*a, chunk=8, mesh=mesh)
        return (jax.jit(scan), jax.jit(jax.grad(
            _scalar(scan), argnums=(0, 1, 2, 3, 4))))

    here = tracing.since()
    with jax.default_matmul_precision("highest"):
        (kernels, kernels_grad), (walk, walk_grad) = forms(None), forms(
            object())                       # any mesh keeps XLA's walk
        got, want = kernels(*args), walk(*args)
        got_g, want_g = kernels_grad(*args), walk_grad(*args)
        x, dt, A, B, C = args
        alone, _ = ssm.ssd_scan(x[1:], dt[1:], A, B[1:], C[1:], chunk=8)
    assert [e["args"]["form"] for e in here.events()
            if e["name"] == "rtpu.ssm.scan_plan"][:4] == [
        "pallas", "xla_walk", "pallas", "xla_walk"]
    for g, w in zip(got + got_g, want + want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(w).max()))
    np.testing.assert_array_equal(np.asarray(got[0][1:]), np.asarray(alone))


def test_scan_kernels_add_the_mixers_skip(scan_kernels):
    """``scan_kernels(skip=D)`` on operands that lie positions last, as
    the mixer hands them over: ``y + D x`` with the skip added while ``x``
    is in VMEM, against the same call without it and the skip in XLA,
    values and the gradients of x, dt, A, B, C and D (a ragged batch of
    two, two groups); without a ``skip`` the call is the scan alone."""
    from ray_tpu.ops import ssm

    scan_kernels(2, 2)
    x, dt, A, B, C = _scan_inputs(s=30)
    b, s, H, P = x.shape
    D = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(7), (H,))
    plan = ssm.scan_plan(b, s, H, P, B.shape[3], B.shape[2], 8)

    def last(a):
        return jnp.swapaxes(a.reshape(b, s, -1), 1, 2)

    def kernels(x, dt, A, B, C, D, inside):
        y, S = ssm.scan_kernels(last(x), last(dt), A, last(B), last(C), plan,
                                skip=D if inside else None)
        y = jnp.swapaxes(y, 1, 2).reshape(x.shape)
        return (y if inside else y + D[:, None] * x), S

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(lambda *a: kernels(*a, inside))(
            x, dt, A, B, C, D) for inside in (True, False))
        got_g, want_g = (jax.jit(jax.grad(_scalar(
            lambda *a: kernels(*a, inside)), argnums=tuple(range(6))))(
                x, dt, A, B, C, D) for inside in (True, False))
        alone, _ = ssm.ssd_scan(x, dt, A, B, C, chunk=8)
    np.testing.assert_allclose(
        np.asarray(want[0] - D[:, None] * x), np.asarray(alone), rtol=1e-6,
        atol=1e-6)
    for name, g, w in zip(("y", "S", "x", "dt", "A", "B", "C", "D"),
                          got + got_g, want + want_g):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def _kernel_fault(fault):
    """What to set in ``ops/ssm.py`` to plant ``fault`` in the kernel
    path's seams (the states a chunk starts from, the running sums, the
    decays), as ``benchmark/tests/scan_limits.py`` plants them in XLA's
    walk."""
    from ray_tpu.ops import ssm

    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    class Rounding:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def exp(self, *a, **kw):
            return rounded(jnp.exp(*a, **kw))

    honest = ssm._kernel_sums
    return {"without_carry": dict(_kernel_state=jnp.zeros_like),
            "with_bfloat16_decays": dict(
                jnp=Rounding(), _kernel_state=rounded,
                _kernel_sums=lambda da: rounded(honest(da)))}[fault]


@pytest.mark.parametrize("fault", ["without_carry", "with_bfloat16_decays"])
def test_scan_kernels_with_a_planted_fault_are_another_function(
        fault, scan_kernels, monkeypatch):
    """The two faults of ``scan_limits.py`` that live inside the scan,
    planted in the kernel path's seams (``_kernel_state``, ``_kernel_sums``,
    and ``_kernel_decays`` through the module's ``jnp.exp``), which the
    kernels look up while they trace, by the margins ``tests/test_ops.py`` holds
    the walk's to: states that are not carried agree with the scan inside
    the first chunk alone and leave it by more than 0.1 after; running
    sums, decays and states rounded to bfloat16 leave output and last state
    by a bfloat16 rounding and more (1e-3 to 0.1); afterwards the module
    is what it was."""
    from ray_tpu.ops import ssm

    scan_kernels(2, 2)
    args = _scan_inputs()
    y, S = ssm.ssd_scan(*args, chunk=8)
    honest = {n: getattr(ssm, n) for n in (
        "_kernel_state", "_kernel_sums", "_kernel_decays", "jnp")}
    with monkeypatch.context() as planted:
        for name, value in _kernel_fault(fault).items():
            planted.setattr(ssm, name, value)
        cut, cut_S = ssm.ssd_scan(*args, chunk=8)
    assert all(getattr(ssm, n) is v for n, v in honest.items())

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    if fault == "without_carry":
        np.testing.assert_allclose(np.asarray(cut[:, :8]),
                                   np.asarray(y[:, :8]), rtol=1e-6, atol=1e-6)
        assert float(jnp.abs(cut[:, 8:] - y[:, 8:]).max()) > 0.1
        assert rel(cut_S, S) > 0.05
    else:
        assert 1e-3 < rel(cut, y) < 0.1
        assert 1e-3 < rel(cut_S, S) < 0.1
    again, _ = ssm.ssd_scan(*args, chunk=8)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(y))


@pytest.mark.parametrize("why", ["cpu", "mesh", "chunk"])
def test_kernel_takes_refuses_and_the_walk_runs(why, scan_kernels,
                                                monkeypatch):
    """``_kernel_takes``' three refusals give XLA's walk: the CPU backend,
    a mesh (a Mosaic call is whole to the partitioner), a chunk that is
    not whole lane tiles (12 positions of 8); the kernels are never
    reached and the result is the recurrence's."""
    from ray_tpu.ops import ssm

    scan_kernels(2, 2)

    def never(*a, **kw):
        raise AssertionError("the kernels were reached")

    monkeypatch.setattr(ssm, "scan_kernels", never)
    args = _scan_inputs(s=24)
    chunk, mesh = 8, None
    if why == "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    elif why == "mesh":
        mesh = jax.sharding.Mesh(jax.devices()[:1], ("x",))
    else:
        chunk = 12
    assert not ssm._kernel_takes(chunk, mesh)
    assert ssm._kernel_takes(8, None) == (why != "cpu")
    plan = ssm.scan_plan(2, 24, 4, 8, 16, 2, chunk, mesh)
    assert (plan["form"], plan["heads_a_block"]) == ("xla_walk", None)
    assert plan["walk"] == plan["chunks_a_call"] == plan["chunks"]
    with jax.default_matmul_precision("highest"):
        y, S = ssm.ssd_scan(*args, chunk=chunk, mesh=mesh)
        want_y, want_S = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)


def test_the_mixer_under_the_kernels_norms_each_of_eight_groups(scan_kernels,
                                                                monkeypatch):
    """``mamba2_mixer`` on its kernel path at 16 heads in 8 groups with the
    gated norm a group at a time (``nemotron_h``'s layer), against the
    reference's mixer, token by token and a group's norm written out."""
    from benchmark.references import nemotron_h_ref
    from ray_tpu.models import nemotron_h
    from ray_tpu.ops import ssm

    scan_kernels(2, 2)
    monkeypatch.setattr(ssm, "taps_silu", functools.partial(
        ssm.taps_silu, interpret=True))
    cfg = nemotron_h.Nemotron_hConfig.tiny(ssm_heads=16, ssm_head_dim=8,
                                           ssm_groups=8)
    p = {name: nemotron_h.stack.draw(cfg, key, leaf.shape, leaf.start)
         for key, (name, leaf) in zip(
             jax.random.split(jax.random.PRNGKey(0), 9),
             nemotron_h.LAYER_KINDS["mamba"][0].leaves(cfg).items())}
    p["m_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                p["m_norm"].shape)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64))
    assert ssm.scan_plan(1, 32, 16, 8, 16, 8, 8)["form"] == "pallas"
    got, S = ssm.mamba2_mixer(u, p, heads=16, head_dim=8, state=16, groups=8,
                              chunk=8, norm_groups=8)
    want, want_S = nemotron_h_ref.mixer(cfg, p, u[0])
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    np.testing.assert_allclose(S[0], want_S, atol=2e-5)
