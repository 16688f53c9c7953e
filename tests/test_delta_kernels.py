"""The gated delta rule's kernel path (``ops/delta.rule_kernels``: the
Mosaic calls ``delta_rule_fwd`` and ``delta_rule_bwd``) through the Pallas
interpreter, against the recurrence token by token, against XLA's walk,
and with the controls' three faults planted in its seams. Fewer key heads
than value heads: ``test_delta_kernels_grouped.py``."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests.test_delta_ops import _delta_recurrence, _rule, _rule_inputs  # noqa: E402


@functools.lru_cache(maxsize=None)
def _recurrence_programs():
    """The reference and its gradient, jitted once for every case of a
    shape."""
    return (jax.jit(_delta_recurrence),
            jax.jit(jax.grad(_rule_scalar(_delta_recurrence),
                             argnums=(0, 1, 2, 3, 4))))


@pytest.fixture
def rule_kernels(monkeypatch):
    """``gated_delta_rule`` takes its kernel path, the Pallas interpreter
    in Mosaic's place: ``rule_kernels(heads, chunks, base)`` sets the
    kernels' three constants."""
    from ray_tpu.ops import delta

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(delta, "rule_kernels", functools.partial(
        delta.rule_kernels, interpret=True))

    def constants(heads, chunks, base, lanes=None):
        monkeypatch.setattr(delta, "KERNEL_HEADS", heads)
        monkeypatch.setattr(delta, "KERNEL_CHUNKS", chunks)
        monkeypatch.setattr(delta, "KERNEL_BASE", base)
        monkeypatch.setattr(delta, "KERNEL_LANES", lanes or base)

    return constants


def _rule_scalar(fn):
    def f(*a):
        o, S = fn(*a)
        return (jnp.sin(o) * o).sum() + (S * S).sum()
    return f


@pytest.mark.parametrize(
    "heads,chunks,base,chunk,shape,beta_from,kept", [
        (2, 2, 8, 8, dict(s=32, H=4), 0.0, 2),
        (2, 2, 8, 8, dict(s=32, H=4), 1.0, 2),
        (1, 1, 2, 8, dict(s=24, H=3), 0.0, 3),
        (2, 8, 4, 16, dict(s=32, H=2), 1.0, 1),
        (3, 2, 4, 8, dict(s=30, H=3), 0.0, 2),
        (2, 2, 16, 64, dict(s=192, H=2, b=1), 0.0, 2)],
    ids=["two-heads-two-chunks", "beta-above-1", "one-head-one-chunk",
         "whole-sequence-a-call", "ragged-three-heads", "chunk-64-base-16"])
def test_rule_kernels_match_the_recurrence(heads, chunks, base, chunk, shape,
                                           beta_from, kept, rule_kernels):
    """The kernel path (forward and backward, a batch of two) against the
    recurrence one position after another, float32 at 1e-5 (gradients
    1e-4): outputs, the last state and every input's gradient, over the
    heads a block, the chunks a grid step, the states the backward keeps,
    the rows of ``T`` substituted before the joins, ``beta`` over (0, 2)
    and above 1 alone, a sequence that is not whole steps (nor whole
    chunks) and the published chunk of 64."""
    from ray_tpu.ops import delta

    rule_kernels(heads, chunks, base)
    args = _rule_inputs(**dict(dict(b=2), **shape), beta_from=beta_from)
    plan = delta.rule_plan(*args[2].shape[:3], args[0].shape[-1],
                           args[2].shape[-1], chunk)
    assert (plan["form"], plan["heads_a_block"], plan["states_kept"]) == (
        "pallas", heads, kept)
    with jax.default_matmul_precision("highest"):
        o, S = jax.jit(lambda *a: _rule(*a, chunk))(*args)
        reference, its_gradient = _recurrence_programs()
        want_o, want_S = reference(*args)
        got = jax.jit(jax.grad(_rule_scalar(lambda *a: _rule(*a, chunk)),
                               argnums=(0, 1, 2, 3, 4)))(*args)
        want = its_gradient(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_rule_kernels_match_the_walk(rule_kernels):
    """Both forms on the same inputs: outputs, last state and gradients
    agree to float32's rounding, and each call's kept span names its
    form."""
    from ray_tpu.ops import delta
    from ray_tpu.util import tracing

    rule_kernels(2, 2, 4)
    args = _rule_inputs(s=40, H=4, seed=3)

    def forms(mesh):
        def rule(*a):
            q, k, v, g, beta = a
            return delta.gated_delta_rule(
                delta.l2_norm(q, scale=8 ** -0.5), delta.l2_norm(k), v, g,
                beta, chunk=8, mesh=mesh)
        return (jax.jit(rule), jax.jit(jax.grad(
            _rule_scalar(rule), argnums=(0, 1, 2, 3, 4))))

    here = tracing.since()
    with jax.default_matmul_precision("highest"):
        (kernels, kernels_grad), (walk, walk_grad) = forms(None), forms(
            object())                       # any mesh keeps XLA's walk
        got, want = kernels(*args), walk(*args)
        got_g, want_g = kernels_grad(*args), walk_grad(*args)
    assert [e["args"]["form"] for e in here.events()
            if e["name"] == "rtpu.gdn.rule_plan"] == [
        "pallas", "xla_walk", "pallas", "xla_walk"]
    for g, w in zip(got + got_g, want + want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def test_kernel_inverse_is_exact_on_repeated_keys():
    """64 equal keys at ``beta = 2`` through the kernels' inverse, two
    chunks side by side on the lanes, the second's keys alternating in
    sign: the tiles by substitution and the two joins give the inverses' 1 and 2 to float32's last bits."""
    from jax.experimental import pallas as pl

    from ray_tpu.ops import delta

    n = 64
    sign = jnp.where(jnp.arange(n) % 2 == 0, 1.0, -1.0)
    A = jnp.stack([jnp.tril(jnp.full((n, n), 2.0, jnp.float32), -1),
                   jnp.tril(2.0 * sign[:, None] * sign[None, :], -1)])

    def kernel(a_ref, t_ref):
        for i, T in enumerate(delta._kernel_inverse([a_ref[0], a_ref[1]])):
            t_ref[i] = T

    T = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(A.shape, jnp.float32),
        interpret=True)(A)
    for i in range(2):
        np.testing.assert_allclose(
            np.asarray(T[i] @ (jnp.eye(n) + A[i])), np.eye(n), atol=1e-5)
        assert float(jnp.abs(T[i]).max()) == 2.0
    np.testing.assert_array_equal(
        np.asarray(T[0]), np.asarray(delta._unit_lower_inverse(A[:1])[0]))


def _kernel_fault(fault):
    """What to set in ``ops/delta.py`` to plant ``fault`` in the kernel
    path's three seams (the state a chunk starts from, the inverse, the
    running sums and decays), as ``benchmark/tests/delta_limits.py``
    plants them in XLA's walk."""
    from ray_tpu.ops import delta

    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    class Rounding:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def cumsum(self, *a, **kw):
            return rounded(jnp.cumsum(*a, **kw))

        def exp(self, *a, **kw):
            return rounded(jnp.exp(*a, **kw))

    return {
        "without_carry": dict(_kernel_state=jnp.zeros_like),
        "with_first_order_inverse": dict(_kernel_inverse=lambda A: [
            jnp.eye(a.shape[-1], dtype=a.dtype) - a for a in A]),
        "with_bfloat16_decays": dict(jnp=Rounding(), _kernel_state=rounded),
    }[fault]


@pytest.mark.parametrize("fault,least,most,inside_first_chunk", [
    ("without_carry", 0.05, None, True),
    ("with_first_order_inverse", 0.05, None, False),
    ("with_bfloat16_decays", 1e-3, 0.1, False)])
def test_rule_kernels_with_a_planted_fault_are_another_function(
        fault, least, most, inside_first_chunk, rule_kernels, monkeypatch):
    """The three faults of ``delta_limits.py`` that live inside the rule,
    planted in the kernel path's seams (``_kernel_state``,
    ``_kernel_inverse``, ``_kernel_sums`` and ``_kernel_decays`` through
    the module's ``jnp``), which the kernels look up while they trace: a
    state that is not carried and ``I - A`` for the inverse leave the
    honest output by more than 5% (the first agrees inside the first
    chunk), running sums, decays and state rounded to bfloat16 by more
    than 1e-3; afterwards the module is what it was."""
    from ray_tpu.ops import delta

    rule_kernels(2, 2, 4)
    args = _rule_inputs(H=4)
    o, S = _rule(*args, 8)
    honest = {n: getattr(delta, n) for n in (
        "_kernel_state", "_kernel_inverse", "_kernel_sums", "_kernel_decays",
        "jnp")}
    with monkeypatch.context() as planted:
        for name, value in _kernel_fault(fault).items():
            planted.setattr(delta, name, value)
        cut, cut_S = _rule(*args, 8)
    assert all(getattr(delta, n) is v_ for n, v_ in honest.items())

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    if inside_first_chunk:
        np.testing.assert_allclose(np.asarray(cut[:, :8]),
                                   np.asarray(o[:, :8]), rtol=1e-6, atol=1e-6)
        cut, o = cut[:, 8:], o[:, 8:]
    assert rel(cut, o) > least and rel(cut_S, S) > least
    if most is not None:
        assert rel(cut, o) < most and rel(cut_S, S) < most
    np.testing.assert_array_equal(np.asarray(_rule(*args, 8)[1]),
                                  np.asarray(S))
