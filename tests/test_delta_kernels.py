"""The gated delta rule's kernel path (``ops/delta.rule_kernels``: the
Mosaic calls ``delta_rule_fwd`` and ``delta_rule_bwd``) through the Pallas
interpreter, against the recurrence token by token, against XLA's walk,
with the controls' three faults planted in its seams, and with fewer key
heads than value heads (q and k read at the key heads, their norms taken
inside the calls, a wrong head map refused). A file of its
own beside ``test_ops.py``, which holds the XLA form's cases and is the
longest file a worker takes as it is."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tests.test_ops import _delta_recurrence, _rule, _rule_inputs  # noqa: E402


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

    def spans():
        return [e["args"]["form"] for e in tracing.chrome_events()
                if e["name"] == "rtpu.gdn.rule_plan"]

    n0 = len(spans())
    with jax.default_matmul_precision("highest"):
        (kernels, kernels_grad), (walk, walk_grad) = forms(None), forms(
            object())                       # any mesh keeps XLA's walk
        got, want = kernels(*args), walk(*args)
        got_g, want_g = kernels_grad(*args), walk_grad(*args)
    assert spans()[n0:] == ["pallas", "xla_walk", "pallas", "xla_walk"]
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


# ---- fewer key heads than value heads: q and k read where they lie


def _grouped_inputs(ratio, key_heads, s, seed=0):
    """``_rule_inputs`` with q and k at ``key_heads`` heads under ``ratio``
    times as many value heads."""
    q, k, v, g, beta = _rule_inputs(s=s, H=ratio * key_heads, seed=seed)
    return q[:, :, :key_heads], k[:, :, :key_heads], v, g, beta


def _joined_recurrence(q, k, *rest):
    """The recurrence with value head ``i`` reading key head ``i // ratio``:
    q and k copied to the value heads before it (their gradients sum
    back over a key head's value heads)."""
    ratio = rest[0].shape[2] // q.shape[2]
    return _delta_recurrence(jnp.repeat(q, ratio, axis=2),
                             jnp.repeat(k, ratio, axis=2), *rest)


@pytest.mark.parametrize("ratio,key_heads,heads,block,chunks,base,chunk,s", [
    (1, 3, 3, 3, 2, 4, 8, 32), (2, 4, 4, 4, 2, 8, 8, 40),
    (4, 2, 4, 4, 1, 4, 8, 24), (4, 1, 8, 4, 8, 4, 16, 30),
    (2, 3, 6, 6, 2, 4, 8, 27)],
    ids=["ratio-1", "ratio-2-two-blocks", "ratio-4-two-blocks",
         "ratio-4-ragged", "ratio-2-ragged-three-key-heads"])
def test_rule_kernels_at_grouped_heads_match_the_recurrence(
        ratio, key_heads, heads, block, chunks, base, chunk, s,
        rule_kernels):
    """``ratio`` value heads a key head through the kernels (a batch of
    two; one block of heads and several; sequences that are not whole
    steps, nor whole chunks) against the recurrence on q and k copied to
    the value heads, float32 at 1e-5 (gradients 1e-4): outputs, the last
    state and the gradients of q and k at the key heads (a key head's
    value heads summed inside the call), v, g and beta; the plan says the
    heads are joined by the index map and a block holds whole key heads."""
    from ray_tpu.ops import delta

    rule_kernels(heads, chunks, base)
    args = _grouped_inputs(ratio, key_heads, s)
    plan = delta.rule_plan(*args[2].shape[:3], args[0].shape[-1],
                           args[2].shape[-1], chunk, key_heads=key_heads)
    assert (plan["form"], plan["heads_a_block"]) == ("pallas", block)
    assert plan["joined"] == (None if ratio == 1 else "index_map")
    with jax.default_matmul_precision("highest"):
        o, S = jax.jit(lambda *a: _rule(*a, chunk))(*args)
        want_o, want_S = jax.jit(_joined_recurrence)(*args)
        got = jax.jit(jax.grad(_rule_scalar(lambda *a: _rule(*a, chunk)),
                               argnums=(0, 1, 2, 3, 4)))(*args)
        want = jax.jit(jax.grad(_rule_scalar(_joined_recurrence),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S),
                               rtol=1e-5, atol=1e-5)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_the_norms_inside_the_calls_are_l2_norm(rule_kernels, ratio=2):
    """q and k handed to the calls as the taps leave them, positions last
    and not normed (``norm=``: the calls take the L2 norms in VMEM,
    forward and backward, q's times ``K^-0.5``), against the same calls on
    q and k that ``l2_norm`` normed in XLA: outputs, the last state and
    every gradient (q's and k's through the norm) agree to float32's
    rounding, on a sequence padded with zero rows (whose norm is 0)."""
    from ray_tpu.ops import delta

    rule_kernels(4, 2, 4)
    q, k, v, g, beta = _grouped_inputs(ratio, 2, 28, seed=5)
    b, s, H, V = v.shape
    K = q.shape[-1]
    plan = delta.rule_plan(b, s, H, K, V, 8, key_heads=2)

    def last(a):
        return jnp.swapaxes(a.reshape(b, s, -1), 1, 2)

    def inside(q, k, v, g, beta):
        o, S = delta.rule_kernels(last(q), last(k), last(v), g, beta, plan,
                                  norm=(delta.QK_NORM_EPS, K ** -0.5))
        return jnp.swapaxes(o, 1, 2).reshape(b, s, H, V), S

    def outside(q, k, v, g, beta):
        return delta.gated_delta_rule(
            delta.l2_norm(q, delta.QK_NORM_EPS, K ** -0.5),
            delta.l2_norm(k, delta.QK_NORM_EPS), v, g, beta, chunk=8)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(f)(q, k, v, g, beta) + jax.jit(jax.grad(
            _rule_scalar(f), argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
            for f in (inside, outside))
    for name, a, w in zip(("o", "S", "dq", "dk", "dv", "dg", "dbeta"), got,
                          want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(w).max()), err_msg=name)


def test_a_wrong_head_map_in_the_kernels_is_another_function(
        rule_kernels, monkeypatch):
    """The kernels' map from a block's value heads to its key heads
    (``_key_head``, looked up while they trace) with key head ``i mod 2``
    planted for ``i // 2``: outputs and every gradient leave the
    recurrence's by more than 10% where the honest map agrees at 1e-5;
    afterwards the module is what it was."""
    from ray_tpu.ops import delta

    rule_kernels(4, 2, 4)
    args = _grouped_inputs(2, 2, 32, seed=2)

    def readings():
        # (new functions each time: a jitted one would keep its first trace)
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: _rule(*a, 8))(*args) + jax.jit(
                jax.grad(_rule_scalar(lambda *a: _rule(*a, 8)),
                         argnums=(0, 1, 2, 3, 4)))(*args)

    def gaps(got, want):
        return [float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                for g, w in zip(got, want)]

    with jax.default_matmul_precision("highest"):
        want = jax.jit(_joined_recurrence)(*args) + jax.jit(jax.grad(
            _rule_scalar(_joined_recurrence), argnums=(0, 1, 2, 3, 4)))(*args)
    honest_map = delta._key_head
    assert max(gaps(readings(), want)) < 1e-4
    with monkeypatch.context() as planted:
        planted.setattr(delta, "_key_head", lambda h, ratio: h % 2)
        wrong = readings()
    assert delta._key_head is honest_map
    assert min(gaps(wrong, want)) > 0.1, gaps(wrong, want)
