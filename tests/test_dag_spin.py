"""Adaptive spin channels + on-device DAG channels.

Spin-mode channels busy-poll the seqno atomic for a budget before
parking on the condvar; DeviceChannel edges hand jax Arrays off by
reference inside one actor process. Runs with RTPU_SANITIZE=1 armed
(conftest): the CompiledDag wlock/rlock pairing and the device-handoff
registry lock are under the runtime lock-order sanitizer here.
"""

from __future__ import annotations

import os
import time

import pytest

import ray_tpu
from ray_tpu.core import runtime_context
from ray_tpu.core.config import config
from ray_tpu.dag import InputNode, bind, compile_dag, compile_pipeline
from ray_tpu.dag.channel import Channel, DeviceChannel
from tests.conftest import own_runtime


@pytest.fixture(scope="module")
def dag_ray():
    with own_runtime(4):
        yield


def test_spin_fanout_fanin_parity_with_block(dag_ray):
    """The spin lane is a latency knob, not a semantics change: a
    diamond (fan-out + fan-in) produces identical results compiled with
    a spin budget and with pure-block channels."""
    from ray_tpu.dag import MultiOutputNode

    @ray_tpu.remote
    class Math:
        def double(self, x):
            return x * 2

        def square(self, x):
            return x * x

        def join(self, a, b):
            return a + b

    a, b, c = Math.remote(), Math.remote(), Math.remote()
    for spin_us in (0, 200):
        with InputNode() as inp:
            left = bind(a, "double", inp)
            right = bind(b, "square", inp)
            out = bind(c, "join", left, right)
        dag = compile_dag(out, spin_us=spin_us)
        try:
            for x in range(5):
                assert dag.execute(x) == 2 * x + x * x
        finally:
            dag.teardown()
        with InputNode() as inp:
            multi = MultiOutputNode([bind(a, "double", inp),
                                     bind(b, "square", inp)])
        dag = compile_dag(multi, spin_us=spin_us)
        try:
            assert dag.execute(7) == [14, 49]
        finally:
            dag.teardown()


def test_spin_budget_exhaustion_no_busy_burn(dag_ray):
    """A stalled producer must cost the waiter its spin BUDGET, not the
    whole timeout: after spin_us the wait parks on the condvar, so CPU
    burned across a long timed-out read stays near zero."""
    store = runtime_context.get_core().store
    ch = Channel.create(store, capacity=1 << 12, spin_us=2000)
    reader = Channel.open(store, ch.descriptor())
    assert reader._spin_us == 2000  # descriptor carries the budget
    try:
        t0_wall = time.monotonic()
        t0_cpu = time.process_time()
        with pytest.raises(TimeoutError):
            reader.read(timeout_ms=600)
        wall = time.monotonic() - t0_wall
        cpu = time.process_time() - t0_cpu
        assert wall >= 0.55, f"timed out early: {wall:.3f}s"
        # spin budget is 2ms; a busy-burn bug would show ~wall of CPU
        assert cpu < 0.25, f"busy-burned {cpu:.3f}s CPU over {wall:.3f}s"
    finally:
        ch.release()
        reader.release()


def test_timeout_poisons_dag_under_spin(dag_ray):
    """A timed-out call leaves an unconsumed in-flight result; the DAG
    must poison itself (next call raises, no off-by-one) on the spin
    lane exactly as on the block lane."""

    @ray_tpu.remote
    class Slow:
        def step(self, x):
            time.sleep(float(x))
            return x

    s = Slow.remote()
    dag = compile_pipeline([(s, "step")], spin_us=200)
    try:
        assert dag.execute(0) == 0
        with pytest.raises(TimeoutError):
            dag.execute(2.0, timeout_ms=150)
        with pytest.raises(RuntimeError, match="broken"):
            dag.execute(0)
    finally:
        dag.teardown()


def test_teardown_drains_inflight_pipeline(dag_ray):
    """Satellite: teardown with pipelined calls still in flight must
    drain every output to its close sentinel instead of leaving sealed
    messages behind (one read drains at most one result)."""

    @ray_tpu.remote
    class Id:
        def step(self, x):
            return x

    a, b = Id.remote(), Id.remote()
    dag = compile_pipeline([(a, "step"), (b, "step")], spin_us=100)
    dag.execute(0)
    # three calls in flight, none resolved
    resolvers = [dag.execute_async(i) for i in range(3)]
    del resolvers
    t0 = time.monotonic()
    dag.teardown()  # must drain 3 results + sentinel, not hang
    assert time.monotonic() - t0 < 10
    with pytest.raises(RuntimeError):
        dag.execute(0)


def test_device_channel_unit_roundtrip(dag_ray):
    """Driver-side DeviceChannel: a jax Array crosses by REFERENCE
    (same object out), non-array payloads ride the inner pickled path,
    release() clears leftover registry entries."""
    import jax.numpy as jnp

    from ray_tpu.dag.channel import _DEVICE_HANDOFF

    store = runtime_context.get_core().store
    ch = DeviceChannel.create(store, capacity=1 << 12, spin_us=100)
    reader = DeviceChannel.open(store, ch.descriptor())
    try:
        arr = jnp.arange(8)
        ch.write(("v", arr))
        tag, out = reader.read()
        assert tag == "v" and out is arr  # no serialize round-trip
        ch.write(("v", {"host": 1}))  # non-array: pickled path
        assert reader.read() == ("v", {"host": 1})
        err = ValueError("boom")
        ch.write(("e", err))
        tag, out = reader.read()
        assert tag == "e" and isinstance(out, ValueError)
        # leftover handoff entries are dropped on release
        ch.write(("v", jnp.ones(2)))
        assert any(k[0] == ch._key for k in _DEVICE_HANDOFF)
    finally:
        ch.release()
        reader.release()
    assert not any(k[0] == ch._key for k in _DEVICE_HANDOFF)


def test_device_edges_fall_back_to_shm_on_cpu(dag_ray):
    """Acceptance: under JAX_PLATFORMS=cpu, device='auto' compiles every
    edge to a plain shm channel (no DeviceChannel) and the DAG works."""

    @ray_tpu.remote
    class Two:
        def first(self, x):
            return x + 1

        def second(self, x):
            return x * 10

    t = Two.remote()
    with InputNode() as inp:
        out = bind(t, "second", bind(t, "first", inp))
    dag = compile_dag(out, device="auto")
    try:
        assert not any(isinstance(c, DeviceChannel)
                       for c in dag._shm_chans)
        assert dag.execute(4) == 50
    finally:
        dag.teardown()


def test_device_edge_forced_same_actor_zero_copy(dag_ray):
    """device='force' puts the same-process edge on a DeviceChannel even
    on CPU: the producer's jax Array reaches the consumer as the SAME
    object (registry handoff), proven by identity inside the actor."""
    import jax.numpy as jnp  # noqa: F401 — jax present for the stages

    @ray_tpu.remote
    class Holder:
        def make(self, x):
            import jax.numpy as jnp

            self._made = jnp.arange(int(x))
            return self._made

        def check(self, arr):
            return bool(arr is self._made)

    h = Holder.remote()
    with InputNode() as inp:
        out = bind(h, "check", bind(h, "make", inp))
    dag = compile_dag(out, device="force", spin_us=100)
    try:
        assert any(isinstance(c, DeviceChannel) for c in dag._shm_chans)
        assert dag.execute(8) is True
    finally:
        dag.teardown()


def test_compile_failure_names_missing_actor(dag_ray, monkeypatch):
    """Satellite: an actor the cluster cannot place fails compile with a
    typed, bounded, actor-naming error — not a blind 5s retry loop."""
    from ray_tpu.exceptions import ActorDiedError

    core = runtime_context.get_core()

    def _addr(aid):
        raise ActorDiedError(f"unknown actor {aid}")

    monkeypatch.setattr(core, "_actor_addr", _addr, raising=False)
    os.environ["RTPU_DAG_COMPILE_ACTOR_WAIT_S"] = "0.3"
    config.reload()
    try:
        class Fake:
            _actor_id = "ghost-actor-42"

        t0 = time.monotonic()
        with pytest.raises(ValueError, match="ghost-actor-42.*step"):
            compile_pipeline([(Fake(), "step")])
        assert time.monotonic() - t0 < 3.0  # deadline honored, not 25x0.2
    finally:
        os.environ.pop("RTPU_DAG_COMPILE_ACTOR_WAIT_S", None)
        config.reload()


def test_serve_dag_mode_on_spin_lane(dag_ray):
    """The serve replica->engine hot path compiles onto the spin lane:
    PipelineDeployment inherits dag_spin_us (or serve_dag_spin_us) and
    serves requests through the compiled channels."""
    from ray_tpu.serve.dag_mode import PipelineDeployment

    class Add:
        def __init__(self, n):
            self._n = n

        def run(self, x):
            return x + self._n

    dep = PipelineDeployment([(Add, "run", (1,)), (Add, "run", (10,))],
                             spin_us=100)
    try:
        assert dep._spin_us == 100
        assert dep._dag._spin_us == 100
        assert dep(5) == 16
        # an expired forwarded deadline sheds instead of executing
        from ray_tpu.exceptions import BackpressureError

        with pytest.raises(BackpressureError):
            dep(5, _deadline=time.time() - 1)
    finally:
        dep.shutdown()


def test_serve_dag_spin_us_inherits_global(dag_ray):
    """serve_dag_spin_us=-1 (default) inherits dag_spin_us; an explicit
    value overrides it for serve only."""
    from ray_tpu.serve.dag_mode import PipelineDeployment

    class Id:
        def run(self, x):
            return x

    os.environ["RTPU_DAG_SPIN_US"] = "77"
    config.reload()
    try:
        dep = PipelineDeployment([(Id, "run", ())])
        try:
            assert dep._spin_us == 77
        finally:
            dep.shutdown()
        os.environ["RTPU_SERVE_DAG_SPIN_US"] = "0"
        config.reload()
        dep = PipelineDeployment([(Id, "run", ())])
        try:
            assert dep._spin_us == 0
            assert dep(3) == 3
        finally:
            dep.shutdown()
    finally:
        os.environ.pop("RTPU_DAG_SPIN_US", None)
        os.environ.pop("RTPU_SERVE_DAG_SPIN_US", None)
        config.reload()
