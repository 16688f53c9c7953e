"""Compiled DAGs: shm channels, resident pipelines, error propagation,
dispatch-latency advantage over regular actor calls.

Reference test model: python/ray/dag/tests/experimental/.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import runtime_context
from ray_tpu.dag import Channel, InputNode, bind, compile_pipeline
from ray_tpu.dag.channel import ChannelClosed
from tests.conftest import own_cluster, own_runtime


@pytest.fixture(scope="module")
def dag_ray():
    with own_runtime(4):
        yield


def test_channel_spsc_roundtrip(dag_ray):
    store = runtime_context.get_core().store
    ch = Channel.create(store, capacity=1 << 16)
    reader = Channel.open(store, ch.descriptor())
    out = []

    def consume():
        for _ in range(50):
            out.append(reader.read(timeout_ms=10_000))

    t = threading.Thread(target=consume)
    t.start()
    for i in range(50):
        ch.write({"i": i, "arr": np.arange(10) * i})
    t.join(20)
    assert len(out) == 50
    assert out[49]["i"] == 49 and out[49]["arr"][9] == 441

    ch.close()
    with pytest.raises(ChannelClosed):
        reader.read(timeout_ms=5000)
    ch.release()
    reader.release()


def test_pipeline_execute_and_errors(dag_ray):
    @ray_tpu.remote
    class Stage:
        def __init__(self, add):
            self.add = add

        def step(self, x):
            if x == "boom":
                raise ValueError("kaboom")
            return x + self.add

    a = Stage.remote(1)
    b = Stage.remote(10)
    c = Stage.remote(100)
    dag = compile_pipeline([(a, "step"), (b, "step"), (c, "step")])
    try:
        assert dag.execute(0) == 111
        assert dag.execute(5) == 116
        # errors raised in a stage propagate through the pipe to the caller
        with pytest.raises(ValueError, match="kaboom"):
            dag.execute("boom")
        # pipeline still healthy afterwards
        assert dag.execute(1) == 112
    finally:
        dag.teardown()
    with pytest.raises(RuntimeError):
        dag.execute(1)


def test_bind_style_compile(dag_ray):
    @ray_tpu.remote
    class M:
        def double(self, x):
            return x * 2

        def inc(self, x):
            return x + 1

    m1, m2 = M.remote(), M.remote()
    with InputNode() as inp:
        node = bind(m2, "inc", bind(m1, "double", inp))
    dag = node.experimental_compile()
    try:
        assert dag.execute(21) == 43
    finally:
        dag.teardown()


def test_pipeline_overlaps_stages(dag_ray):
    @ray_tpu.remote
    class Slow:
        def step(self, x):
            time.sleep(0.1)
            return x

    s1, s2, s3 = Slow.remote(), Slow.remote(), Slow.remote()
    dag = compile_pipeline([(s1, "step"), (s2, "step"), (s3, "step")])
    try:
        dag.execute(0)  # warm the loops
        t0 = time.perf_counter()
        resolvers = [dag.execute_async(i) for i in range(4)]
        outs = [r() for r in resolvers]
        dt = time.perf_counter() - t0
        assert outs == [0, 1, 2, 3]
        # serial would be 4 calls x 3 stages x 0.1s = 1.2s; pipelined
        # overlap must beat it clearly
        assert dt < 0.95, f"no pipelining: {dt:.2f}s"
    finally:
        dag.teardown()


def test_dag_dispatch_latency_vs_actor_calls(dag_ray):
    """What makes a compiled DAG's round cheaper than the same chain of
    actor calls, read as the path taken and not as a ratio of two times
    (which a CPU run under six workers cannot show: the channels spin, and
    medians read 0.4x to 3x from run to run): a round through the actors
    makes one task a stage (an id, a spec, the scheduler, a reply), a round
    through the DAG makes none, its values go through the stages' channels
    and come back in order. ``benchmark``'s dag bench records the ratio."""
    from ray_tpu.core import ids

    @ray_tpu.remote
    class Id:
        def step(self, x):
            return x

    actors = [Id.remote() for _ in range(3)]
    n = 40

    def tasks_made(round_):
        before = ids._task_counter._value
        round_()
        return ids._task_counter._value - before

    def through_actors():
        for i in range(n):
            v = i
            for a in actors:
                v = ray_tpu.get(a.step.remote(v), timeout=30)
            assert v == i

    dag = compile_pipeline([(a, "step") for a in actors])
    try:
        def through_dag():
            for i in range(n):
                assert dag.execute(i) == i

        through_dag()                       # the stage loops are up
        assert tasks_made(through_actors) == 3 * n
        assert tasks_made(through_dag) == 0
        # and several in flight at once stay in order
        waiting = [dag.execute_async(i) for i in range(3)]
        assert tasks_made(lambda: None) == 0
        assert [w() for w in waiting] == [0, 1, 2]
    finally:
        dag.teardown()


def test_diamond_dag_fan_out_fan_in(dag_ray):
    """Branching graph: input fans out to two parallel stages whose
    outputs join at a combiner (reference: compiled diamond DAGs,
    python/ray/dag/dag_node_operation.py)."""
    from ray_tpu.dag import MultiOutputNode, compile_dag

    @ray_tpu.remote
    class Math:
        def double(self, x):
            return x * 2

        def square(self, x):
            return x * x

        def join(self, a, b):
            return a + b

    a, b, c = Math.remote(), Math.remote(), Math.remote()
    with InputNode() as inp:
        left = bind(a, "double", inp)
        right = bind(b, "square", inp)
        out = bind(c, "join", left, right)
    dag = compile_dag(out)
    try:
        for x in range(5):
            assert dag.execute(x) == 2 * x + x * x
    finally:
        dag.teardown()

    # multi-output: both branches surface to the driver
    with InputNode() as inp:
        left = bind(a, "double", inp)
        right = bind(b, "square", inp)
        multi = MultiOutputNode([left, right])
    dag = compile_dag(multi)
    try:
        assert dag.execute(7) == [14, 49]
    finally:
        dag.teardown()


def test_diamond_dag_error_propagation(dag_ray):
    from ray_tpu.dag import compile_dag

    @ray_tpu.remote
    class M:
        def ok(self, x):
            return x

        def boom(self, x):
            raise ValueError("branch exploded")

        def join(self, a, b):
            return (a, b)

    a, b, c = M.remote(), M.remote(), M.remote()
    with InputNode() as inp:
        out = bind(c, "join", bind(a, "ok", inp), bind(b, "boom", inp))
    dag = compile_dag(out)
    try:
        with pytest.raises(ValueError, match="branch exploded"):
            dag.execute(1)
        # pairing intact: the next call still works
        with pytest.raises(ValueError, match="branch exploded"):
            dag.execute(2)
    finally:
        dag.teardown()


def test_cross_node_dag():
    """A DAG whose stages live on DIFFERENT nodes: edges ride socket
    channels with KV rendezvous; the diamond joins across the cluster
    (reference: multi-node compiled DAGs over the channel abstraction,
    python/ray/experimental/channel/)."""
    from ray_tpu.dag import compile_dag, compile_pipeline

    with own_cluster(3, num_workers_per_node=1,
                     node_resources=[{"n0": 4}, {"n1": 4}, {"n2": 4}]) as c:
        @ray_tpu.remote
        class Stage:
            def __init__(self, tag):
                self.tag = tag

            def step(self, x):
                return x + [self.tag]

            def join(self, a, b):
                return (a, b)

        s0 = Stage.options(resources={"n0": 1}).remote("n0")
        s1 = Stage.options(resources={"n1": 1}).remote("n1")
        s2 = Stage.options(resources={"n2": 1}).remote("n2")
        for s in (s0, s1, s2):
            ray_tpu.get(s.step.remote([]), timeout=60)

        # linear chain spanning three nodes
        dag = compile_pipeline([(s0, "step"), (s1, "step"), (s2, "step")])
        try:
            assert dag.execute([], timeout_ms=120_000) == \
                ["n0", "n1", "n2"]
            assert dag.execute(["x"], timeout_ms=120_000) == \
                ["x", "n0", "n1", "n2"]
        finally:
            dag.teardown()

        # diamond across nodes
        with InputNode() as inp:
            out = bind(s2, "join", bind(s0, "step", inp),
                       bind(s1, "step", inp))
        dag = compile_dag(out)
        try:
            assert dag.execute([], timeout_ms=120_000) == (["n0"], ["n1"])
        finally:
            dag.teardown()


def test_socket_channel_rejects_unauthenticated_peer():
    """A stray/hostile connection must neither hijack the edge nor wedge
    it: the reader keeps accepting until an authkey'd peer completes the
    HMAC handshake (an unauthenticated SocketChannel accepted anybody)."""
    import socket as _socket

    from ray_tpu.dag.channel import SocketChannel

    kv_store = {}

    def kv(op, key, value=None):
        if op == "put":
            kv_store[key] = value
        elif op == "get":
            return kv_store.get(key)
        elif op == "del":
            kv_store.pop(key, None)

    key = b"k" * 16
    cid = SocketChannel.create_id()
    reader = SocketChannel(cid, kv, "reader", host="127.0.0.1", authkey=key)
    port = kv_store[f"dagchan:{cid}"]

    got = []
    t = threading.Thread(
        target=lambda: got.append(reader.read(timeout_ms=20_000)),
        daemon=True)
    t.start()

    # hostile peer: connects first, sends garbage instead of a valid HMAC
    # answer — must be dropped, not accepted
    evil = _socket.create_connection(("127.0.0.1", port), timeout=5)
    evil.sendall(b"\x00" * 64)
    time.sleep(0.3)

    # wrong-key peer: completes the handshake protocol but can't answer
    # the challenge
    with pytest.raises(Exception):
        bad = SocketChannel(cid, kv, "writer", host="127.0.0.1",
                            authkey=b"x" * 16)
        bad.write("stolen", timeout_ms=3000)

    # the real writer still gets through
    writer = SocketChannel(cid, kv, "writer", host="127.0.0.1", authkey=key)
    writer.write("hello", timeout_ms=10_000)
    t.join(timeout=10)
    assert got == ["hello"]
    evil.close()
    writer.release()
    reader.release()


def test_rpc_retry_whitelist():
    """Lost-reply retries are restricted to idempotent ops (the
    at-least-once hazard on submit/kv-merge/publish)."""
    from ray_tpu.core.cluster.rpc import _retry_safe_after_apply

    assert _retry_safe_after_apply(("loc_get", b"x"))
    assert _retry_safe_after_apply(("heartbeat", b"n", {}, 0))
    assert _retry_safe_after_apply(("kv", "get", "k"))
    assert _retry_safe_after_apply(("kv", "put", "k", 1))
    assert not _retry_safe_after_apply(("kv", "merge", "k", {}))
    assert not _retry_safe_after_apply(("kv", "cas_merge", "k", {}, 0))
    assert not _retry_safe_after_apply(("publish", "ch", "m"))
    assert not _retry_safe_after_apply(("free", [b"o"]))
    assert not _retry_safe_after_apply(("release", [b"o"]))
    # submit/actor_call/create_actor are retry-safe ONLY because the node
    # dedups them on the per-request nonce (NodeServer._dedup)
    assert _retry_safe_after_apply(("submit", b"f"))
    assert _retry_safe_after_apply(("actor_call", b"a"))
    assert _retry_safe_after_apply(("create_actor", b"c"))


def test_node_server_dedups_retried_submissions():
    """A re-delivered submit/actor_call (lost-reply retry) must not run
    side effects twice, while a FAILED apply must be re-runnable and an
    in-progress apply must latch duplicates."""
    from collections import OrderedDict

    from ray_tpu.core.cluster.node_server import NodeServer

    s = NodeServer.__new__(NodeServer)
    s._applied = OrderedDict()
    s._applied_lock = threading.Lock()

    calls = []
    assert s._dedup(b"n1", lambda: calls.append(1) or "r1") == "r1"
    assert s._dedup(b"n1", lambda: calls.append(2) or "r2") == "r1"
    assert calls == [1]                      # duplicate deduped
    assert s._dedup(None, lambda: "x") == "x"  # no nonce: always runs

    # a failed apply is NOT memoized: the retry re-runs it
    with pytest.raises(ValueError):
        s._dedup(b"n2", lambda: (_ for _ in ()).throw(ValueError("boom")))
    assert s._dedup(b"n2", lambda: "ok") == "ok"

    # wip latch: a duplicate racing an in-progress apply waits for the
    # original result instead of reporting phantom success
    started, release = threading.Event(), threading.Event()

    def slow():
        started.set()
        release.wait(10)
        return "slow-result"

    results = []
    t1 = threading.Thread(target=lambda: results.append(
        s._dedup(b"n3", slow)), daemon=True)
    t1.start()
    started.wait(5)
    t2 = threading.Thread(target=lambda: results.append(
        s._dedup(b"n3", lambda: "dup-ran")), daemon=True)
    t2.start()
    time.sleep(0.2)
    release.set()
    t1.join(5)
    t2.join(5)
    assert results.count("slow-result") == 2 and "dup-ran" not in results

    # bounded: old done entries age out
    for i in range(NodeServer._APPLIED_CAP + 10):
        s._dedup(b"x%d" % i, lambda: True)
    assert len(s._applied) <= NodeServer._APPLIED_CAP
