"""Multi-node cluster tests: GCS, cross-node scheduling, object transfer,
actors, PGs, spillback, and node-failure survival.

Reference test model: python/ray/tests/test_multi_node*.py and
cluster_utils.Cluster-based suites.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import runtime_context
from ray_tpu.core.cluster.fixture import Cluster
from ray_tpu.core.cluster.gcs import GcsServer
from ray_tpu.core.cluster.rpc import RpcClient
from ray_tpu.exceptions import ObjectLostError
from tests.conftest import own_cluster


# --------------------------------------------------------------------- GCS


def test_gcs_registry_heartbeat_and_death():
    gcs = GcsServer(authkey=b"k")
    try:
        c = RpcClient(gcs.address, b"k")
        assert c.call(("ping",)) == "pong"
        c.call(("register_node", b"n1", ("127.0.0.1", 1), {"CPU": 2}, {}, {}))
        c.call(("register_node", b"n2", ("127.0.0.1", 2), {"CPU": 4}, {}, {}))
        assert c.call(("wait_nodes", 2, 1.0))
        view = c.call(("list_nodes", True))
        assert len(view["nodes"]) == 2

        # kv
        c.call(("kv", "put", "a/b", 42))
        assert c.call(("kv", "get", "a/b")) == 42
        assert c.call(("kv", "keys", "a/")) == ["a/b"]

        # object directory: blocking loc_get
        t0 = time.monotonic()
        assert c.call(("loc_get", b"obj1", 0.2)) == []
        assert time.monotonic() - t0 >= 0.2
        c.call(("loc_add", b"obj1", ("127.0.0.1", 1)))
        assert c.call(("loc_get", b"obj1", 0.0)) == [("127.0.0.1", 1)]

        # death: n2 stops heartbeating -> DEAD within timeout; its object
        # locations are dropped
        c.call(("loc_add", b"obj2", ("127.0.0.1", 2)))
        from ray_tpu.core.config import config
        deadline = time.monotonic() + config.gcs_heartbeat_timeout_s + 2
        while time.monotonic() < deadline:
            c.call(("heartbeat", b"n1", {"CPU": 2}, 0))
            nodes = {n["node_id"]: n["state"]
                     for n in c.call(("list_nodes", False))["nodes"]}
            if nodes[b"n2"] == "DEAD":
                break
            time.sleep(0.1)
        assert nodes[b"n2"] == "DEAD"
        assert nodes[b"n1"] == "ALIVE"
        assert c.call(("loc_get", b"obj2", 0.0)) == []
        deaths = c.call(("deaths_since", 0))
        assert [nid for _, nid in deaths] == [b"n2"]
        c.close()
    finally:
        gcs.close()


# ----------------------------------------------------------- cluster basics


@pytest.fixture(scope="module")
def cluster():
    prev_core = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=3, num_workers_per_node=2,
                node_resources=[{"res0": 4}, {"res1": 4}, {"res2": 4}])
    c.wait_for_nodes(3)
    c.connect()
    yield c
    c.shutdown()
    runtime_context.set_core(prev_core)


def test_cluster_tasks_schedule_across_nodes(cluster):
    @ray_tpu.remote
    def who():
        from ray_tpu.util import host_node_pid
        return host_node_pid()

    # pin one task per node via its unique resource
    pids = {}
    for i in range(3):
        ref = who.options(resources={f"res{i}": 1}).remote()
        pids[i] = ray_tpu.get(ref, timeout=60)
    node_pids = {n.proc.pid for n in cluster.nodes}
    assert set(pids.values()) == node_pids


def test_cluster_cross_node_object_transfer(cluster):
    import numpy as np

    @ray_tpu.remote
    def produce():
        import numpy as np
        return np.arange(200_000, dtype=np.int64)

    @ray_tpu.remote
    def consume(arr):
        return int(arr.sum())

    # produce on node 0, consume on node 2 (the arg must travel node->node)
    ref = produce.options(resources={"res0": 1}).remote()
    total = ray_tpu.get(
        consume.options(resources={"res2": 1}).remote(ref), timeout=60)
    assert total == int(np.arange(200_000, dtype=np.int64).sum())


def test_cluster_free_fails_fast_and_worker_free(cluster):
    """Cluster-mode eager free: a later driver get fails immediately with
    the documented freed message (driver tombstone — not the 600s fetch
    deadline), and ray_tpu.free works from INSIDE a task (REQ_FREE path
    through the node server)."""
    import numpy as np

    ref = ray_tpu.put(np.zeros(1 << 20, np.uint8))
    assert ray_tpu.free(ref) == 1
    t0 = time.monotonic()
    with pytest.raises(ObjectLostError, match="freed"):
        ray_tpu.get(ref, timeout=60)
    assert time.monotonic() - t0 < 5.0  # fail-fast, not fetch-deadline

    @ray_tpu.remote
    def free_inside():
        r = ray_tpu.put(b"x" * (1 << 20))
        n = ray_tpu.free(r)
        return n

    assert ray_tpu.get(free_inside.remote(), timeout=60) == 1

    # worker on node 1 frees an object produced on node 0 (cross-node
    # fan-out + GCS tombstone); a dependent task on node 2 must then fail
    # fast via the fetch-loop tombstone check, not spin out the deadline
    @ray_tpu.remote
    def produce():
        import numpy as np
        return np.zeros(1 << 20, np.uint8)

    @ray_tpu.remote
    def free_refs(refs):
        return ray_tpu.free(refs)

    @ray_tpu.remote
    def consume(arr):
        return int(arr.sum())

    ref2 = produce.options(resources={"res0": 1}).remote()
    ray_tpu.get(ref2, timeout=60)
    assert ray_tpu.get(free_refs.options(resources={"res1": 1})
                       .remote([ref2]), timeout=60) == 1
    t0 = time.monotonic()
    # the dependent task fails fast with the freed error propagated
    # through its dep resolution (TaskError wrapping ObjectLostError)
    from ray_tpu.exceptions import TaskError
    with pytest.raises((ObjectLostError, TaskError), match="freed"):
        ray_tpu.get(consume.options(resources={"res2": 1}).remote(ref2),
                    timeout=90)
    assert time.monotonic() - t0 < 30.0


def test_cluster_put_get_and_wait(cluster):
    refs = [ray_tpu.put(i * 11) for i in range(5)]
    assert ray_tpu.get(refs) == [0, 11, 22, 33, 44]

    @ray_tpu.remote
    def slow(x):
        time.sleep(x)
        return x

    r_fast = slow.options(resources={"res1": 1}).remote(0.05)
    r_slow = slow.options(resources={"res2": 1}).remote(5.0)
    ready, rest = ray_tpu.wait([r_fast, r_slow], num_returns=1, timeout=30)
    assert ready == [r_fast] and rest == [r_slow]


def test_cluster_actor_cross_node_calls(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0
            from ray_tpu.util import host_node_pid
            self.pid = host_node_pid()

        def incr(self):
            self.n += 1
            return self.n

        def where(self):
            return self.pid

    # place the actor on node 1
    c = Counter.options(resources={"res1": 1}, name="ctr").remote()
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    assert ray_tpu.get(c.where.remote(), timeout=30) == cluster.nodes[1].proc.pid

    # a task on node 2 calls the actor on node 1 through its handle
    @ray_tpu.remote
    def poke(h):
        return ray_tpu.get(h.incr.remote(), timeout=30)

    assert ray_tpu.get(
        poke.options(resources={"res2": 1}).remote(c), timeout=60) == 2

    # named-actor lookup from the driver
    h = ray_tpu.get_actor("ctr")
    assert ray_tpu.get(h.incr.remote(), timeout=30) == 3


def test_detached_actor_survives_driver_and_node_death():
    """Detached named actors: the restart FSM lives in the GCS
    (reference: gcs_actor_manager.h:278), so the actor (a) outlives the
    creating driver, and (b) is restarted on a surviving node after its
    host dies — with no driver involved."""
    with own_cluster(2, num_workers_per_node=2,
                     node_resources=[{"stay": 4}, {"doomed": 4}]) as c:
        @ray_tpu.remote
        class Svc:
            def __init__(self):
                self.calls = 0

            def ping(self):
                self.calls += 1
                return self.calls

        svc = Svc.options(name="svc", lifetime="detached",
                          resources={"doomed": 1}).remote()
        assert ray_tpu.get(svc.ping.remote(), timeout=60) == 1

        # driver 1 exits; the actor must keep running
        c.disconnect()
        c.connect()  # a brand-new driver
        again = ray_tpu.get_actor("svc")
        assert ray_tpu.get(again.ping.remote(), timeout=60) == 2

        # the hosting node dies; a replacement provides the resources;
        # the GCS (not any driver) restarts the actor under its id
        doomed = c.nodes[1]
        c.remove_node(doomed, graceful=False)
        c.add_node(resources={"doomed": 4})
        c.wait_for_nodes(2)
        deadline = time.time() + 60
        last = None
        while time.time() < deadline:
            try:
                h = ray_tpu.get_actor("svc")
                last = ray_tpu.get(h.ping.remote(), timeout=30)
                break
            except Exception as e:  # noqa: BLE001 — restart in flight
                last = e
                time.sleep(0.5)
        assert last == 1, f"restarted actor should answer fresh: {last!r}"


def test_cluster_placement_group_spread(cluster):
    from ray_tpu.util import placement_group, remove_placement_group

    pg = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
    assert pg.wait(timeout_seconds=30)

    @ray_tpu.remote
    def who():
        from ray_tpu.util import host_node_pid
        return host_node_pid()

    pids = set()
    for i in range(3):
        ref = who.options(
            scheduling_strategy=("pg", pg.id.binary(), i)).remote()
        pids.add(ray_tpu.get(ref, timeout=60))
    assert pids == {n.proc.pid for n in cluster.nodes}
    remove_placement_group(pg)


def test_cluster_spillback_from_worker_submission(cluster):
    # a worker on node 0 submits a task needing res2 (only node 2 has it):
    # the node-0 scheduler must spill it to node 2
    @ray_tpu.remote
    def inner():
        from ray_tpu.util import host_node_pid
        return host_node_pid()

    @ray_tpu.remote
    def outer():
        ref = inner.options(resources={"res2": 1}).remote()
        return ray_tpu.get(ref, timeout=60)

    pid = ray_tpu.get(
        outer.options(resources={"res0": 1}).remote(), timeout=90)
    assert pid == cluster.nodes[2].proc.pid


def test_many_nodes_scale_stress():
    """Scale smoke: 16 real node-server processes, a task wave, an actor
    fleet, and placement groups — exposes O(N) control-plane paths before
    they matter (reference envelope: release/benchmarks/README.md, 64
    nodes; 16 here is bounded by this 1-core CI box, not the design)."""
    with own_cluster(16, num_workers_per_node=1,
                     object_store_memory=64 << 20) as c:
        @ray_tpu.remote
        def f(x):
            return x + 1

        # every task of the wave finishes, each with its own result, inside
        # the wait (a rate is the bench's to record: on a CPU that six
        # workers share it says what the machine was doing)
        out = ray_tpu.get([f.remote(i) for i in range(2000)], timeout=300)
        assert out == list(range(1, 2001))

        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        actors = [A.remote() for _ in range(30)]
        assert ray_tpu.get([a.ping.remote() for a in actors],
                           timeout=300) == [1] * 30

        from ray_tpu.util import placement_group, remove_placement_group
        pgs = [placement_group([{"CPU": 0.01}] * 2, strategy="SPREAD")
               for _ in range(10)]
        for pg in pgs:
            assert pg.wait(timeout_seconds=60)
        for pg in pgs:
            remove_placement_group(pg)


def test_cluster_kv(cluster):
    core = runtime_context.get_core()
    core.kv_op("put", "shared", {"x": 1})
    assert core.kv_op("get", "shared") == {"x": 1}


# ------------------------------------------------------------ node failure


def test_cluster_remove_node_survival():
    prev_core = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=3, num_workers_per_node=2,
                node_resources=[{"ra": 4}, {"rb": 4}, {"rc": 4, "rd": 4}])
    try:
        c.wait_for_nodes(3)
        core = c.connect()

        @ray_tpu.remote
        def who():
            from ray_tpu.util import host_node_pid
            return host_node_pid()

        @ray_tpu.remote
        class Sticky:
            def __init__(self):
                self.v = "alive"

            def ping(self):
                return self.v

        # object + restartable actor on the doomed node
        doomed_ref = who.options(resources={"rc": 1}).remote()
        ray_tpu.wait([doomed_ref], num_returns=1, timeout=60)
        a = Sticky.options(resources={"CPU": 0.01}, max_restarts=2,
                           scheduling_strategy=None).remote()
        # pin actor to doomed node via resource
        b = Sticky.options(resources={"rd": 0.1}, max_restarts=2).remote()
        assert ray_tpu.get(b.ping.remote(), timeout=60) == "alive"

        victim = c.nodes[2]
        c.remove_node(victim, graceful=False)

        # cluster keeps scheduling on surviving nodes
        surviving = {n.proc.pid for n in c.nodes}
        pids = {ray_tpu.get(who.options(resources={"ra": 1}).remote(),
                            timeout=60),
                ray_tpu.get(who.options(resources={"rb": 1}).remote(),
                            timeout=60)}
        assert pids == surviving

        # the dead node's object is lost: ObjectLostError, or
        # GetTimeoutError while its creating task, resubmitted through
        # lineage, waits for a node with "rc" — which never comes back.
        # Read beside the actor's restart: either way the driver first
        # dials the dead address for the RPC client's 10 s, and the two
        # dials need not stand in line.
        from ray_tpu.exceptions import GetTimeoutError
        lost = []

        def read_lost():
            with pytest.raises((ObjectLostError, GetTimeoutError)) as e:
                ray_tpu.get(doomed_ref, timeout=2)
            lost.append(e.type)

        reader = threading.Thread(target=read_lost)
        reader.start()

        # a replacement node with the actor's resource joins; the
        # restartable actor's pending restart lands on it
        c.add_node(resources={"rd": 4})
        c.wait_for_nodes(3)
        deadline = time.monotonic() + 90
        ok = False
        while time.monotonic() < deadline:
            try:
                if ray_tpu.get(b.ping.remote(), timeout=10) == "alive":
                    ok = True
                    break
            except Exception:
                time.sleep(0.5)
        assert ok, "actor did not restart on the replacement node"
        assert ray_tpu.get(a.ping.remote(), timeout=60) == "alive"
        reader.join(timeout=60)
        assert lost, "the read of the dead node's object never ended"
    finally:
        c.shutdown()
        runtime_context.set_core(prev_core)


def test_runtime_env_working_dir_across_nodes(cluster, tmp_path):
    """Packages registered by the driver reach workers on every node via
    the GCS KV package store."""
    proj = tmp_path / "clusterproj"
    proj.mkdir()
    (proj / "marker.txt").write_text("cluster-pkg")

    @ray_tpu.remote(runtime_env={"working_dir": str(proj)})
    def read_marker():
        with open("marker.txt") as f:
            from ray_tpu.util import host_node_pid
            return f.read(), host_node_pid()

    # spread over enough tasks to hit more than one node's workers
    results = ray_tpu.get([read_marker.remote() for _ in range(8)],
                          timeout=120)
    assert all(content == "cluster-pkg" for content, _ in results)
    assert len({node for _, node in results}) >= 2


def test_chunked_parallel_object_transfer(tmp_path):
    """A large object created on one node transfers to another via the
    ranged multi-connection path (threshold forced low; producer and
    consumer pinned to different nodes through custom resources)."""
    import hashlib

    import numpy as np

    with own_cluster(2, num_workers_per_node=2,
                     object_store_memory=256 << 20,
                     node_resources=[{"pin0": 4}, {"pin1": 4}],
                     env={"RTPU_FETCH_PARALLEL_THRESHOLD_BYTES": str(1 << 20),
                          "RTPU_FETCH_CHUNK_BYTES": str(1 << 20),
                          "RTPU_FETCH_PARALLELISM": "3"}) as c:
        @ray_tpu.remote(resources={"pin0": 1})
        def make_big():
            rng = np.random.default_rng(0)
            return rng.integers(0, 255, size=8 << 20, dtype=np.uint8)

        @ray_tpu.remote(resources={"pin1": 1})
        def digest(arr):
            return hashlib.sha256(arr.tobytes()).hexdigest()

        ref = make_big.remote()
        expected = hashlib.sha256(
            np.random.default_rng(0).integers(
                0, 255, size=8 << 20, dtype=np.uint8).tobytes()).hexdigest()
        # consumer runs on the OTHER node: the 8 MiB payload crosses the
        # node boundary through fetch_size + parallel fetch_range calls
        assert ray_tpu.get(digest.remote(ref), timeout=120) == expected


def test_runtime_env_nested_submission_spills_across_nodes(tmp_path):
    """A nested runtime_env submission from a worker publishes its
    package to the GCS KV, so the nested task survives spilling to a
    node whose table never saw the upload."""
    with own_cluster(2, num_workers_per_node=2,
                     object_store_memory=128 << 20,
                     node_resources=[{"pinA": 4}, {"pinB": 4}]) as c:
        proj = tmp_path / "nestproj"
        proj.mkdir()
        (proj / "x.txt").write_text("cross-node-nested")

        @ray_tpu.remote(resources={"pinA": 1})
        def outer(path):
            # nested task requires pinB => must run on the OTHER node
            @ray_tpu.remote(resources={"pinB": 1},
                            runtime_env={"working_dir": path})
            def inner():
                with open("x.txt") as f:
                    return f.read()

            return ray_tpu.get(inner.remote())

        assert ray_tpu.get(outer.remote(str(proj)),
                           timeout=120) == "cross-node-nested"


def test_pull_admission_bounded_concurrent_fetch():
    """Pull admission control (reference: pull_manager.h:52): a consumer
    node concurrently fetching more total bytes than its store capacity
    completes correctly — bulk pulls reserve budget and queue instead of
    over-committing the store — and pull events with their priority
    class land in the timeline."""
    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    env = {"RTPU_FETCH_PARALLEL_THRESHOLD_BYTES": str(4 << 20),
           "RTPU_TASK_EVENTS_ENABLED": "1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    c = Cluster(num_nodes=2, num_workers_per_node=2,
                object_store_memory=48 << 20,
                node_resources=[{"src": 8}, {"dst": 8}])
    try:
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote
        def produce(i):
            import numpy as np
            return np.full((10 << 20) // 8, float(i))  # 10 MB each

        # 8 x 10MB = 80MB total, all produced on node 0 (spill covers
        # the producer side); budget on node 1 = 48MB * 0.5 = 24MB, so
        # at most 2 pulls transfer at once
        refs = [produce.options(resources={"src": 1}).remote(i)
                for i in range(8)]
        ray_tpu.wait(refs, num_returns=len(refs), timeout=120)

        @ray_tpu.remote
        def consume(*arrs):
            return [float(a[0]) for a in arrs]

        out = ray_tpu.get(
            consume.options(resources={"dst": 1}).remote(*refs),
            timeout=180)
        assert out == [float(i) for i in range(8)]

        # priorities observable in the timeline: the dep pulls above ran
        # as task-args class
        events = ray_tpu.timeline()
        pulls = [e for e in events if str(e.get("name", "")).startswith("pull:")]
        assert pulls, "no pull events recorded"
        assert any(e["name"] == "pull:task_args" for e in pulls), \
            [e["name"] for e in pulls]
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        c.shutdown()
        runtime_context.set_core(prev)


def test_ray_client_proxy_multi_tenant(tmp_path):
    """The Ray-Client proxy (reference: util/client/server/proxier.py):
    one endpoint, isolated per-client drivers. A subprocess client works
    through `init(address="ray://...")`; a second tenant's disconnect
    tears down only ITS state; idle tenants reap."""
    import subprocess
    import sys

    from ray_tpu.client import ClientProxyServer, ProxyCore

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=2, num_workers_per_node=2,
                object_store_memory=64 << 20)
    proxy = None
    try:
        c.wait_for_nodes(2)
        proxy = ClientProxyServer(c.gcs_address, authkey=c.authkey,
                                  idle_timeout_s=30.0)
        host, port = proxy.address

        # tenant A: a full thin-client session in a subprocess
        script = f"""
import ray_tpu
import numpy as np
ray_tpu.init(address="ray://{host}:{port}")

@ray_tpu.remote
def double(x):
    return x * 2

@ray_tpu.remote
def plus(a, b):
    return a + b

assert ray_tpu.get(double.remote(21), timeout=60) == 42
# nested ref in args crosses the proxy by id
assert ray_tpu.get(plus.remote(double.remote(1), 3), timeout=60) == 5

@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def incr(self):
        self.n += 1
        return self.n

cnt = Counter.remote()
assert ray_tpu.get(cnt.incr.remote(), timeout=60) == 1
assert ray_tpu.get(cnt.incr.remote(), timeout=60) == 2

arr = np.arange(1000, dtype=np.float32)
ref = ray_tpu.put(arr)
back = ray_tpu.get(ref, timeout=60)
assert (back == arr).all()
print("CLIENT_A_DONE", flush=True)
ray_tpu.shutdown()
"""
        env = dict(os.environ)
        env["RTPU_CLUSTER_AUTHKEY"] = c.authkey.hex()
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=240)
        assert "CLIENT_A_DONE" in out.stdout, out.stderr[-2000:]

        # tenant B and C side by side in this process (direct ProxyCore)
        pb = ProxyCore(proxy.address, authkey=c.authkey)
        pc2 = ProxyCore(proxy.address, authkey=c.authkey)
        assert proxy.num_tenants == 2  # A already disconnected at exit
        rb = pb.put_object({"who": "B"})
        rc = pc2.put_object({"who": "C"})
        # C leaves: B's objects stay fetchable (isolated teardown)
        pc2.shutdown()
        assert proxy.num_tenants == 1
        assert pb.get_objects([rb], timeout=30)[0] == {"who": "B"}
        pb.shutdown()
        assert proxy.num_tenants == 0
    finally:
        if proxy is not None:
            proxy.close()
        c.shutdown()
        runtime_context.set_core(prev)


def test_push_throttle_bounds_inflight_bytes():
    """Deterministic check of the sender-side throttle itself: N
    concurrent chunk reads never exceed the in-flight byte cap, an
    oversized single chunk still proceeds when alone (no deadlock),
    and every queued request eventually serves."""
    import threading

    from ray_tpu.core.cluster import node_server as ns_mod
    from ray_tpu.core.config import config

    class FakeServer:
        _push_cv = threading.Condition()
        _push_inflight = 0
        _push_waits = 0

        def __init__(self):
            self.peak = 0
            self.lock = threading.Lock()

        def _fetch_range_inner(self, oid, off, length):
            with self.lock:
                self.peak = max(self.peak, self._push_inflight)
            time.sleep(0.01)  # hold the grant so requests overlap
            return b"x" * 8

    os.environ["RTPU_PUSH_MAX_INFLIGHT_BYTES"] = str(2 << 20)
    config.reload()
    try:
        srv = FakeServer()
        results = []
        threads = [threading.Thread(
            target=lambda: results.append(
                ns_mod.NodeServer._op_fetch_range(
                    srv, b"o", 0, 1 << 20)))
            for _ in range(8)]
        # oversized lone chunk: bigger than the cap, must not deadlock
        big = threading.Thread(target=lambda: results.append(
            ns_mod.NodeServer._op_fetch_range(srv, b"o", 0, 8 << 20)))
        for t in threads:
            t.start()
        big.start()
        for t in threads + [big]:
            t.join(timeout=60)
        assert len(results) == 9 and all(r == b"x" * 8 for r in results)
        # the cap held: readers observe at most the 2MB cap; the 8MB
        # outlier is admitted only when ALONE (its own observation is
        # the 8MB itself, never 8MB + a reader)
        assert srv.peak <= (8 << 20), srv.peak
        assert srv._push_waits > 0
        assert srv._push_inflight == 0  # fully drained
    finally:
        os.environ.pop("RTPU_PUSH_MAX_INFLIGHT_BYTES", None)
        config.reload()


def test_sender_side_push_flow_control():
    """Sender-side transfer cap (reference: push_manager.h): a node
    serving many concurrent chunk reads bounds bytes in flight; excess
    chunk requests queue and the transfer still completes exactly."""
    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    env = {"RTPU_FETCH_PARALLEL_THRESHOLD_BYTES": str(1 << 20),
           "RTPU_FETCH_CHUNK_BYTES": str(1 << 20),
           "RTPU_FETCH_PARALLELISM": "6",
           "RTPU_PUSH_MAX_INFLIGHT_BYTES": str(2 << 20)}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    from ray_tpu.core.config import config
    config.reload()
    c = Cluster(num_nodes=2, num_workers_per_node=1,
                object_store_memory=96 << 20,
                node_resources=[{"src": 4}, {"dst": 4}])
    try:
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote
        def produce():
            import numpy as np
            return np.arange((24 << 20) // 8, dtype=np.float64)  # 24 MB

        @ray_tpu.remote
        def consume(a):
            return float(a.sum())

        ref = produce.options(resources={"src": 1}).remote()
        out = ray_tpu.get(
            consume.options(resources={"dst": 1}).remote(ref), timeout=120)
        n = (24 << 20) // 8
        assert out == (n - 1) * n / 2.0
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)
        config.reload()
        c.shutdown()
        runtime_context.set_core(prev)


def test_cluster_streaming_generator_cross_node(cluster):
    """Streaming returns work cluster-wide: the driver consumes refs from
    a producer pinned to a remote node while it is still yielding, the
    generator survives being pickled into a task on a THIRD node, and
    mid-stream cancel propagates."""
    from ray_tpu.exceptions import TaskCancelledError, TaskError

    @ray_tpu.remote
    def gen(n):
        for i in range(n):
            time.sleep(0.02)
            yield i * 10

    # driver consumes from a pinned remote producer, while running
    g = gen.options(num_returns="streaming",
                    resources={"res1": 1}).remote(8)
    t0 = time.monotonic()
    vals, first_at = [], None
    for ref in g:
        if first_at is None:
            first_at = time.monotonic() - t0
        vals.append(ray_tpu.get(ref, timeout=30))
    total = time.monotonic() - t0
    assert vals == [i * 10 for i in range(8)]
    assert first_at < total / 2, (first_at, total)

    # the generator handle pickles into a task on ANOTHER node
    @ray_tpu.remote
    def consume(g2):
        return [ray_tpu.get(ref, timeout=30) for ref in g2]

    g2 = gen.options(num_returns="streaming",
                     resources={"res0": 1}).remote(5)
    out = ray_tpu.get(
        consume.options(resources={"res2": 1}).remote(g2), timeout=60)
    assert out == [i * 10 for i in range(5)]

    # mid-stream cancel of a remote producer
    g3 = gen.options(num_returns="streaming",
                     resources={"res1": 1}).remote(1000)
    ray_tpu.get(g3.next_ref(timeout=30), timeout=30)
    ray_tpu.cancel(g3)
    with pytest.raises((TaskCancelledError, TaskError)):
        for ref in g3:
            ray_tpu.get(ref, timeout=30)


def test_cluster_actor_restart_transparent_calls():
    """Cross-node restart transparency: after the actor's host node dies,
    new calls ride out the RESTARTING window (the GCS actor_state channel
    tells the driver a restart is underway) and land on the restarted
    incarnation on the replacement node — the death never surfaces."""
    with own_cluster(2, num_workers_per_node=2,
                     node_resources=[{"ra": 4}, {"rb": 4}]) as c:
        @ray_tpu.remote
        class Echo:
            def __init__(self):
                self.served = 0

            def hit(self, x):
                self.served += 1
                return x * 3

        e = Echo.options(resources={"rb": 0.1}, max_restarts=2,
                         max_task_retries=2).remote()
        assert ray_tpu.get(e.hit.remote(1), timeout=60) == 3

        victim = c.nodes[1]
        c.remove_node(victim, graceful=False)
        c.add_node(resources={"rb": 4})
        c.wait_for_nodes(2)

        # new calls during/after the restart window reach the new
        # incarnation; the transient death must not surface as
        # ActorDiedError once the budget and window allow a comeback
        deadline = time.monotonic() + 120
        got = None
        while time.monotonic() < deadline:
            try:
                got = ray_tpu.get(e.hit.remote(14), timeout=15)
                break
            except Exception:
                time.sleep(0.5)
        assert got == 42, "actor calls never recovered after node death"
        # steady state: calls work repeatedly against the new incarnation
        assert ray_tpu.get(e.hit.remote(5), timeout=60) == 15
