"""Fault tolerance: task retries, object spilling, chaos, reconstruction.

Reference test model: python/ray/tests/test_failure*.py,
test_object_spilling.py, and the ResourceKiller chaos suites
(python/ray/_private/test_utils.py:1433).
"""

from __future__ import annotations

import os
import time
import uuid

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import runtime_context
from ray_tpu.exceptions import WorkerCrashedError
from tests.conftest import own_cluster


@pytest.fixture
def local_ray():
    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    yield
    core = runtime_context.get_core_or_none()
    if core is not None:
        core.shutdown()
    runtime_context.set_core(prev)


def test_task_retry_on_worker_crash(local_ray, tmp_path):
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    marker = str(tmp_path / "attempt")

    @ray_tpu.remote
    def flaky(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)  # simulate a hard worker crash on first attempt
        return "recovered"

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == "recovered"


def test_task_retry_budget_exhausted(local_ray):
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_retries=0)
    def always_crash():
        os._exit(1)

    with pytest.raises(WorkerCrashedError):
        ray_tpu.get(always_crash.remote(), timeout=60)


def test_retry_preserves_resource_accounting(local_ray, tmp_path):
    ray_tpu.init(num_workers=3, object_store_memory=64 << 20)
    marker = str(tmp_path / "attempt2")

    @ray_tpu.remote(num_cpus=2)
    def flaky(path):
        if not os.path.exists(path):
            open(path, "w").close()
            os._exit(1)
        return 7

    assert ray_tpu.get(flaky.remote(marker), timeout=60) == 7
    # pool must still run resource-ful tasks afterwards (no leaked grants)
    @ray_tpu.remote(num_cpus=2)
    def heavy():
        return 1
    assert ray_tpu.get(heavy.remote(), timeout=60) == 1


def test_spill_driver_puts_larger_than_store(local_ray):
    # 16 x 8 MiB puts through a 48 MiB store: most must spill to disk and
    # read back intact (reference: test_object_spilling.py)
    ray_tpu.init(num_workers=2, object_store_memory=48 << 20)
    arrays = [np.full((1 << 20,), i, dtype=np.float64) for i in range(16)]
    refs = [ray_tpu.put(a) for a in arrays]
    core = runtime_context.get_core()
    assert core._spilled_bytes > 0, "nothing was spilled"
    for i, ref in enumerate(refs):
        out = ray_tpu.get(ref, timeout=60)
        assert out[0] == i and out[-1] == i and out.shape == arrays[i].shape


def test_spill_worker_results_larger_than_store(local_ray):
    ray_tpu.init(num_workers=2, object_store_memory=48 << 20)

    @ray_tpu.remote
    def produce(i):
        import numpy as np
        return np.full((1 << 20,), i, dtype=np.float64)  # 8 MiB

    refs = [produce.remote(i) for i in range(16)]
    totals = [float(a[0]) for a in ray_tpu.get(refs, timeout=120)]
    assert totals == [float(i) for i in range(16)]

    # spilled objects are consumable as downstream task args too
    @ray_tpu.remote
    def head(a):
        return float(a[0])

    assert ray_tpu.get([head.remote(r) for r in refs], timeout=120) == totals


def test_chaos_workers_die_during_data_pipeline(local_ray):
    # every task start has a 2% chance of killing its worker; retries must
    # carry the pipeline to a correct result
    os.environ["RTPU_TESTING_KILL_WORKER_PROB"] = "0.02"
    try:
        ray_tpu.init(num_workers=3, object_store_memory=128 << 20)
        import ray_tpu.data as rd

        n = 2000
        ds = rd.range(n, parallelism=16).map_batches(
            lambda b: {"v": [x * 2 for x in b["id"]]})
        total = sum(row["v"] for row in ds.iter_rows())
        assert total == n * (n - 1)  # 2 * sum(0..n-1)
    finally:
        del os.environ["RTPU_TESTING_KILL_WORKER_PROB"]


def test_gcs_restart_rehydrates_cluster_state(tmp_path):
    """Chaos: hard-kill the GCS mid-workload and restart it on the same
    port from its WAL/snapshot. Nodes heartbeat back in, KV and named
    actors survive, and new tasks + calls on the pre-crash actor work
    (reference role: redis_store_client.h:33 GCS table persistence +
    gcs_redis_failure_detector)."""
    with own_cluster(2, num_workers_per_node=2,
                     node_resources=[{"a": 4}, {"b": 4}],
                     gcs_persist_dir=str(tmp_path / "gcs")) as c:
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        counter = Counter.options(name="survivor").remote()
        assert ray_tpu.get(counter.bump.remote(), timeout=60) == 1
        core = runtime_context.get_core()
        core.kv_op("put", "answer", 42)

        @ray_tpu.remote
        def work(x):
            return x * 2

        # in-flight work, then the control plane dies hard
        pre = [work.remote(i) for i in range(10)]
        c.kill_gcs()
        time.sleep(0.5)
        c.restart_gcs()
        # nodes were persisted as ALIVE and keep heartbeating into the
        # new GCS (a non-persisted node would re-register instead)
        assert c.wait_for_nodes(2, timeout=30)

        # KV survived the restart
        assert core.kv_op("get", "answer") == 42
        # the named-actor directory survived: a fresh lookup resolves and
        # the actor (which never died) kept its state
        again = ray_tpu.get_actor("survivor")
        assert ray_tpu.get(again.bump.remote(), timeout=60) == 2
        # pre-crash work completes (nodes never died), new work schedules
        assert ray_tpu.get(pre, timeout=120) == [i * 2 for i in range(10)]
        assert ray_tpu.get([work.remote(i) for i in range(10)],
                           timeout=120) == [i * 2 for i in range(10)]


def test_cluster_reconstruction_after_node_death():
    with own_cluster(2, num_workers_per_node=2,
                     node_resources=[{"left": 4}, {"right": 4}]) as c:
        @ray_tpu.remote
        def produce(tag):
            import numpy as np
            return np.full((300_000,), 42.0)

        # produced on the doomed node
        ref = produce.options(resources={"right": 1}).remote("x")
        ray_tpu.wait([ref], num_returns=1, timeout=60)

        c.remove_node(c.nodes[1], graceful=False)
        # a replacement node provides the task's resources again
        c.add_node(resources={"right": 4})
        c.wait_for_nodes(2)

        # lineage reconstruction: the driver resubmits produce() to the
        # replacement node and the get succeeds transparently
        out = ray_tpu.get(ref, timeout=120)
        assert out.shape == (300_000,) and out[0] == 42.0


def test_driver_death_reclaims_owned_state():
    """Owner-failure semantics (reference: reference_count.h:61 owner
    death, gcs_job_manager.h): kill -9 a driver mid-workload; its
    detached actor keeps serving, its non-detached actor is killed, and
    its owned objects are reclaimed from the store."""
    import subprocess
    import sys

    from ray_tpu.core.cluster.fixture import Cluster
    from ray_tpu.core.cluster.rpc import RpcClient

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=1, num_workers_per_node=2,
                object_store_memory=64 << 20)
    try:
        script = r"""
import os, sys, time
import ray_tpu
from ray_tpu.core import runtime_context
from ray_tpu.core.cluster.cluster_core import ClusterCore

core = ClusterCore((sys.argv[1], int(sys.argv[2])))
runtime_context.set_core(core)

@ray_tpu.remote
class Counter:
    def __init__(self): self.n = 0
    def bump(self): self.n += 1; return self.n

det = Counter.options(name="survivor", lifetime="detached").remote()
assert ray_tpu.get(det.bump.remote(), timeout=60) == 1
plain = Counter.options(name="casualty", max_restarts=5).remote()
assert ray_tpu.get(plain.bump.remote(), timeout=60) == 1

import numpy as np
ref = ray_tpu.put(np.zeros(4 << 20, dtype=np.uint8))  # 4 MiB, driver-owned
print("OID", ref.binary().hex(), flush=True)
print("DRIVER_READY", flush=True)
time.sleep(600)  # parked until killed
"""
        env = dict(os.environ)
        env["RTPU_CLUSTER_AUTHKEY"] = c.authkey.hex()
        proc = subprocess.Popen(
            [sys.executable, "-c", script,
             c.gcs_address[0], str(c.gcs_address[1])],
            stdout=subprocess.PIPE, env=env, text=True)
        oid_hex = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline().strip()
            if line.startswith("OID "):
                oid_hex = line.split()[1]
            if line == "DRIVER_READY":
                break
        assert oid_hex, "driver never published its object id"
        oid_b = bytes.fromhex(oid_hex)

        node = RpcClient(c.nodes[0].address, c.authkey)
        assert node.call(("has", oid_b)), "object should exist pre-kill"

        proc.kill()
        proc.wait()

        # the GCS declares the driver dead after its heartbeat timeout;
        # nodes then reclaim. Poll for the cleanup to land.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and node.call(("has", oid_b)):
            time.sleep(0.25)
        assert not node.call(("has", oid_b)), \
            "dead driver's object was never reclaimed"

        # a second driver: the detached actor lives, the plain one died
        core2 = c.connect()
        runtime_context.set_core(core2)
        h = ray_tpu.get_actor("survivor")
        assert ray_tpu.get(h.bump.remote(), timeout=60) == 2

        from ray_tpu.exceptions import ActorDiedError, GetTimeoutError
        deadline = time.monotonic() + 30
        dead = False
        while time.monotonic() < deadline and not dead:
            try:
                h2 = ray_tpu.get_actor("casualty")
                ray_tpu.get(h2.bump.remote(), timeout=5)
                time.sleep(0.5)       # still serving: poll again
            except GetTimeoutError:
                continue              # slow cluster is NOT death
            except (ActorDiedError, ValueError):
                dead = True           # killed, or name already dropped
        assert dead, "non-detached actor outlived its dead driver"
        node.close()
    finally:
        c.shutdown()
        runtime_context.set_core(prev)


def test_owner_cleanup_op_reclaims_immediately():
    """The ops hook ('owner_cleanup', driver_id) reclaims one owner's
    objects deterministically — the node-local half of the organic
    driver-death path, without waiting for heartbeat timeouts."""
    from ray_tpu.core import runtime_context as rc
    from ray_tpu.core.cluster.fixture import Cluster
    from ray_tpu.core.cluster.rpc import RpcClient

    prev = rc.get_core_or_none()
    rc.set_core(None)
    c = Cluster(num_nodes=1, num_workers_per_node=1,
                object_store_memory=64 << 20)
    try:
        core = c.connect()
        rc.set_core(core)
        ref = ray_tpu.put(np.zeros(1 << 20, dtype=np.uint8))
        node = RpcClient(c.nodes[0].address, c.authkey)
        assert node.call(("has", ref.binary()))
        node.call(("owner_cleanup", core._driver_id))
        assert not node.call(("has", ref.binary()))
        # untagged (worker-owned) objects are untouched by owner cleanup
        @ray_tpu.remote
        def make():
            return ray_tpu.put(b"worker-owned")
        inner = ray_tpu.get(make.remote(), timeout=60)
        node.call(("owner_cleanup", core._driver_id))
        assert ray_tpu.get(inner, timeout=30) == b"worker-owned"
        node.close()
    finally:
        c.shutdown()
        rc.set_core(prev)


def test_memory_monitor_oom_kill_retry_and_typed_error(local_ray, tmp_path):
    """Memory monitor + group-by-owner kill policy (reference:
    memory_monitor.h:52, worker_killing_policy_group_by_owner.h): drive
    the worker tree into (bounded) memory pressure; the newest retriable
    task's worker is killed and the task retries WITHOUT consuming its
    crash budget; with OOM retries exhausted the caller gets a typed
    OutOfMemoryError; the node survives throughout."""
    from ray_tpu.core.config import config
    from ray_tpu.core.memory_monitor import tree_rss
    from ray_tpu.exceptions import OutOfMemoryError

    os.environ["RTPU_MEMORY_MONITOR_INTERVAL_S"] = "0.1"
    try:
        from ray_tpu.core.config import config as _c
        _c.reload()
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
        core = runtime_context.get_core()
        core.wait_for_workers()
        pids = [w.proc.pid for w in core._workers.values()
                if w.proc is not None]
        base = tree_rss(pids)
        # cap the worker tree a bit above its idle footprint: a ~500 MB
        # hog must trip the monitor, the retry's modest path must not
        os.environ["RTPU_MEMORY_LIMIT_BYTES"] = str(base + (250 << 20))
        config.reload()

        marker = str(tmp_path / "oom_attempt")

        @ray_tpu.remote
        def hog(path):
            import os as _os
            import time as _time

            import numpy as np
            if not _os.path.exists(path):
                open(path, "w").close()
                a = np.ones((500 << 20) // 8)  # ~500 MB: over the cap
                _time.sleep(30)                # stay fat until killed
                return float(a[0])
            return 41.0                        # retry: fits fine

        assert ray_tpu.get(hog.remote(marker), timeout=120) == 41.0
        assert core._oom_kill_count >= 1, "monitor never fired"

        # OOM budget exhausted -> typed error, not a crash error
        os.environ["RTPU_TASK_OOM_RETRIES"] = "0"
        config.reload()

        @ray_tpu.remote
        def hog_forever():
            import time as _time

            import numpy as np
            a = np.ones((500 << 20) // 8)
            _time.sleep(30)
            return float(a[0])

        with pytest.raises(OutOfMemoryError):
            ray_tpu.get(hog_forever.remote(), timeout=120)

        # the node is alive and healthy after policy kills
        @ray_tpu.remote
        def fine():
            return "fine"

        assert ray_tpu.get(fine.remote(), timeout=60) == "fine"
    finally:
        for k in ("RTPU_MEMORY_MONITOR_INTERVAL_S",
                  "RTPU_MEMORY_LIMIT_BYTES", "RTPU_TASK_OOM_RETRIES"):
            os.environ.pop(k, None)
        config.reload()


def test_spill_to_fsspec_uri_backends(local_ray, tmp_path):
    """Spill routes through fsspec when RTPU_SPILL_DIR is a URI
    (reference: external_storage.py:451 spills to filesystem OR S3):
    round-trip through file:// and the in-process memory:// backend."""
    from ray_tpu.core.config import config

    for uri in (f"file://{tmp_path}/spill_uri", "memory://rtpu_spill_t"):
        os.environ["RTPU_SPILL_DIR"] = uri
        config.reload()
        try:
            ray_tpu.init(num_workers=2, object_store_memory=48 << 20)
            core = runtime_context.get_core()
            arrays = [np.full((1 << 20,), i, dtype=np.float64)
                      for i in range(12)]  # 12 x 8MB through 48MB store
            refs = [ray_tpu.put(a) for a in arrays]
            assert core._spilled_bytes > 0, f"nothing spilled for {uri}"
            for i, ref in enumerate(refs):
                out = ray_tpu.get(ref, timeout=60)
                assert out[0] == i and out[-1] == i
            if uri.startswith("file://"):
                spilled = list((tmp_path / "spill_uri").rglob("*"))
                assert any(p.is_file() for p in spilled), \
                    "no spill files under the file:// URI"
        finally:
            core = runtime_context.get_core_or_none()
            if core is not None:
                core.shutdown()
            runtime_context.set_core(None)
            os.environ.pop("RTPU_SPILL_DIR", None)
            config.reload()


# ---------------------------------------------------------------------------
# lineage reconstruction: task-produced objects lost to eviction, spill-file
# loss, or corruption are transparently recomputed from recorded lineage;
# losses are injected deterministically via core.fault_injection.


@pytest.fixture
def fault_injection():
    from ray_tpu.core import fault_injection as fi

    fi.clear()
    yield fi
    fi.clear()


def _payload(x):
    # > the 100KB inline threshold, so results land in the shm store
    # (inline payloads ride in the object table and cannot be "lost")
    return list(range(x, x + 50_000))


def test_reconstruct_evicted_shm_object(local_ray, fault_injection):
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    core = runtime_context.get_core()

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    ref = produce.remote(7)
    want = ray_tpu.get(ref, timeout=60)
    assert fi.evict_object(core, ref), "eviction should remove the container"
    assert ray_tpu.get(ref, timeout=60) == want


def test_reconstruct_deleted_spill_file(local_ray, fault_injection):
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    core = runtime_context.get_core()

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    ref = produce.remote(9)
    want = ray_tpu.get(ref, timeout=60)
    assert fi.spill_object(core, ref), "object should spill on demand"
    assert fi.delete_spill_file(core, ref)
    assert ray_tpu.get(ref, timeout=60) == want


def test_reconstruct_corrupt_spill_file(local_ray, fault_injection):
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    core = runtime_context.get_core()

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    ref = produce.remote(13)
    want = ray_tpu.get(ref, timeout=60)
    assert fi.spill_object(core, ref)
    assert fi.corrupt_spill_file(core, ref)
    # the file still exists and stats fine — only decode notices
    assert ray_tpu.get(ref, timeout=60) == want


def test_reconstruct_chained_lineage(local_ray, fault_injection):
    """Recovering y whose dep x is ALSO lost resubmits both, in order."""
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    core = runtime_context.get_core()

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    @ray_tpu.remote
    def double(v):
        return [n * 2 for n in v]

    x = produce.remote(1)
    y = double.remote(x)
    want = ray_tpu.get(y, timeout=60)
    assert fi.evict_object(core, x)
    assert fi.evict_object(core, y)
    assert ray_tpu.get(y, timeout=60) == want


def test_max_reconstructions_zero_names_producing_task(
        local_ray, fault_injection):
    from ray_tpu.core.config import config
    from ray_tpu.exceptions import ObjectLostError

    fi = fault_injection
    os.environ["RTPU_MAX_RECONSTRUCTIONS"] = "0"
    config.reload()
    try:
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
        core = runtime_context.get_core()

        @ray_tpu.remote
        def produce(x):
            return _payload(x)

        ref = produce.remote(21)
        ray_tpu.get(ref, timeout=60)
        assert fi.evict_object(core, ref)
        with pytest.raises(ObjectLostError) as ei:
            ray_tpu.get(ref, timeout=60)
        # deterministic failure must NAME the producing task
        assert ei.value.task_id, "error should carry the producing task id"
        assert "task" in str(ei.value)
    finally:
        os.environ.pop("RTPU_MAX_RECONSTRUCTIONS", None)
        config.reload()


def test_reconstruction_budget_exhaustion(local_ray, fault_injection):
    """Repeated injected loss at the get site burns the whole budget,
    then surfaces ObjectLostError with the attempt history."""
    from ray_tpu.core.config import config
    from ray_tpu.exceptions import ObjectLostError

    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    ref = produce.remote(33)
    ray_tpu.get(ref, timeout=60)
    fi.inject("get", "evict", target=ref.id.hex(), times=-1)
    with pytest.raises(ObjectLostError) as ei:
        ray_tpu.get(ref, timeout=120)
    assert ei.value.task_id
    assert len(ei.value.attempts) == config.max_reconstructions
    assert "budget" in str(ei.value)


def test_free_means_dead_no_reconstruction(local_ray, fault_injection):
    from ray_tpu.exceptions import ObjectLostError

    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    ref = produce.remote(41)
    ray_tpu.get(ref, timeout=60)
    assert ray_tpu.free([ref]) == 1
    with pytest.raises(ObjectLostError):
        ray_tpu.get(ref, timeout=60)


def test_put_objects_not_reconstructed(local_ray, fault_injection):
    from ray_tpu.exceptions import ObjectLostError

    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    core = runtime_context.get_core()
    ref = ray_tpu.put(_payload(0))
    assert fi.evict_object(core, ref)
    with pytest.raises(ObjectLostError):
        ray_tpu.get(ref, timeout=60)


def test_fault_injection_env_surface(local_ray):
    """RTPU_FAULT_<SITE> env specs arm the same deterministic hooks."""
    from ray_tpu.core import fault_injection as fi

    os.environ["RTPU_FAULT_GET"] = "evict:1"
    try:
        assert fi.load_env() == 1
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

        @ray_tpu.remote
        def produce(x):
            return _payload(x)

        ref = produce.remote(55)
        want_first = _payload(55)
        # the armed fault evicts exactly once at the get site; the value
        # still comes back via reconstruction
        assert ray_tpu.get(ref, timeout=60) == want_first
        assert ray_tpu.get(ref, timeout=60) == want_first
    finally:
        os.environ.pop("RTPU_FAULT_GET", None)
        fi.clear()


def test_dispatch_fault_site_kill_worker_recovers(local_ray,
                                                  fault_injection):
    """The deterministic 'dispatch' site SIGKILLs the worker right after
    it receives the task batch; the worker-death retry path re-runs the
    task elsewhere, invisibly to the caller."""
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    fi.inject("dispatch", "kill_worker")
    ref = produce.remote(17)
    assert ray_tpu.get(ref, timeout=60) == _payload(17)


def test_task_fault_site_env_armed_exit_recovers(local_ray):
    """RTPU_FAULT_TASK is worker-side: every worker (including zygote
    respawns, which inherit the zygote's armed environment) os._exit(1)s
    before running the task, so each retry deterministically dies and
    the caller gets WorkerCrashedError once the budget is spent."""
    from ray_tpu.core import fault_injection as fi
    from ray_tpu.exceptions import WorkerCrashedError

    os.environ["RTPU_FAULT_TASK"] = "exit:-1"
    try:
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

        @ray_tpu.remote(max_retries=1)
        def produce(x):
            return _payload(x)

        with pytest.raises(WorkerCrashedError):
            ray_tpu.get(produce.remote(29), timeout=60)
    finally:
        os.environ.pop("RTPU_FAULT_TASK", None)
        fi.clear()


def test_spill_fault_site_delete_on_spill_reconstructs(local_ray,
                                                       fault_injection):
    """The 'spill' site loses the file the moment the payload moves to
    disk (torn write / reclaimed scratch volume); a later get
    reconstructs from lineage instead of reading the vanished file."""
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    core = runtime_context.get_core()

    @ray_tpu.remote
    def produce(x):
        return _payload(x)

    ref = produce.remote(23)
    want = ray_tpu.get(ref, timeout=60)
    fi.inject("spill", "delete")
    assert fi.spill_object(core, ref), "object should spill on demand"
    assert ray_tpu.get(ref, timeout=60) == want


def test_lineage_evicted_past_budget_not_reconstructed(
        local_ray, fault_injection):
    """With a zero lineage byte budget every entry is evicted on
    insert, so a lost object is unrecoverable — and says why."""
    from ray_tpu.core.config import config
    from ray_tpu.exceptions import ObjectLostError

    fi = fault_injection
    os.environ["RTPU_LINEAGE_MAX_BYTES"] = "0"
    config.reload()
    try:
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
        core = runtime_context.get_core()

        @ray_tpu.remote
        def produce(x):
            return _payload(x)

        ref = produce.remote(61)
        ray_tpu.get(ref, timeout=60)
        assert fi.evict_object(core, ref)
        with pytest.raises(ObjectLostError) as ei:
            ray_tpu.get(ref, timeout=60)
        assert "lineage" in str(ei.value)
    finally:
        os.environ.pop("RTPU_LINEAGE_MAX_BYTES", None)
        config.reload()


# ---------------------------------------------------------------------------
# actor task retries: at-least-once execution, exactly-once result delivery.
# Reference model: max_task_retries / ActorUnavailableError semantics in
# python/ray/tests/test_actor_failures.py.


def _actor_pid(handle):
    return ray_tpu.get(handle.pid.remote(), timeout=60)


def test_actor_task_retry_inflight_kill(local_ray):
    """SIGKILL the actor's worker mid-call: with max_task_retries the
    in-flight call replays against the restarted incarnation and the
    caller sees the correct result, never the death."""
    import signal

    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=2, max_task_retries=2)
    class Counter:
        def __init__(self):
            self.n = 0

        def pid(self):
            return os.getpid()

        def slow_inc(self, delay):
            time.sleep(delay)
            self.n += 1
            return self.n

    c = Counter.remote()
    pid = _actor_pid(c)
    ref = c.slow_inc.remote(1.0)
    time.sleep(0.3)  # let the call reach the worker
    os.kill(pid, signal.SIGKILL)
    assert ray_tpu.get(ref, timeout=60) == 1
    assert _actor_pid(c) != pid  # really a new incarnation


def test_actor_call_fault_site_kill_worker(local_ray, fault_injection):
    """The deterministic actor_call site kills the worker right after one
    targeted dispatch; the replay is invisible to the caller."""
    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=1, max_task_retries=1)
    class A:
        def pid(self):
            return os.getpid()

        def f(self, x):
            return x * 2

    a = A.remote()
    pid = _actor_pid(a)
    fi.inject("actor_call", "kill_worker", target=f"{a.actor_id.hex()}:f")
    assert ray_tpu.get(a.f.remote(21), timeout=60) == 42
    assert _actor_pid(a) != pid


def test_actor_call_drop_then_death_replays(local_ray, fault_injection):
    """A dropped dispatch (lost message) is recovered by the worker-death
    replay: the call is still tracked in-flight, so killing the worker
    re-submits it to the new incarnation."""
    import signal

    fi = fault_injection
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=1, max_task_retries=1)
    class A:
        def pid(self):
            return os.getpid()

        def f(self, x):
            return x + 1

    a = A.remote()
    pid = _actor_pid(a)
    fi.inject("actor_call", "drop", target=f"{a.actor_id.hex()}:f")
    ref = a.f.remote(1)  # silently dropped: worker never sees it
    time.sleep(0.3)
    os.kill(pid, signal.SIGKILL)
    assert ray_tpu.get(ref, timeout=60) == 2


def test_actor_sealed_result_adopted_exactly_once(local_ray, tmp_path):
    """Worker dies between sealing the results and flushing the DONE
    report (exit_after fault): the owner adopts the sealed containers
    instead of re-executing — the side effect happens exactly once."""
    from ray_tpu.core import fault_injection as fi

    marker = str(tmp_path / "executions")
    os.environ["RTPU_FAULT_ACTOR_WORKER_KILL"] = "exit_after:1"
    try:
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

        @ray_tpu.remote(max_restarts=2, max_task_retries=2)
        class S:
            def bump(self, path):
                with open(path, "a") as f:
                    f.write("x")
                return _payload(7)  # > inline threshold: sealed into shm

        s = S.remote()
        assert ray_tpu.get(s.bump.remote(marker), timeout=60) == _payload(7)
        time.sleep(0.5)  # nothing should re-execute afterwards
        assert open(marker).read() == "x"
    finally:
        os.environ.pop("RTPU_FAULT_ACTOR_WORKER_KILL", None)
        fi.clear()


def test_actor_replayed_completed_call_served_from_store(local_ray):
    """In-flight kill with several calls queued: completed calls at or
    below the watermark are never re-executed on replay — each increment
    lands exactly once even though the batch is re-submitted."""
    import signal

    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=2, max_task_retries=2)
    class Seq:
        def __init__(self):
            self.log = []

        def pid(self):
            return os.getpid()

        def add(self, i, delay=0.0):
            time.sleep(delay)
            self.log.append(i)
            return i

        def get_log(self):
            return list(self.log)

    s = Seq.remote()
    pid = _actor_pid(s)
    refs = [s.add.remote(0), s.add.remote(1),
            s.add.remote(2, delay=1.0), s.add.remote(3)]
    time.sleep(0.4)  # 0 and 1 complete; 2 is mid-execution
    os.kill(pid, signal.SIGKILL)
    assert ray_tpu.get(refs, timeout=60) == [0, 1, 2, 3]
    # state is rebuilt by replay, and no index ran twice POST-restart
    log = ray_tpu.get(s.get_log.remote(), timeout=60)
    assert sorted(set(log)) == sorted(log), f"re-executed entries: {log}"


def test_actor_restart_buffer_overflow_unavailable(local_ray):
    """Calls buffer on a RESTARTING actor up to actor_restart_buffer_max;
    past it submissions raise ActorUnavailableError (not a hang, not
    ActorDiedError). Buffered calls drain after the restart."""
    import signal

    from ray_tpu.core.config import config
    from ray_tpu.exceptions import ActorUnavailableError

    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    old = config.actor_restart_buffer_max
    config.actor_restart_buffer_max = 5
    try:
        @ray_tpu.remote(max_restarts=3, max_task_retries=1)
        class B:
            def __init__(self):
                time.sleep(2.0)  # slow restart: hold the window open

            def pid(self):
                return os.getpid()

            def f(self, i):
                return i

        b = B.remote()
        pid = _actor_pid(b)
        os.kill(pid, signal.SIGKILL)
        time.sleep(0.3)  # death noticed -> RESTARTING
        refs, unavailable = [], 0
        for i in range(20):
            try:
                refs.append(b.f.remote(i))
            except ActorUnavailableError:
                unavailable += 1
        assert unavailable > 0, "overflow never raised"
        assert len(refs) <= 5 + 1  # cap (one may race the death notice)
        assert ray_tpu.get(refs, timeout=60) == list(range(len(refs)))
    finally:
        config.actor_restart_buffer_max = old


def test_actor_budget_exhaustion_enriched_death(local_ray):
    """Terminal death carries the cause, restarts consumed, and the
    failing incarnation in both the message and structured fields."""
    from ray_tpu.exceptions import ActorDiedError

    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=1, max_task_retries=0)
    class D:
        def boom(self):
            os._exit(1)

        def ok(self):
            return "fine"

    d = D.remote()
    # crash until the budget is gone: the terminal error (unlike the
    # transient mid-call one) carries the structured death fields
    deadline = time.monotonic() + 60
    err = None
    while time.monotonic() < deadline:
        try:
            ray_tpu.get(d.boom.remote(), timeout=10)
        except ActorDiedError as e:
            if e.restarts_consumed is not None:
                err = e
                break
        except Exception:
            pass
        time.sleep(0.2)
    assert err is not None, "never saw the terminal ActorDiedError"
    assert "restarts consumed: 1" in str(err)
    assert err.restarts_consumed == 1
    assert err.incarnation is not None
    assert "cause" in str(err)


def test_actor_retry_exceptions_app_error(local_ray):
    """retry_exceptions re-runs a call whose application error matches;
    non-matching errors surface immediately."""
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_task_retries=3, retry_exceptions=[ValueError])
    class Flaky:
        def __init__(self):
            self.attempts = 0

        def eventually(self):
            self.attempts += 1
            if self.attempts < 3:
                raise ValueError("transient")
            return self.attempts

        def wrong_type(self):
            raise KeyError("not retryable")

    f = Flaky.remote()
    assert ray_tpu.get(f.eventually.remote(), timeout=60) == 3
    with pytest.raises(Exception) as ei:
        ray_tpu.get(f.wrong_type.remote(), timeout=60)
    assert "KeyError" in str(ei.value)


def test_actor_method_options_explicit_kwargs(local_ray):
    """ActorMethod.options accepts the retry options and rejects typos
    with TypeError instead of swallowing them."""
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote
    class M:
        def f(self):
            return 1

    m = M.remote()
    assert ray_tpu.get(
        m.f.options(max_task_retries=2, retry_exceptions=True).remote(),
        timeout=60) == 1
    with pytest.raises(TypeError):
        m.f.options(max_retires=5)
    with pytest.raises(TypeError):
        m.f.options(num_return=2)


def test_kill_no_restart_false_consumes_budget_and_restarts(local_ray):
    """ray_tpu.kill(actor, no_restart=False) behaves like a worker death:
    one restart is consumed and the actor comes back; once the budget is
    gone the next kill is terminal."""
    from ray_tpu.exceptions import ActorDiedError

    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=1, max_task_retries=2)
    class K:
        def pid(self):
            return os.getpid()

    k = K.remote()
    p1 = _actor_pid(k)
    ray_tpu.kill(k, no_restart=False)
    p2 = _actor_pid(k)
    assert p2 != p1, "actor did not restart after kill(no_restart=False)"
    ray_tpu.kill(k, no_restart=False)  # budget exhausted: terminal
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        with pytest.raises(Exception) as ei:
            ray_tpu.get(k.pid.remote(), timeout=10)
        if isinstance(ei.value, ActorDiedError):
            break
        time.sleep(0.2)
    assert isinstance(ei.value, ActorDiedError)


def test_chaos_actor_workers_sigkilled_zero_lost_calls(local_ray):
    """Serve/Tune-shaped chaos: replica actors serve a stream of calls
    while their workers are SIGKILLed repeatedly; with max_task_retries
    every call returns its correct result — zero lost, zero duplicated
    deliveries."""
    import signal

    ray_tpu.init(num_workers=4, object_store_memory=64 << 20)

    @ray_tpu.remote(max_restarts=-1, max_task_retries=-1)
    class Replica:
        def __init__(self, scale):
            self.scale = scale

        def pid(self):
            return os.getpid()

        def infer(self, x):
            time.sleep(0.01)
            return x * self.scale

    replicas = [Replica.remote(10), Replica.remote(100)]
    pids = [_actor_pid(r) for r in replicas]

    stop = {"flag": False}

    def killer():
        rounds = 0
        while not stop["flag"] and rounds < 4:
            time.sleep(0.5)
            for i, r in enumerate(replicas):
                try:
                    os.kill(pids[i], signal.SIGKILL)
                except ProcessLookupError:
                    pass
            time.sleep(1.0)
            for i, r in enumerate(replicas):
                try:
                    pids[i] = ray_tpu.get(r.pid.remote(), timeout=30)
                except Exception:
                    pass
            rounds += 1

    import threading
    kt = threading.Thread(target=killer, daemon=True)
    kt.start()
    refs = []
    for i in range(60):
        refs.append((i, 0, replicas[0].infer.remote(i)))
        refs.append((i, 1, replicas[1].infer.remote(i)))
        time.sleep(0.02)
    stop["flag"] = True
    kt.join(timeout=30)
    scales = [10, 100]
    for i, rep, ref in refs:
        assert ray_tpu.get(ref, timeout=120) == i * scales[rep], \
            f"call {i} on replica {rep} lost or wrong"


# ---------------------------------------------------------------------------
# elastic gang training: preemption ride-through with deterministic
# shrink/grow resume. The chaos drill kills/preempts gang workers via the
# gang_resize fault site and asserts the loss curve matches an
# uninterrupted run; the unit tests pin the resize protocol's pieces
# (session interrupt drain, collective abort, worker-group bookkeeping,
# crash-safe checkpoint commit, PG-wait timeout flag).


def _elastic_sgd_loop(config):
    """Data-parallel SGD on a fixed linear-regression problem, float64.

    Deterministic by construction at ANY world size: step ``s``'s global
    batch comes from an rng keyed by ``s`` alone, each rank takes the
    ``rank::world`` slice, and the allreduced gradient SUM is normalized
    by the GLOBAL batch size — the loss curve depends only on the step
    sequence, never on how many ranks computed it.
    """
    import json as _json
    import os as _os
    import tempfile

    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel import collective

    ctx = train.get_context()
    rank, world = ctx.get_world_rank(), ctx.get_world_size()
    dim, gb = 4, int(config["global_batch"])
    true_w = np.arange(1.0, dim + 1.0)
    weights = np.zeros(dim, dtype=np.float64)
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        with ckpt.as_directory() as d:
            state = _json.load(open(_os.path.join(d, "state.json")))
        start = state["step"] + 1
        weights = np.asarray(state["w"], dtype=np.float64)
    for step in range(start, int(config["steps"])):
        if train.preempted():
            # maintenance SIGTERM observed at the step boundary — the
            # previous step's checkpoint is already persisted
            raise train.PreemptedError(f"rank {rank} preempted")
        rng = np.random.default_rng(1000 + step)  # keyed by step ONLY
        X = rng.normal(size=(gb, dim))
        y = X @ true_w
        Xs, ys = X[rank::world], y[rank::world]
        grad = Xs.T @ (Xs @ weights - ys)  # local SUM over the shard
        if world > 1:
            grad = np.asarray(
                collective.allreduce(grad, group_name="train"))
        weights = weights - float(config["lr"]) * grad / gb
        loss = float(np.mean((X @ weights - y) ** 2))
        with tempfile.TemporaryDirectory() as d:
            with open(_os.path.join(d, "state.json"), "w") as f:
                _json.dump({"step": step, "w": weights.tolist()}, f)
            train.report(
                {"step": step, "loss": loss, "world": world,
                 "pid": _os.getpid()},
                checkpoint=train.Checkpoint.from_directory(d))


def _fit_elastic(loop_cfg, scaling, storage_path, max_failures=0):
    from ray_tpu import train as train_mod
    from ray_tpu.train import FailureConfig, JaxConfig, RunConfig

    trainer = train_mod.DataParallelTrainer(
        _elastic_sgd_loop,
        train_loop_config=loop_cfg,
        backend_config=train_mod.JaxConfig(platform=None,
                                           host_collectives=True),
        scaling_config=scaling,
        run_config=RunConfig(storage_path=storage_path, name="elastic",
                             failure_config=FailureConfig(
                                 max_failures=max_failures)),
    )
    return trainer.fit()


def test_elastic_chaos_shrink_grow_loss_parity(local_ray, fault_injection,
                                               tmp_path):
    """The chaos drill: a 4-worker elastic gang rides through an abrupt
    SIGKILL (shrink to 3), grows back when the cooldown expires, then
    rides through a scheduled SIGTERM preemption — and the per-step loss
    curve is identical to an uninterrupted 4-worker run. Rank 0's worker
    process survives every resize (warm resume, not a cold gang
    restart)."""
    from ray_tpu.core.config import config
    from ray_tpu.train import ScalingConfig

    fi = fault_injection
    os.environ["RTPU_ELASTIC_GROW_COOLDOWN_S"] = "0.4"
    config.reload()
    try:
        ray_tpu.init(num_workers=6, object_store_memory=128 << 20)
        steps = 80
        loop_cfg = {"steps": steps, "global_batch": 16, "lr": 0.05}

        base = _fit_elastic(loop_cfg, ScalingConfig(num_workers=4),
                            str(tmp_path / "base"))
        assert base.error is None, base.error
        base_loss = {m["step"]: m["loss"] for m in base.metrics_history}
        assert len(base_loss) == steps

        # abrupt preemption (SIGKILL) after batch 3; scheduled
        # preemption (SIGTERM, checkpoint grace) after batch 45
        fi.inject("gang_resize", "kill", target="3")
        fi.inject("gang_resize", "sigterm", target="45")
        el = _fit_elastic(loop_cfg,
                          ScalingConfig(num_workers=4, min_workers=2),
                          str(tmp_path / "elastic"))
        assert el.error is None, el.error

        # deterministic resume: replayed steps overwrite their first
        # attempt (last occurrence wins), and every step's loss matches
        # the uninterrupted run
        el_loss, pids0 = {}, set()
        for m in el.metrics_history:
            el_loss[m["step"]] = m["loss"]
            pids0.add(m["pid"])
        assert set(el_loss) == set(base_loss)
        for s in sorted(base_loss):
            assert np.isclose(el_loss[s], base_loss[s],
                              rtol=1e-8, atol=1e-12), \
                f"step {s}: {el_loss[s]} != {base_loss[s]}"

        # the gang really shrank, and grew back when capacity returned
        worlds = [m["world"] for m in el.metrics_history]
        assert min(worlds) < 4, "the gang never shrank"
        shrinks = [e for e in el.elastic_stats if e["event"] == "shrink"]
        grows = [e for e in el.elastic_stats if e["event"] == "grow"]
        assert len(shrinks) >= 2, el.elastic_stats  # kill + sigterm
        assert len(grows) >= 1, el.elastic_stats
        assert all(e["resume_s"] > 0 for e in el.elastic_stats)
        assert {e["cause"] for e in shrinks} >= {"ActorDiedError",
                                                 "PreemptedError"}

        # warm resume: rank 0's process was never replaced
        assert len(pids0) == 1, f"rank-0 worker was replaced: {pids0}"
    finally:
        os.environ.pop("RTPU_ELASTIC_GROW_COOLDOWN_S", None)
        config.reload()


def test_elastic_below_min_workers_cold_restarts(local_ray, fault_injection,
                                                 tmp_path):
    """Shrinking below min_workers must NOT limp along at a world size
    the user forbade: the resize path raises TrainingWorkerError and
    recovery goes through the classic cold gang restart (consuming the
    failure budget), resuming from the last consistent checkpoint."""
    from ray_tpu.train import ScalingConfig

    fi = fault_injection
    ray_tpu.init(num_workers=4, object_store_memory=64 << 20)
    fi.inject("gang_resize", "kill", target="1")
    res = _fit_elastic({"steps": 6, "global_batch": 8, "lr": 0.05},
                       ScalingConfig(num_workers=2, min_workers=2),
                       str(tmp_path / "floor"), max_failures=1)
    assert res.error is None, res.error
    assert not res.elastic_stats, res.elastic_stats  # no in-place resize
    step_seq = [m["step"] for m in res.metrics_history]
    assert set(step_seq) == set(range(6))
    # the restart resumed from the batch-1 checkpoint, not from scratch
    assert step_seq.count(0) == 1, step_seq


def test_session_interrupt_drains_to_done_sentinel():
    """The resize drain protocol, in-process: an interrupt that lands
    while the loop is blocked in lockstep (result queued, waiting for
    the driver) must deliver BOTH the overtaken result and the done
    sentinel — and a hostile ``except Exception`` in user code must not
    swallow the interrupt (it is a BaseException)."""
    from ray_tpu.train.session import (
        SessionInterruptedError,
        TrainContext,
        _TrainSession,
    )

    box = {}

    def loop():
        i = 0
        while True:
            try:
                box["s"].report({"i": i})
            except Exception:
                pass  # hostile user code: must not eat the interrupt
            i += 1

    s = _TrainSession(loop, {}, TrainContext())
    box["s"] = s
    s.start()
    assert s.next_result(timeout=10).metrics == {"i": 0}
    # wait until the loop queued i=1 and blocked in lockstep
    deadline = time.monotonic() + 10
    while s._result_q.qsize() == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    s.interrupt("gang resize test")
    r1 = s.next_result(timeout=10)
    assert r1.metrics == {"i": 1} and not r1.done
    r2 = s.next_result(timeout=10)
    assert r2.done
    assert isinstance(r2.error, SessionInterruptedError)
    assert "gang resize test" in str(r2.error)
    s._thread.join(timeout=10)
    assert not s._thread.is_alive(), "train loop thread leaked"


def test_collective_abort_unblocks_member_fast(local_ray, tmp_path):
    """A member blocked in an in-flight collective fails over to
    CollectiveAbortedError (naming the reason — here, the dead rank)
    within ~a poll interval of the abort, not the 120 s op timeout."""
    from ray_tpu.parallel import collective

    ray_tpu.init(num_workers=3, object_store_memory=64 << 20)
    ready = str(tmp_path / "member_ready")

    @ray_tpu.remote
    class Member:
        def run(self, world, rank, ready_path):
            import time as _time

            import numpy as np

            from ray_tpu.parallel import collective as coll

            g = coll.init_collective_group(world, rank, group_name="abrt")
            open(ready_path, "w").close()
            t0 = _time.monotonic()
            try:
                g.allreduce(np.ones(3))
            except coll.CollectiveAbortedError as e:
                return _time.monotonic() - t0, str(e)
            return None, "allreduce completed?!"

    m = Member.remote()
    ref = m.run.remote(2, 0, ready)  # rank 1 never joins: the op blocks
    deadline = time.monotonic() + 30
    while not os.path.exists(ready) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert os.path.exists(ready), "member never started"
    time.sleep(0.5)  # member is now blocked polling the coordinator
    t0 = time.monotonic()
    assert collective.abort_group(
        "abrt", reason="gang resize: lost rank(s) [1] (ActorDiedError)")
    blocked_s, msg = ray_tpu.get(ref, timeout=30)
    unblock_s = time.monotonic() - t0
    assert unblock_s < 2.0, f"abort took {unblock_s:.2f}s to propagate"
    assert blocked_s >= 0.4, "member was not actually blocked"
    assert "lost rank(s) [1]" in msg and "'abrt'" in msg
    # a second abort on the same group is idempotent; a missing group
    # reports False instead of raising
    assert collective.abort_group("abrt", reason="again")
    assert not collective.abort_group("no_such_group")


def test_worker_group_resize_bookkeeping(local_ray):
    """Shrink/grow bookkeeping: removed positions free their placement
    bundles, a grow re-creates a worker INTO the freed bundle, and
    reassign_ranks compacts ranks to 0..n-1 in survivor order."""
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.worker_group import WorkerGroup

    ray_tpu.init(num_workers=5, object_store_memory=64 << 20)
    wg = WorkerGroup(ScalingConfig(num_workers=3, min_workers=1))
    wg.start()
    try:
        assert wg.bundle_indices == [0, 1, 2]
        assert len(wg) == 3
        wg.remove_positions({1})
        assert wg.bundle_indices == [0, 2]
        wg.generation += 1
        wg.reassign_ranks()
        infos = ray_tpu.get([w.node_info.remote() for w in wg.workers])
        assert [i["rank"] for i in infos] == [0, 1]
        pos = wg.try_add_worker(probe_timeout_s=30.0)
        assert pos == 2, "grow did not land"
        assert wg.bundle_indices == [0, 2, 1]  # reused the freed bundle
        wg.reassign_ranks()
        infos = ray_tpu.get([w.node_info.remote() for w in wg.workers])
        assert [i["rank"] for i in infos] == [0, 1, 2]
    finally:
        wg.shutdown()
    assert wg.workers == [] and wg.bundle_indices == []


def test_checkpoint_persist_atomic_manifest(tmp_path):
    """Crash-safe persistence: the committed dir carries a manifest
    listing every file and size, no stage (.tmp-*) dirs survive the
    commit, and re-persisting the same index (deterministic elastic
    replay over an orphan) replaces the dir atomically."""
    import json as _json

    from ray_tpu.train.storage import (
        MANIFEST_NAME,
        StorageContext,
        validate_checkpoint_dir,
    )

    storage = StorageContext(str(tmp_path / "results"), "exp", "trial")
    storage.ensure_trial_dir()
    src = tmp_path / "src"
    src.mkdir()
    (src / "state.json").write_text('{"step": 0}')
    (src / "shards").mkdir()
    (src / "shards" / "part-0.bin").write_bytes(b"x" * 1024)
    ckpt = storage.persist_checkpoint_dir(str(src), 0)

    man = _json.load(open(os.path.join(ckpt.path, MANIFEST_NAME)))
    assert man["index"] == 0
    assert man["files"] == {
        "state.json": len('{"step": 0}'),
        os.path.join("shards", "part-0.bin"): 1024,
    }
    parent = os.path.dirname(ckpt.path)
    assert not [p for p in os.listdir(parent) if p.startswith(".tmp-")]
    assert validate_checkpoint_dir(ckpt.path)

    # deterministic replay: overwriting the same index wins atomically
    (src / "state.json").write_text('{"step": 0, "replayed": true}')
    ckpt2 = storage.persist_checkpoint_dir(str(src), 0)
    assert ckpt2.path == ckpt.path
    assert validate_checkpoint_dir(ckpt.path)
    assert "replayed" in open(os.path.join(ckpt.path, "state.json")).read()


def test_torn_checkpoint_falls_back_to_previous(tmp_path):
    """Resume skips torn checkpoint dirs: a size-mismatched file and a
    missing file both fail manifest validation, and latest_consistent()
    walks back to the newest intact checkpoint instead of crashing."""
    from ray_tpu.train.checkpoint_manager import CheckpointManager
    from ray_tpu.train.config import CheckpointConfig
    from ray_tpu.train.storage import StorageContext, validate_checkpoint_dir

    storage = StorageContext(str(tmp_path / "results"), "exp", "trial")
    storage.ensure_trial_dir()
    mgr = CheckpointManager(storage, CheckpointConfig())
    for i in range(3):
        src = tmp_path / f"src{i}"
        src.mkdir()
        (src / "state.json").write_text('{"step": %d}' % i)
        ckpt = storage.persist_checkpoint_dir(str(src), i)
        mgr.register_persisted(ckpt.path, {"step": i})

    p2 = storage.checkpoint_path(2)
    open(os.path.join(p2, "state.json"), "w").close()  # torn: size mismatch
    assert not validate_checkpoint_dir(p2)
    p1 = storage.checkpoint_path(1)
    os.remove(os.path.join(p1, "state.json"))  # torn: file missing
    assert not validate_checkpoint_dir(p1)

    best = mgr.latest_consistent()
    assert best is not None
    assert best.path == storage.checkpoint_path(0)
    assert len(mgr.checkpoints) == 1  # torn entries dropped from tracking
    # a manifest-less (legacy) dir is trusted as-is
    legacy = tmp_path / "legacy_ckpt"
    legacy.mkdir()
    (legacy / "state.json").write_text("{}")
    assert validate_checkpoint_dir(str(legacy))


def test_train_pg_ready_timeout_flag_names_bundle(local_ray):
    """WorkerGroup.start honours train_pg_ready_timeout_s (replacing the
    old hardcoded 60 s wait) and the error names the bundle the cluster
    cannot satisfy."""
    from ray_tpu.core.config import config
    from ray_tpu.exceptions import PlacementGroupError
    from ray_tpu.train import ScalingConfig
    from ray_tpu.train.worker_group import WorkerGroup

    os.environ["RTPU_TRAIN_PG_READY_TIMEOUT_S"] = "1.5"
    config.reload()
    try:
        # 2-CPU cluster, 3 one-CPU bundles: each bundle fits, the gang
        # never will — the PG stays pending until the configured timeout
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
        wg = WorkerGroup(ScalingConfig(num_workers=3))
        t0 = time.monotonic()
        with pytest.raises(PlacementGroupError) as ei:
            wg.start()
        assert time.monotonic() - t0 < 30.0  # the hardcoded 60 s is gone
        msg = str(ei.value)
        assert "train_pg_ready_timeout_s" in msg
        assert "1.5" in msg
        assert "CPU" in msg, msg  # names the bundle it cannot place
    finally:
        os.environ.pop("RTPU_TRAIN_PG_READY_TIMEOUT_S", None)
        config.reload()
