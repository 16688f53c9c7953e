"""Control-plane services: state API, jobs, autoscaler, workflows,
metrics, timeline, CLI.

Reference test model: python/ray/tests/test_state_api.py,
dashboard/modules/job/tests, autoscaler fake-node tests,
workflow/tests, test_metrics_agent.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.core import runtime_context
from ray_tpu.core.cluster.fixture import Cluster


# ------------------------------------------------------------- state (local)


def test_state_api_embedded():
    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    os.environ["RTPU_TASK_EVENTS_ENABLED"] = "1"
    from ray_tpu.core.config import config
    config.reload()
    try:
        ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
        from ray_tpu import state

        @ray_tpu.remote
        class A:
            def f(self):
                return 1

        a = A.remote()
        ray_tpu.get(a.f.remote())

        @ray_tpu.remote
        def t(x):
            return x

        ray_tpu.get([t.remote(i) for i in range(5)])

        s = state.state_summary()
        assert len(s["nodes"]) == 1
        assert any(x["state"] == "ALIVE" for x in s["actors"])
        assert s["objects"]["tracked"] > 0
        assert state.cluster_resources()["CPU"] == 2

        # timeline captured the task events
        trace = ray_tpu.timeline()
        assert len(trace) >= 6
        assert all(ev["ph"] == "X" and ev["dur"] >= 0 for ev in trace)

        # cross-process span propagation: a task submitted FROM a task
        # records its submitter as parent_task_id
        @ray_tpu.remote
        def child():
            return 1

        @ray_tpu.remote
        def parent():
            return ray_tpu.get(child.remote())

        ray_tpu.get(parent.remote())
        trace = ray_tpu.timeline()
        parents = {ev["args"]["task_id"]: ev["args"]["parent_task_id"]
                   for ev in trace if ev["cat"] != "span"}
        linked = [p for p in parents.values() if p is not None]
        assert linked and all(p in parents for p in linked), (
            "nested task missing parent span link")
    finally:
        os.environ.pop("RTPU_TASK_EVENTS_ENABLED", None)
        config.reload()
        core = runtime_context.get_core_or_none()
        if core is not None:
            core.shutdown()
        runtime_context.set_core(prev)


# ------------------------------------------------------ cluster-side services


@pytest.fixture()
def cluster2():
    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=2, num_workers_per_node=2)
    c.wait_for_nodes(2)
    yield c
    c.shutdown()
    runtime_context.set_core(prev)


def test_state_api_cluster(cluster2):
    cluster2.connect()
    from ray_tpu import state

    @ray_tpu.remote
    def f():
        return os.getpid()

    ray_tpu.get([f.remote() for _ in range(4)], timeout=60)
    nodes = state.list_nodes()
    assert len(nodes) == 2 and all(n["state"] == "ALIVE" for n in nodes)
    s = state.state_summary()
    assert s["cluster_resources"]["CPU"] == 4
    assert isinstance(state.list_workers(), list)


def test_job_submission(cluster2):
    from ray_tpu.core.cluster.rpc import RpcClient
    from ray_tpu.job import JobAgent, JobStatus, JobSubmissionClient

    gcs_addr = cluster2.gcs_address
    os.environ["RTPU_CLUSTER_AUTHKEY"] = cluster2.authkey.hex()
    try:
        agent_gcs = RpcClient(gcs_addr, cluster2.authkey)
        agent = JobAgent(agent_gcs, gcs_addr, "test-agent",
                         log_dir="/tmp/ray_tpu_test_jobs")
        client = JobSubmissionClient(f"{gcs_addr[0]}:{gcs_addr[1]}",
                                     authkey=cluster2.authkey)
        job_id = client.submit_job(
            entrypoint=f"{sys.executable} -c \"print('hello from job')\"")
        status = client.wait_until_finished(job_id, timeout=60)
        assert status == JobStatus.SUCCEEDED
        assert "hello from job" in client.get_job_logs(job_id)

        # failing job surfaces FAILED
        bad = client.submit_job(
            entrypoint=f"{sys.executable} -c \"import sys; sys.exit(3)\"")
        assert client.wait_until_finished(bad, timeout=60) == JobStatus.FAILED
        assert client.get_job_info(bad)["returncode"] == 3
        assert len(client.list_jobs()) == 2
        client.close()
        agent.close()
    finally:
        os.environ.pop("RTPU_CLUSTER_AUTHKEY", None)


def test_cluster_timeline_aggregates_nodes():
    """ray_tpu.timeline() in CLUSTER mode merges every node's flag-gated
    task-event log, tids prefixed by node (reference: ray.timeline over
    per-raylet events)."""
    from ray_tpu.core.config import config

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    os.environ["RTPU_TASK_EVENTS_ENABLED"] = "1"
    config.reload()
    c = None
    try:
        c = Cluster(num_nodes=2, num_workers_per_node=1,
                    node_resources=[{"ta": 4}, {"tb": 4}])
        c.wait_for_nodes(2)
        c.connect()

        @ray_tpu.remote
        def t(x):
            return x

        ray_tpu.get([t.options(resources={"ta": 1}).remote(i)
                     for i in range(3)], timeout=60)
        ray_tpu.get([t.options(resources={"tb": 1}).remote(i)
                     for i in range(3)], timeout=60)
        trace = ray_tpu.timeline()
        assert len(trace) >= 6
        # events from BOTH nodes, tid carrying the node prefix
        # (the driver's own spans, cat "span", carry no node prefix)
        prefixes = {ev["tid"].split(":")[0] for ev in trace
                    if ev["cat"] != "span"}
        assert len(prefixes) == 2, prefixes
    finally:
        os.environ.pop("RTPU_TASK_EVENTS_ENABLED", None)
        config.reload()
        if c is not None:
            c.shutdown()
        runtime_context.set_core(prev)


def test_worker_proc_stats_and_stack_dump(rt):
    """Observability depth: per-worker CPU/RSS from /proc in the state
    API (reference: reporter_agent.py:428) and live py-spy-style stack
    dumps of a BUSY worker showing the executing function."""
    import time as _time

    from ray_tpu import state

    @ray_tpu.remote
    def spin_for(seconds):
        deadline = _time.time() + seconds
        while _time.time() < deadline:
            sum(range(1000))
        return "done"

    ref = spin_for.remote(6.0)
    _time.sleep(1.0)

    workers = state.list_workers()
    assert workers, "no workers listed"
    stats_seen = [w for w in workers if "rss_bytes" in w]
    assert stats_seen, f"no proc stats in worker rows: {workers}"
    assert all(w["rss_bytes"] > 1 << 20 for w in stats_seen)
    # second sample gives a cpu_percent delta; the spinning worker burns
    state.list_workers()
    _time.sleep(0.5)
    busy = [w for w in state.list_workers()
            if w.get("cpu_percent", 0) > 10]
    assert busy, "spinning worker shows no CPU"

    dumps = state.stack_dump()
    assert dumps, "no stack dumps collected"
    assert any("spin_for" in text for text in dumps.values()), (
        f"busy worker's executing frame missing: {list(dumps)}")
    assert ray_tpu.get(ref, timeout=60) == "done"


def test_gce_tpu_provider_mocked_api():
    """GCE TPU-VM provider against a mocked REST API (the reference tests
    its cloud providers the same way, python/ray/tests/aws/): launch
    creates a TPU node with the join-cluster startup script, listing
    filters by cluster label and live state, terminate deletes the node
    whose endpoint matches the departing cluster address."""
    from ray_tpu.autoscaler import GceTpuNodeProvider

    calls = []
    nodes = {}

    def transport(method, url, body=None):
        calls.append((method, url, body))
        if method == "POST":
            name = url.split("nodeId=")[1]
            full = f"projects/p/locations/z/nodes/{name}"
            nodes[full] = dict(body, name=full, state="READY",
                               networkEndpoints=[
                                   {"ipAddress": f"10.0.0.{len(nodes)+1}"}])
            return {"name": f"operations/{name}"}
        if method == "GET":
            return {"nodes": list(nodes.values())}
        if method == "DELETE":
            path = url.split("/v2/")[1]
            nodes[path]["state"] = "DELETING"
            return {}
        raise AssertionError(f"unexpected {method}")

    p = GceTpuNodeProvider("p", "z", ("10.9.9.9", 7000),
                           accelerator_type="v5litepod-4",
                           authkey_hex="cafe", transport=transport)
    p.launch_node()
    p.launch_node()
    method, url, body = calls[0]
    assert method == "POST" and "nodeId=rtpu-node-1" in url
    assert body["acceleratorType"] == "v5litepod-4"
    script = body["metadata"]["startup-script"]
    assert "--address 10.9.9.9:7000" in script
    assert "RTPU_CLUSTER_AUTHKEY=cafe" in script
    # label value sanitized to the GCE charset (no dots)
    assert body["labels"]["rtpu-cluster"] == "10-9-9-9-7000"

    live = p.non_terminated_nodes()
    assert len(live) == 2

    # a node from ANOTHER cluster must be invisible
    nodes["projects/p/locations/z/nodes/other"] = {
        "name": "projects/p/locations/z/nodes/other", "state": "READY",
        "labels": {"rtpu-cluster": "elsewhere"}, "networkEndpoints": []}
    assert len(p.non_terminated_nodes()) == 2

    # terminate by cluster address -> DELETE of the matching TPU node
    p.terminate_node(("10.0.0.1", 9999))
    deletes = [c for c in calls if c[0] == "DELETE"]
    assert len(deletes) == 1 and "rtpu-node-1" in deletes[0][1]
    assert len(p.non_terminated_nodes()) == 1


def test_autoscaler_scales_up_and_down():
    from ray_tpu.autoscaler import AutoscalerMonitor, SubprocessNodeProvider

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=1, num_workers_per_node=1)
    try:
        c.wait_for_nodes(1)
        c.connect()
        os.environ["RTPU_CLUSTER_AUTHKEY"] = c.authkey.hex()
        provider = SubprocessNodeProvider(c.gcs_address, num_workers=1)
        monitor = AutoscalerMonitor(
            c.gcs_address, provider, min_nodes=1, max_nodes=2,
            scale_up_after_ticks=2, scale_down_after_ticks=6,
            tick_s=0.25, authkey=c.authkey)

        @ray_tpu.remote
        def slow():
            time.sleep(0.6)
            return os.getpid()

        # flood one 1-worker node: queue builds -> a second node launches
        refs = [slow.remote() for _ in range(16)]
        deadline = time.monotonic() + 60
        from ray_tpu.core.cluster.rpc import RpcClient
        gcs = RpcClient(c.gcs_address, c.authkey)
        while time.monotonic() < deadline:
            view = gcs.call(("list_nodes", True))
            if len(view["nodes"]) >= 2:
                break
            time.sleep(0.25)
        assert len(gcs.call(("list_nodes", True))["nodes"]) >= 2, \
            f"no scale-up: {monitor.events}"
        ray_tpu.get(refs, timeout=120)

        # drain: the extra node idles out and is terminated
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            view = gcs.call(("list_nodes", True))
            if len(view["nodes"]) == 1:
                break
            time.sleep(0.5)
        assert len(gcs.call(("list_nodes", True))["nodes"]) == 1, \
            f"no scale-down: {monitor.events}"
        monitor.stop()
        gcs.close()
        for p in provider.procs:
            if p.poll() is None:
                p.kill()
    finally:
        os.environ.pop("RTPU_CLUSTER_AUTHKEY", None)
        c.shutdown()
        runtime_context.set_core(prev)


# --------------------------------------------------------------- workflows


def test_workflow_run_and_resume(tmp_path, rt):
    from ray_tpu import workflow

    calls = str(tmp_path / "calls")
    os.makedirs(calls)

    @workflow.step
    def double(x):
        open(os.path.join(calls, f"double_{x}"), "a").write("1")
        return x * 2

    @workflow.step
    def add(a, b):
        open(os.path.join(calls, "add"), "a").write("1")
        return a + b

    storage = str(tmp_path / "wf")
    dag = add.bind(double.bind(3), double.bind(4))
    out = workflow.run(dag, workflow_id="w1", storage=storage)
    assert out == 14
    assert workflow.get_status("w1", storage=storage) == "SUCCESSFUL"

    # resume: everything checkpointed, nothing re-executes
    out2 = workflow.resume("w1", storage=storage)
    assert out2 == 14
    assert open(os.path.join(calls, "add")).read() == "1"

    # rebuilding the same graph reuses checkpoints (deterministic ids)
    dag2 = add.bind(double.bind(3), double.bind(4))
    assert workflow.run(dag2, workflow_id="w1", storage=storage) == 14
    assert open(os.path.join(calls, "add")).read() == "1"
    assert [w["workflow_id"] for w in workflow.list_all(storage=storage)] \
        == ["w1"]


def test_workflow_failure_and_partial_resume(tmp_path, rt):
    from ray_tpu import workflow

    storage = str(tmp_path / "wf2")
    marker = str(tmp_path / "ok")

    @workflow.step
    def stage1():
        return 10

    @workflow.step
    def flaky(x):
        if not os.path.exists(marker):
            raise RuntimeError("not yet")
        return x + 1

    dag = flaky.bind(stage1.bind())
    with pytest.raises(Exception):
        workflow.run(dag, workflow_id="w2", storage=storage)
    assert workflow.get_status("w2", storage=storage) == "FAILED"

    open(marker, "w").close()
    # resume executes only the failed suffix; stage1's checkpoint is reused
    assert workflow.resume("w2", storage=storage) == 11
    assert workflow.get_status("w2", storage=storage) == "SUCCESSFUL"


# ----------------------------------------------------------------- metrics


def test_metrics_registry_and_http():
    from ray_tpu import metrics

    c = metrics.Counter("rtpu_test_total", "test counter", ("kind",))
    c.inc(tags={"kind": "a"})
    c.inc(2, tags={"kind": "a"})
    g = metrics.Gauge("rtpu_test_gauge", "test gauge")
    g.set(7.5)
    h = metrics.Histogram("rtpu_test_hist", "test hist",
                          boundaries=(1, 10))
    h.observe(0.5)
    h.observe(5)
    h.observe(50)

    text = metrics.REGISTRY.render()
    assert 'rtpu_test_total{kind="a"} 3.0' in text
    assert "rtpu_test_gauge 7.5" in text
    assert 'rtpu_test_hist_bucket{le="+Inf"} 3' in text

    host, port = metrics.start_metrics_server()
    try:
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10).read().decode()
        assert "rtpu_test_gauge 7.5" in body
    finally:
        metrics.stop_metrics_server()


# --------------------------------------------------------------------- CLI


def test_cli_start_status_job_stop(tmp_path):
    env = dict(os.environ)
    env["RTPU_CLUSTER_AUTHKEY"] = os.urandom(16).hex()
    # isolated session file via HOME trick is overkill; just run the flow
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "start", "--head",
         "--num-workers", "1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "GCS address" in out.stdout
    try:
        status = subprocess.run(
            [sys.executable, "-m", "ray_tpu", "status"],
            capture_output=True, text=True, env=env, timeout=120)
        assert status.returncode == 0, status.stderr
        assert "nodes: 1" in status.stdout

        job = subprocess.run(
            [sys.executable, "-m", "ray_tpu", "job", "submit", "--wait",
             "--", sys.executable, "-c", "print(6*7)"],
            capture_output=True, text=True, env=env, timeout=120)
        assert job.returncode == 0, job.stderr
        assert "SUCCEEDED" in job.stdout and "42" in job.stdout

        nodes = subprocess.run(
            [sys.executable, "-m", "ray_tpu", "state", "nodes"],
            capture_output=True, text=True, env=env, timeout=120)
        assert nodes.returncode == 0
        assert len(json.loads(nodes.stdout)) == 1
    finally:
        stop = subprocess.run(
            [sys.executable, "-m", "ray_tpu", "stop"],
            capture_output=True, text=True, env=env, timeout=60)
        assert stop.returncode == 0, stop.stderr


# ------------------------------------------------------ pubsub / env / dash


def test_gcs_pubsub():
    from ray_tpu.core.cluster.gcs import GcsServer
    from ray_tpu.core.cluster.rpc import RpcClient

    gcs = GcsServer(authkey=b"k2")
    try:
        c = RpcClient(gcs.address, b"k2")
        assert c.call(("poll", "chan1", 0, 0.1)) == []
        seq = c.call(("publish", "chan1", {"x": 1}))
        assert seq == 1
        msgs = c.call(("poll", "chan1", 0, 1.0))
        assert msgs == [(1, {"x": 1})]
        # long-poll wakes on publish from another connection
        import threading
        got = []
        t = threading.Thread(target=lambda: got.extend(
            c.call(("poll", "chan1", 1, 10.0))))
        t.start()
        time.sleep(0.2)
        RpcClient(gcs.address, b"k2").call(("publish", "chan1", "late"))
        t.join(10)
        assert got == [(2, "late")]
        c.close()
    finally:
        gcs.close()


def test_runtime_env_env_vars(rt):
    @ray_tpu.remote(runtime_env={"env_vars": {"RTPU_TEST_VAR": "abc"}})
    def read_env():
        return os.environ.get("RTPU_TEST_VAR")

    @ray_tpu.remote
    def read_env_plain():
        return os.environ.get("RTPU_TEST_VAR")

    assert ray_tpu.get(read_env.remote()) == "abc"
    # env is restored after the task: cover every pool worker so the one
    # that ran read_env is definitely observed again
    vals = ray_tpu.get([read_env_plain.remote() for _ in range(16)])
    assert all(v is None for v in vals)

    @ray_tpu.remote(runtime_env={"env_vars": {"ACTOR_SCOPE": "yes"}})
    class EnvActor:
        def get(self):
            return os.environ.get("ACTOR_SCOPE")

    a = EnvActor.remote()
    assert ray_tpu.get(a.get.remote()) == "yes"


def test_dashboard_lite(rt):
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    host, port = start_dashboard()
    try:
        page = urllib.request.urlopen(
            f"http://{host}:{port}/", timeout=15).read().decode()
        assert "ray_tpu" in page and "resources" in page
        api = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/api/state", timeout=15).read())
        assert "nodes" in api and "cluster_resources" in api

        # time-series view: the sampler fills the history ring and
        # /api/metrics/history serves JSON for the app's canvas charts
        # (reference role: dashboard/modules/metrics Grafana panels)
        from ray_tpu import dashboard as _d
        for _ in range(3):
            _d._history._sample()
        hist = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/api/metrics/history",
            timeout=15).read())
        assert len(hist["t"]) >= 3
        assert "tasks_running" in hist["series"]
        assert "nodes_alive" in hist["series"]
        # "/" is the client-rendered app shell: it fetches both APIs
        # and draws tabs + canvas charts client-side
        assert "/api/state" in page and "canvas" in page
        assert "setInterval(tick" in page
    finally:
        stop_dashboard()


# ---------------------------------------------------------------------------
# worker log capture + streaming (reference: log_monitor.py, log_to_driver)
# ---------------------------------------------------------------------------


def test_worker_logs_captured_and_streamed(rt):
    import io
    import time

    from ray_tpu import state
    from ray_tpu.core import runtime_context
    from ray_tpu.core.log_monitor import LogMonitor

    @rt.remote
    def shout(x):
        print(f"log-line-{x}")
        return x

    assert rt.get(shout.remote(7)) == 7
    core = runtime_context.get_core()

    # the line landed in some worker-*.out file
    deadline = time.time() + 5
    found = False
    while time.time() < deadline and not found:
        for f in state.list_logs():
            if f["name"].endswith(".out") and f["size"] > 0:
                if "log-line-7" in state.get_log(f["name"]):
                    found = True
                    break
        time.sleep(0.05)
    assert found, state.list_logs()

    # a monitor over the same dir streams it with the worker prefix
    sink = io.StringIO()
    mon = LogMonitor(core.log_dir, sink=sink, interval_s=0.05)
    mon.poll_once()
    out = sink.getvalue()
    assert "log-line-7" in out
    assert "(worker=" in out and " out) " in out


def test_get_log_rejects_path_escape(rt):
    from ray_tpu import state

    import pytest as _pytest
    with _pytest.raises(ValueError):
        state.get_log("../../etc/passwd")


# ---------------------------------------------------------------------------
# runtime_env: working_dir / py_modules code shipping
# ---------------------------------------------------------------------------


def test_runtime_env_working_dir(rt, tmp_path):
    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "data.txt").write_text("payload-42")
    (proj / "helper.py").write_text("VALUE = 1234\n")

    @rt.remote(runtime_env={"working_dir": str(proj)})
    def read_rel():
        import os
        import helper  # importable: working_dir is on sys.path

        with open("data.txt") as f:
            return f.read(), helper.VALUE, os.getcwd()

    content, val, cwd = rt.get(read_rel.remote())
    assert content == "payload-42" and val == 1234
    assert "/packages/" in cwd  # extracted into the session package cache

    # per-task scope: a plain task afterwards is back in the original cwd
    @rt.remote
    def plain_cwd():
        import os
        return os.getcwd()

    assert "/packages/" not in rt.get(plain_cwd.remote())


def test_runtime_env_py_modules(rt, tmp_path):
    mod = tmp_path / "shippedmod"
    mod.mkdir()
    (mod / "__init__.py").write_text("def f():\n    return 'shipped'\n")

    @rt.remote(runtime_env={"py_modules": [str(mod)]})
    def use_mod():
        import shippedmod
        return shippedmod.f()

    assert rt.get(use_mod.remote()) == "shipped"

    # module is NOT importable without the runtime_env
    @rt.remote
    def no_mod():
        try:
            import shippedmod  # noqa: F401
            return True
        except ImportError:
            return False

    assert rt.get(no_mod.remote()) is False


def test_runtime_env_actor_scoped_working_dir(rt, tmp_path):
    proj = tmp_path / "aproj"
    proj.mkdir()
    (proj / "cfg.txt").write_text("actor-cfg")

    @rt.remote(runtime_env={"working_dir": str(proj)})
    class Reader:
        def read(self):
            with open("cfg.txt") as f:
                return f.read()

    r = Reader.remote()
    assert rt.get(r.read.remote()) == "actor-cfg"
    assert rt.get(r.read.remote()) == "actor-cfg"  # persists across calls
    rt.kill(r)


def test_runtime_env_package_determinism(tmp_path):
    from ray_tpu.core.runtime_env import package_path

    d = tmp_path / "pkg"
    d.mkdir()
    (d / "a.py").write_text("x = 1\n")
    h1, z1 = package_path(str(d))
    h2, z2 = package_path(str(d))
    assert h1 == h2 and z1 == z2
    (d / "a.py").write_text("x = 2\n")
    h3, _ = package_path(str(d))
    assert h3 != h1


def test_runtime_env_nested_submission(rt, tmp_path):
    """A task can itself submit a runtime_env task: the worker packages
    the path and uploads it to the core's package store."""
    proj = tmp_path / "nested"
    proj.mkdir()
    (proj / "n.txt").write_text("nested-ok")

    @rt.remote
    def outer(path):
        @rt.remote(runtime_env={"working_dir": path})
        def inner():
            with open("n.txt") as f:
                return f.read()

        return rt.get(inner.remote())

    assert rt.get(outer.remote(str(proj))) == "nested-ok"


def test_runtime_env_missing_package_fails_task_not_worker(rt):
    """A task whose runtime_env names an unknown package must fail with a
    clean error while the worker (and the rest of the pool) lives on."""

    @rt.remote(runtime_env={"working_dir_pkg": "deadbeef" * 4})
    def doomed():
        return 1

    @rt.remote
    def fine():
        return 2

    with pytest.raises(Exception, match="not found in the package"):
        rt.get(doomed.remote(), timeout=60)
    # pool is still healthy
    assert rt.get(fine.remote(), timeout=60) == 2


def test_workflow_waits_for_http_event(tmp_path):
    """workflow.wait_for_event + HTTPEventProvider (reference:
    python/ray/workflow/http_event_provider.py): the DAG blocks at the
    event node until an external HTTP POST delivers the payload; the
    payload checkpoints durably, so a resume returns without re-waiting
    (and without a provider)."""
    import json
    import threading
    import urllib.request

    import ray_tpu
    from ray_tpu import workflow
    from ray_tpu.core import runtime_context

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
    provider = workflow.HTTPEventProvider()
    try:
        @workflow.step
        def enrich(payload, factor):
            return {"value": payload["value"] * factor, "src": "enriched"}

        dag = enrich.bind(
            workflow.wait_for_event("approval", provider, timeout=60),
            10)

        result_box = []
        t = threading.Thread(
            target=lambda: result_box.append(workflow.run(
                dag, workflow_id="wf_event", storage=str(tmp_path))),
            daemon=True)
        t.start()
        time.sleep(0.5)
        assert not result_box, "workflow finished before the event?!"

        host, port = provider.address
        req = urllib.request.Request(
            f"http://{host}:{port}/event/approval",
            data=json.dumps({"value": 7}).encode(),
            headers={"Content-Type": "application/json"})
        assert urllib.request.urlopen(req, timeout=10).status == 200

        t.join(timeout=60)
        assert result_box and result_box[0] == {"value": 70,
                                                "src": "enriched"}
        assert workflow.get_status("wf_event",
                                   storage=str(tmp_path)) == "SUCCESSFUL"

        # resume: the event payload is checkpointed — no provider needed,
        # no re-wait
        out = workflow.resume("wf_event", storage=str(tmp_path))
        assert out == {"value": 70, "src": "enriched"}
    finally:
        provider.close()
        core = runtime_context.get_core_or_none()
        if core is not None:
            core.shutdown()
        runtime_context.set_core(prev)


def test_workflow_run_async(tmp_path, rt):
    from ray_tpu import workflow

    @workflow.step
    def slow_double(x):
        time.sleep(0.3)
        return x * 2

    @workflow.step
    def add(a, b):
        return a + b

    dag = add.bind(slow_double.bind(3), slow_double.bind(4))
    h = workflow.run_async(dag, workflow_id="wf_async",
                           storage=str(tmp_path))
    assert not h.done()
    assert h.result(timeout=60) == 14
    assert h.done()
    assert workflow.get_status("wf_async",
                               storage=str(tmp_path)) == "SUCCESSFUL"


def test_workflow_cancel_and_management_actor(tmp_path, rt):
    """The management surface (reference: workflow_access.py): runs
    register with a named detached actor; cancel() aborts an in-flight
    workflow from OUTSIDE the driving thread; get_output() reads a
    finished workflow's result from storage alone."""
    from ray_tpu import workflow

    @workflow.step
    def crawl(x):
        time.sleep(30)  # long enough that cancel lands mid-step
        return x

    h = workflow.run_async(crawl.bind(7), workflow_id="wf_cancel",
                           storage=str(tmp_path))
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if workflow.get_status("wf_cancel",
                                   storage=str(tmp_path)) == "RUNNING":
                break
        except KeyError:
            pass
        time.sleep(0.05)
    workflow.cancel("wf_cancel", storage=str(tmp_path))
    with pytest.raises(workflow.WorkflowCancellationError):
        h.result(timeout=60)
    assert workflow.get_status("wf_cancel",
                               storage=str(tmp_path)) == "CANCELED"

    # registry: the run registered with the named management actor, and
    # cancel with NO storage argument resolves it through the registry
    mgr = rt.get_actor(workflow.access.MANAGEMENT_ACTOR_NAME)
    ids = [r["workflow_id"] for r in
           rt.get(mgr.list_registered.remote())]
    assert "wf_cancel" in ids

    # get_output: result read back from storage, not the driver thread
    @workflow.step
    def quick(x):
        return x * 3

    workflow.run(quick.bind(5), workflow_id="wf_out",
                 storage=str(tmp_path))
    assert workflow.get_output("wf_out", storage=str(tmp_path)) == 15

    workflow.delete("wf_cancel", storage=str(tmp_path))
    ids = [r["workflow_id"] for r in
           rt.get(mgr.list_registered.remote())]
    assert "wf_cancel" not in ids


class _FakeCloud:
    """Deterministic provider double: launches become visible only when
    the test advances the 'cloud', so REQUESTED->ALLOCATED timing is
    controlled; terminations disappear likewise."""

    def __init__(self, fail_launches: int = 0):
        self.pending = 0           # requested, not yet visible
        self.visible = 0           # provider-listed instances
        self.terminated = []
        self._fail = fail_launches

    def launch_node(self):
        if self._fail > 0:
            self._fail -= 1
            raise RuntimeError("quota")
        self.pending += 1

    def satisfy(self, n=None):
        take = self.pending if n is None else min(n, self.pending)
        self.pending -= take
        self.visible += take

    def terminate_node(self, address):
        self.terminated.append(tuple(address))
        self.visible -= 1

    def non_terminated_nodes(self):
        return [{"i": i} for i in range(self.visible)]


def test_instance_manager_fsm_and_reconciler():
    """Autoscaler v2 (reference: autoscaler/v2/instance_manager/): every
    instance walks the audited FSM QUEUED->REQUESTED->ALLOCATED->
    RAY_RUNNING->RAY_STOPPING->TERMINATED; illegal jumps raise; request
    timeouts retry through ALLOCATION_FAILED with a bounded budget."""
    from ray_tpu.autoscaler_v2 import (InstanceManager, InstanceStatus,
                                       InvalidTransitionError, Reconciler)

    cloud = _FakeCloud()
    im = InstanceManager()
    rec = Reconciler(im, cloud, request_timeout_s=0.2,
                     max_allocation_retries=1)

    # scale 0 -> 2: instances queue and get requested
    rec.reconcile(2, cloud.visible, [])
    assert len(im.instances(InstanceStatus.REQUESTED)) == 2
    assert cloud.pending == 2

    # the cloud honors one launch; one instance allocates
    cloud.satisfy(1)
    rec.reconcile(2, cloud.visible, [])
    assert len(im.instances(InstanceStatus.ALLOCATED)) == 1

    # a ray node heartbeats at an address: ALLOCATED -> RAY_RUNNING
    rec.reconcile(2, cloud.visible, [("10.0.0.1", 7000)])
    running = im.instances(InstanceStatus.RAY_RUNNING)
    assert [i.address for i in running] == [("10.0.0.1", 7000)]

    # the second request times out -> ALLOCATION_FAILED -> requeued;
    # the NEXT pass re-requests it (reconcilers converge over passes)
    time.sleep(0.25)
    rec.reconcile(2, cloud.visible, [("10.0.0.1", 7000)])
    inst2 = [i for i in im.instances() if not i.address][0]
    states = [s for s, _ in inst2.history]
    assert "ALLOCATION_FAILED" in states and states[-1] == "QUEUED"
    rec.reconcile(2, cloud.visible, [("10.0.0.1", 7000)])
    assert inst2.history[-1][0] == "REQUESTED"

    # second timeout exhausts the retry budget -> TERMINATED
    time.sleep(0.25)
    rec.reconcile(2, cloud.visible, [("10.0.0.1", 7000)])
    states = [s for s, _ in inst2.history]
    assert states[-1] == "TERMINATED"
    assert states.count("ALLOCATION_FAILED") == 2

    # scale down to 0: the running instance drains then terminates
    rec.reconcile(0, cloud.visible, [("10.0.0.1", 7000)])
    assert cloud.terminated == [("10.0.0.1", 7000)]
    rec.reconcile(0, cloud.visible, [])
    assert [i.status for i in im.instances()
            if i.address] == [InstanceStatus.TERMINATED]

    # FSM rejects illegal jumps
    fresh = im.create_instance()
    with pytest.raises(InvalidTransitionError):
        im.transition(fresh, InstanceStatus.RAY_RUNNING)

    # full history is timestamped, first state QUEUED
    done = [i for i in im.instances() if i.address][0]
    assert [s for s, _ in done.history] == [
        "QUEUED", "REQUESTED", "ALLOCATED", "RAY_RUNNING",
        "RAY_STOPPING", "TERMINATED"]


def test_instance_storage_versioned_cas():
    from ray_tpu.autoscaler_v2 import Instance, InstanceStorage

    st = InstanceStorage()
    a = Instance(instance_id="a")
    assert st.upsert(a)
    _, v = st.get_all()
    assert st.upsert(Instance(instance_id="b"), expected_version=v)
    # a stale writer (read before 'b' landed) must lose, not clobber
    assert not st.upsert(Instance(instance_id="c"), expected_version=v)
    insts, _ = st.get_all()
    assert set(insts) == {"a", "b"}


def test_autoscaler_v2_provider_failure_keeps_queued():
    from ray_tpu.autoscaler_v2 import (InstanceManager, InstanceStatus,
                                       Reconciler)

    cloud = _FakeCloud(fail_launches=1)
    im = InstanceManager()
    rec = Reconciler(im, cloud)
    rec.reconcile(1, 0, [])
    # launch raised: the instance stays QUEUED for the next pass
    assert len(im.instances(InstanceStatus.QUEUED)) == 1
    rec.reconcile(1, 0, [])
    assert len(im.instances(InstanceStatus.REQUESTED)) == 1


def test_autoscaler_v2_end_to_end_real_nodes():
    """Autoscaler v2 drives REAL local node_server processes through the
    full instance FSM (VERDICT r4 item 9): a pending placement-group
    demand scales up; the first launch is dropped by a flaky provider
    and recovers through ALLOCATION_FAILED -> requeue; idleness scales
    back down and the node process exits."""
    import ray_tpu
    from ray_tpu.autoscaler import SubprocessNodeProvider
    from ray_tpu.autoscaler_v2 import AutoscalerV2, InstanceStatus
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster
    from ray_tpu.core.cluster.rpc import RpcClient

    class FlakyProvider(SubprocessNodeProvider):
        """Swallows the first launch: the cloud never delivers it, so
        the REQUESTED record must time out into ALLOCATION_FAILED and
        the retry path must produce the node."""

        def __init__(self, *a, fail_first: int = 1, **kw):
            super().__init__(*a, **kw)
            self.fails_left = fail_first
            self.launch_calls = 0

        def launch_node(self):
            self.launch_calls += 1
            if self.fails_left > 0:
                self.fails_left -= 1
                return  # accepted... and lost by the "cloud"
            super().launch_node()

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=1, num_workers_per_node=1,
                node_resources=[{"CPU": 1}])
    monitor = None
    provider = None
    try:
        c.wait_for_nodes(1)
        c.connect()
        os.environ["RTPU_CLUSTER_AUTHKEY"] = c.authkey.hex()
        provider = FlakyProvider(c.gcs_address, num_workers=1)
        monitor = AutoscalerV2(
            c.gcs_address, provider, min_nodes=0, max_nodes=1,
            tick_s=0.25, scale_up_after_ticks=2,
            scale_down_after_ticks=8, request_timeout_s=2.0,
            authkey=c.authkey)

        # a PG demanding more CPU than the head provides stays PENDING
        from ray_tpu.util import placement_group, remove_placement_group

        pg = placement_group([{"CPU": 1}] * 3, strategy="PACK")

        gcs = RpcClient(c.gcs_address, c.authkey)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if len(gcs.call(("list_nodes", True))["nodes"]) >= 2:
                break
            time.sleep(0.25)
        assert len(gcs.call(("list_nodes", True))["nodes"]) >= 2, (
            f"no scale-up: {monitor.events} "
            f"{[(i.instance_id[:6], i.status) for i in monitor.im.instances()]}")
        # the flaky first launch went through the failure FSM
        assert provider.launch_calls >= 2, provider.launch_calls
        failed = [s for inst in monitor.im.instances()
                  for s, _ in inst.history
                  if s == InstanceStatus.ALLOCATION_FAILED]
        assert failed, "first launch never went through ALLOCATION_FAILED"
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and not monitor.im.instances(InstanceStatus.RAY_RUNNING)):
            time.sleep(0.25)
        assert monitor.im.instances(InstanceStatus.RAY_RUNNING), (
            [i.status for i in monitor.im.instances()], monitor.events,
            [i.history for i in monitor.im.instances()])
        # the blocked demand is withdrawn; a fresh SPREAD PG now lands
        # across head + the autoscaled node
        remove_placement_group(pg)
        pg2 = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD")
        assert pg2.wait(timeout_seconds=60), "PG not placed on new node"
        remove_placement_group(pg2)

        # drain: target shrinks, the dynamic node is terminated, its
        # process exits
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if (len(gcs.call(("list_nodes", True))["nodes"]) == 1
                    and not provider.non_terminated_nodes()):
                break
            time.sleep(0.5)
        assert len(gcs.call(("list_nodes", True))["nodes"]) == 1, \
            f"no scale-down: {monitor.events}"
        assert not provider.non_terminated_nodes()
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and not monitor.im.instances(InstanceStatus.TERMINATED)):
            time.sleep(0.25)
        term = monitor.im.instances(InstanceStatus.TERMINATED)
        assert term, [i.status for i in monitor.im.instances()]
        gcs.close()
    finally:
        if monitor is not None:
            monitor.stop()
        if provider is not None:
            for p in provider.procs:
                if p.poll() is None:
                    p.kill()
        os.environ.pop("RTPU_CLUSTER_AUTHKEY", None)
        c.shutdown()
        runtime_context.set_core(prev)
