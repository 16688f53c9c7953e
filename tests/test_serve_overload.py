"""Graceful degradation under overload: SLO-aware admission, priority
classes, typed backpressure, and the serve_overload chaos harness.

Reference test model: serve overload/backpressure suites — admission
rejects at the door with a typed error carrying retry hints, lower
priority classes shed strictly earlier, deadlines shed both at
admission (estimated-wait check) and mid-flight (stream close + cancel),
and the HTTP proxy maps the typed errors to 429/503 instead of a bare
500. The chaos test drives sustained mixed-priority traffic at a
many-x arrival/capacity ratio and asserts the degradation is graceful:
high-priority latency stays bounded, low-priority sheds are typed, and
no replica crashes or deadlocks.
"""

from __future__ import annotations

import json
import os
import pickle
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.core import fault_injection, runtime_context
from ray_tpu.core.config import config
from ray_tpu.exceptions import BackpressureError, ReplicaUnavailableError
from ray_tpu.serve import qos
from tests.conftest import own_runtime


@pytest.fixture(scope="module")
def serve_ray():
    with own_runtime(4):
        yield


# ------------------------------------------------------------ typed errors


def test_backpressure_error_pickle_roundtrip():
    e = BackpressureError("shed it", deployment="dep", queue_depth=7,
                          estimated_wait_s=1.25, retry_after_s=2.5)
    e2 = pickle.loads(pickle.dumps(e))
    assert isinstance(e2, BackpressureError)
    assert e2.deployment == "dep"
    assert e2.queue_depth == 7
    assert e2.estimated_wait_s == 1.25
    assert e2.retry_after_s == 2.5
    # the detail suffix must not double across pickle cycles
    assert str(e2) == str(e)
    assert str(pickle.loads(pickle.dumps(e2))) == str(e)
    assert isinstance(e2, ray_tpu.exceptions.RayTpuError)


def test_replica_unavailable_error_pickle_roundtrip():
    e = ReplicaUnavailableError(deployment="gone")
    e2 = pickle.loads(pickle.dumps(e))
    assert isinstance(e2, ReplicaUnavailableError)
    assert e2.deployment == "gone"
    assert "gone" in str(e2)
    assert str(pickle.loads(pickle.dumps(e2))) == str(e)


# ------------------------------------------------------------- qos units


def test_priority_normalization():
    assert qos.normalize_priority(None) == 1
    assert qos.normalize_priority("low") == 0
    assert qos.normalize_priority("HIGH") == 2
    assert qos.normalize_priority(0) == 0
    assert qos.normalize_priority(99) == 2  # clamped
    assert qos.normalize_priority(-3) == 0
    with pytest.raises(ValueError):
        qos.normalize_priority("urgent")


def test_depth_limits_tiered():
    # low sheds strictly earliest, high gets the full depth
    assert qos.depth_limit(9, 0) == 3
    assert qos.depth_limit(9, 1) == 6
    assert qos.depth_limit(9, 2) == 9
    # tiny caps keep a floor of 1 for every class
    assert qos.depth_limit(1, 0) == 1
    # 0 = unbounded for everyone
    assert qos.depth_limit(0, 0) == 0


def test_ttft_estimator():
    est = qos.TtftEstimator(alpha=0.5)
    assert est.estimated_wait_s(10, 2) == 0.0  # no data: admit
    est.observe("r1", 1.0)
    est.observe("r2", 3.0)
    assert est.mean_ttft_s() == pytest.approx(2.0)
    # wait scales with depth spread over replicas
    assert est.estimated_wait_s(2, 2) == pytest.approx(2.0 * 2.0)
    est.drop_replica("r2")
    assert est.mean_ttft_s() == pytest.approx(1.0)
    samples = est.drain_samples()
    assert sorted(samples) == [1000.0, 3000.0]
    assert est.drain_samples() == []  # drained
    assert qos.retry_after_hint(0.0, 0.0) == pytest.approx(0.1)
    assert qos.retry_after_hint(1.0, 4.0) == pytest.approx(4.0)


def test_qos_from_config_validation_and_flag_fallback():
    out = qos.qos_from_config({"priority": "high", "max_queue_depth": 5,
                               "deadline_s": 2.0})
    assert out == {"priority": 2, "max_queue_depth": 5, "deadline_s": 2.0}
    with pytest.raises(ValueError):
        qos.qos_from_config({"deadline_s": 0})
    with pytest.raises(ValueError):
        qos.qos_from_config({"max_queue_depth": -1})
    # unset depth falls back to the serve_max_queue_depth flag
    os.environ["RTPU_SERVE_MAX_QUEUE_DEPTH"] = "4"
    try:
        config.reload()
        assert qos.qos_from_config({})["max_queue_depth"] == 4
    finally:
        del os.environ["RTPU_SERVE_MAX_QUEUE_DEPTH"]
        config.reload()
    assert qos.qos_from_config({})["max_queue_depth"] == 0


def test_deployment_qos_validation():
    with pytest.raises(ValueError):
        serve.deployment(priority="urgent")(lambda x: x)
    with pytest.raises(ValueError):
        serve.deployment(deadline_s=-1.0)(lambda x: x)
    d = serve.deployment(priority="low", max_queue_depth=3)(lambda x: x)
    assert d.config["priority"] == "low"
    with pytest.raises(ValueError):
        d.options(max_queue_depth=-2)


def test_serve_demand_signal_pure():
    from ray_tpu.autoscaler_v2 import serve_demand_signal

    now = 1000.0
    fresh = {"ts": now - 1.0, "deployments": {
        "a": {"queue_depth": 3, "ttft_p50_ms": 10, "ttft_p99_ms": 90},
        "b": {"queue_depth": 2, "ttft_p50_ms": 5, "ttft_p99_ms": 20},
    }}
    assert serve_demand_signal(fresh, 0.0, now) == (5, False)
    # SLO breach on any deployment's p99
    assert serve_demand_signal(fresh, 50.0, now) == (5, True)
    assert serve_demand_signal(fresh, 100.0, now) == (5, False)
    # stale payloads are NOT demand (controller gone != load forever)
    assert serve_demand_signal(fresh, 50.0, now + 30.0) == (0, False)
    # malformed payloads never throw
    assert serve_demand_signal(None, 50.0, now) == (0, False)
    assert serve_demand_signal({"ts": "x"}, 50.0, now) == (0, False)
    assert serve_demand_signal({"ts": now, "deployments": [1]},
                               50.0, now) == (0, False)


# ----------------------------------------------------- admission control


def test_depth_shedding_by_priority_class(serve_ray):
    @serve.deployment(name="gated", max_queue_depth=6)
    def gated(dt):
        time.sleep(dt)
        return dt

    handle = serve.run(gated)
    router = handle._get_router()
    # saturate the full (high-class) depth with slow requests
    futs = [handle.options(priority="high").remote(0.8) for _ in range(6)]
    assert router._depth == 6
    # low's share is max(1, 6*1//3) = 2 — already far past it
    with pytest.raises(BackpressureError) as ei:
        handle.options(priority="low").remote(0.0)
    assert ei.value.deployment == "gated"
    assert ei.value.queue_depth == 6
    assert ei.value.retry_after_s >= 0.1
    # normal (share 4) sheds too; high (share 6) is at its own cap
    with pytest.raises(BackpressureError):
        handle.options(priority="normal").remote(0.0)
    with pytest.raises(BackpressureError):
        handle.options(priority="high").remote(0.0)
    # the saturating requests complete and depth drains to zero
    assert [f.result(timeout=60) for f in futs] == [0.8] * 6
    deadline = time.monotonic() + 5
    while router._depth and time.monotonic() < deadline:
        time.sleep(0.02)
    assert router._depth == 0
    # capacity freed: low priority admits again
    assert handle.options(priority="low").remote(0.0).result(timeout=30) \
        == 0.0


def test_deadline_admission_uses_ttft_estimate(serve_ray):
    @serve.deployment(name="slowest", deadline_s=30.0)
    def slowest(x):
        return x

    handle = serve.run(slowest)
    router = handle._get_router()
    # seed the estimator: mean TTFT 5s makes a 0.5s deadline infeasible
    router._ttft.observe("seed", 5.0)
    with pytest.raises(BackpressureError) as ei:
        handle.options(deadline_s=0.5).remote(1)
    assert "estimated wait" in str(ei.value)
    assert ei.value.estimated_wait_s > 0.5
    # a feasible deadline still admits
    assert handle.options(deadline_s=60.0).remote(7).result(timeout=30) == 7


def test_replica_sheds_expired_deadline_and_stays_healthy(serve_ray):
    @serve.deployment(name="queuey")
    def queuey(dt):
        time.sleep(dt)
        return dt

    handle = serve.run(queuey)
    blocker = handle.remote(0.6)
    time.sleep(0.2)  # ensure the blocker reaches the replica first
    # admitted (no TTFT data yet -> estimate 0) but queued behind the
    # blocker; its wall deadline expires before execution starts, so the
    # REPLICA sheds it — and the typed error arrives unwrapped
    late = handle.options(deadline_s=0.1).remote(0.0)
    with pytest.raises(BackpressureError) as ei:
        late.result(timeout=30)
    assert "deadline expired before execution" in str(ei.value)
    assert blocker.result(timeout=30) == 0.6
    # the shed never touched the callable: replica serves on
    assert handle.remote(0.05).result(timeout=30) == 0.05


def test_qos_off_admission_is_noop(serve_ray):
    @serve.deployment(name="plain")
    def plain(x):
        return x * 3

    handle = serve.run(plain)
    router = handle._get_router()
    assert router._qos["max_queue_depth"] == 0
    assert router._qos["deadline_s"] is None
    assert not router._report_enabled  # no QoS, no autoscaling: no loop
    futs = [handle.remote(i) for i in range(8)]
    assert [f.result(timeout=30) for f in futs] == [i * 3 for i in range(8)]
    # the depth counter is never touched on the QoS-off path
    assert router._depth == 0
    assert router._report_thread is None


# --------------------------------------------------------- http mapping


def test_http_proxy_429_with_retry_after(serve_ray):
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment(name="qecho", max_queue_depth=4)
    def qecho(x):
        return x

    serve.run(qecho)
    proxy = start_http()
    host, port = proxy.address
    try:
        # deterministic overload: the serve_overload fault site sheds at
        # admission without needing real queue pressure
        fault_injection.inject("serve_overload", "shed", "qecho", times=1)
        req = urllib.request.Request(
            f"http://{host}:{port}/qecho",
            data=json.dumps({"args": [1]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())
        assert body["type"] == "BackpressureError"
        assert body["deployment"] == "qecho"
        assert body["retry_after_s"] >= 0.1
        # the site disarms after firing once: next request serves fine
        out = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert out["result"] == 1
    finally:
        fault_injection.clear()
        stop_http()


def test_http_proxy_503_when_no_replicas(serve_ray):
    from ray_tpu.serve.http_proxy import start_http, stop_http

    serve.start()
    proxy = start_http()
    host, port = proxy.address
    os.environ["RTPU_SERVE_REPLICA_WAIT_S"] = "0.5"
    try:
        config.reload()
        req = urllib.request.Request(
            f"http://{host}:{port}/never_deployed",
            data=json.dumps({"args": []}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["type"] == "ReplicaUnavailableError"
        assert body["deployment"] == "never_deployed"
    finally:
        del os.environ["RTPU_SERVE_REPLICA_WAIT_S"]
        config.reload()
        stop_http()


def test_stream_mid_flight_shed_closes_cleanly(serve_ray):
    from ray_tpu.serve.http_proxy import start_http, stop_http

    @serve.deployment(name="ticker")
    def ticker(n):
        for i in range(n):
            time.sleep(0.1)
            yield i

    handle = serve.run(ticker)
    proxy = start_http()
    host, port = proxy.address
    try:
        req = urllib.request.Request(
            f"http://{host}:{port}/ticker",
            data=json.dumps({"args": [50], "stream": True,
                             "deadline_s": 0.45}).encode(),
            headers={"Content-Type": "application/json"})
        # admitted (estimate is below the deadline), so the stream opens
        # with 200 and sheds TYPED mid-flight when the deadline expires
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            events = []
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith("data: "):
                    events.append(line[len("data: "):])
        assert events, "stream produced no events"
        assert events[-1] != "[DONE]"  # shed, not completed
        last = json.loads(events[-1])
        assert last["type"] == "BackpressureError"
        assert "deadline" in last["error"]
        assert last["retry_after_s"] >= 0.1
        # some tokens streamed before the shed
        assert any("tokens" in json.loads(e) for e in events[:-1])
        # the shed released its depth slot and the replica still serves
        router = handle._get_router()
        deadline = time.monotonic() + 5
        while router._depth and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router._depth == 0
        assert list(handle.stream(3)) == [0, 1, 2]
    finally:
        stop_http()


# ------------------------------------------------- demand signal plumbing


def test_controller_publishes_serve_demand(serve_ray):
    from ray_tpu.serve.controller import (CONTROLLER_NAME,
                                          SERVE_DEMAND_KEY)

    @serve.deployment(name="demandy", max_queue_depth=16)
    def demandy(x):
        time.sleep(0.05)
        return x

    handle = serve.run(demandy)
    futs = [handle.remote(i) for i in range(10)]
    [f.result(timeout=30) for f in futs]
    # the router's report loop (0.5s) feeds the controller; the
    # controller's publish loop (0.5s) feeds the KV key
    core = runtime_context.get_core_or_none()
    payload = None
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        payload = core.kv_op("get", SERVE_DEMAND_KEY)
        if (isinstance(payload, dict)
                and "demandy" in payload.get("deployments", {})
                and payload["deployments"]["demandy"]["ttft_p99_ms"] > 0):
            break
        time.sleep(0.2)
    assert isinstance(payload, dict), "serve:demand never published"
    dep = payload["deployments"]["demandy"]
    assert dep["ttft_p99_ms"] >= dep["ttft_p50_ms"] > 0
    assert dep["queue_depth"] >= 0
    assert payload["ts"] == pytest.approx(time.time(), abs=30)
    # status() surfaces the same QoS telemetry
    st = serve.status()["demandy"]
    assert "queue_depth" in st and "ttft_p99_ms" in st
    # old-signature load reports (no depth/ttft args) stay accepted
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    ray_tpu.get(controller.report_load.remote("demandy", "legacy-r", 2),
                timeout=30)


# ------------------------------------------------------------ chaos test


def test_chaos_sustained_mixed_overload(serve_ray):
    """Sustained mixed-priority traffic at many times capacity, with
    heavy-tail service times and injected serve_overload sheds mixed in:
    high-priority latency stays bounded, every shed is typed, and the
    replicas neither crash nor deadlock."""

    @serve.deployment(name="mixed", num_replicas=2, max_queue_depth=8)
    def mixed(dt):
        time.sleep(dt)
        return dt

    handle = serve.run(mixed)
    # a slice of deterministic chaos: some admissions shed by injection
    # even when the queue has room (the typed path must absorb both)
    fault_injection.inject("serve_overload", "shed", "mixed", times=5)
    try:
        # heavy-tail service times: mostly fast, a thick slow tail
        def service_time(i):
            if i % 13 == 0:
                return 0.6
            if i % 5 == 0:
                return 0.25
            return 0.03

        results = {"low": [], "normal": [], "high": []}
        sheds = {"low": 0, "normal": 0, "high": 0}
        lock = threading.Lock()
        inflight = []
        # ~150 requests over ~1s against ~2 replicas * ~10/s capacity:
        # an order-of-magnitude arrival/capacity ratio, sustained
        for i in range(50):
            for prio in ("low", "normal", "high"):
                t_submit = time.monotonic()
                try:
                    fut = handle.options(priority=prio).remote(
                        service_time(i))
                except BackpressureError as e:
                    # lowest-priority-first shedding, typed at admission
                    assert e.deployment == "mixed"
                    assert e.retry_after_s >= 0.1
                    with lock:
                        sheds[prio] += 1
                    continue

                def reap(fut=fut, prio=prio, t0=t_submit):
                    try:
                        fut.result(timeout=90)
                        with lock:
                            results[prio].append(time.monotonic() - t0)
                    except BackpressureError:
                        with lock:
                            sheds[prio] += 1

                t = threading.Thread(target=reap, daemon=True)
                t.start()
                inflight.append(t)
            time.sleep(0.02)
        for t in inflight:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in inflight), \
            "requests deadlocked under overload"

        total_shed = sum(sheds.values())
        total_done = sum(len(v) for v in results.values())
        assert total_shed > 0, "overload never shed"
        assert total_done > 0, "overload completed nothing"
        # graceful degradation: the low class sheds at least as often as
        # the high class (tiered admission shares)
        assert sheds["low"] >= sheds["high"]
        assert results["high"], "no high-priority request completed"
        # bounded high-priority latency: admitted work rides a queue
        # capped at max_queue_depth, so p99 stays far under the
        # unbounded-queue blowup (50 reqs * 0.6s tail would be ~30s)
        p99_high = qos.percentile(results["high"], 99)
        assert p99_high < 15.0, f"high-priority p99 {p99_high:.1f}s"
        # zero replica crashes: both replicas alive and serving
        st = serve.status()["mixed"]
        assert st["running"] == 2
        assert handle.options(priority="low").remote(0.01).result(
            timeout=30) == 0.01
        # depth fully drained (no leaked admission tokens)
        router = handle._get_router()
        deadline = time.monotonic() + 10
        while router._depth and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router._depth == 0
    finally:
        fault_injection.clear()
