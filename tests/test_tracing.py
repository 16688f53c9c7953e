"""ray_tpu.util.tracing and what is wired through it: spans (nesting,
ids, self time, the two tiers), the profiler's host plane, the runtime's
and the trainer's set-up spans, ``timeline()``, ``trace_spans.json``, the
engine's tick counters and trace hook, the metrics registry's sources,
and the named scopes of the train step (metadata only)."""

import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import time

import pytest

from ray_tpu.core.config import config
from ray_tpu.tools.step_program import strip_metadata as _strip
from ray_tpu.util import tracing
from tests.engines import SMALLEST, drain, private_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def events_on():
    os.environ["RTPU_TASK_EVENTS_ENABLED"] = "1"
    config.reload()
    try:
        yield
    finally:
        os.environ.pop("RTPU_TASK_EVENTS_ENABLED", None)
        config.reload()


def _mine(prefix):
    return [e for e in tracing.chrome_events()
            if e["name"].startswith(prefix)]


def test_span_nesting_ids_and_self_time(events_on):
    with tracing.span("t1.outer", id="req-7", tokens=3) as outer:
        time.sleep(0.01)
        with tracing.span("t1.inner"):
            time.sleep(0.02)
        with tracing.span("t1.inner", id="other"):
            pass
    ev = {(e["name"], e["args"]["id"]): e for e in _mine("t1.")}
    o, i = ev[("t1.outer", "req-7")], ev[("t1.inner", "req-7")]
    assert i["args"]["parent"] == "t1.outer" and o["args"]["parent"] is None
    assert ("t1.inner", "other") in ev          # an explicit id wins
    assert o["args"]["tokens"] == 3
    assert o["dur"] >= 30e3 and i["dur"] >= 20e3
    # self time leaves the children out
    assert 10e3 <= o["args"]["self_us"] <= o["dur"] - i["dur"] + 1
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert abs(o["ts"] / 1e6 - time.time()) < 60     # the wall clock
    assert outer.dur_ns == pytest.approx(o["dur"] * 1e3)
    tot = tracing.totals()
    assert tot["t1.inner"]["count"] == 2
    assert tot["t1.outer"]["total_ns"] == outer.dur_ns


def test_events_are_kept_only_for_setup_spans_or_under_the_flag():
    assert not config.task_events_enabled
    with tracing.span("t2.hot"):
        pass
    with tracing.span("t2.setup", keep=True):
        pass
    tracing.mark("t2.mark", id="r")
    assert [e["name"] for e in _mine("t2.")] == ["t2.setup"]
    # the accumulators count either way
    assert tracing.totals()["t2.hot"]["count"] == 1
    assert tracing.totals()["t2.mark"]["count"] == 1


def test_span_module_never_imports_jax():
    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.span('a', keep=True):\n"
            "    pass\n"
            "assert tracing.chrome_events()[0]['name'] == 'a'\n"
            # without jax there is nothing to watch, and nothing is served
            "assert tracing.watch_jax() is False\n"
            "assert set(tracing.totals()) == {'a'}\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_record_obeys_the_kept_rule_and_nests_under_the_open_span():
    assert not config.task_events_enabled
    now = time.time()
    with tracing.span("t7.outer", id="run-3", keep=True) as outer:
        time.sleep(0.05)
        end = time.time()
        # as jax reports them: the inner one first, then the one around it
        tracing.record("t7.inner", end - 0.03, end - 0.01, keep=True, fun="g")
        tracing.record("t7.around", end - 0.04, end, keep=True, fun="f")
        tracing.record("t7.short", end - 0.0005, end, keep=False)
    ev = {e["name"]: e for e in _mine("t7.")}
    assert set(ev) == {"t7.outer", "t7.inner", "t7.around"}   # not the short
    inner, around, o = ev["t7.inner"], ev["t7.around"], ev["t7.outer"]
    assert inner["args"]["parent"] == around["args"]["parent"] == "t7.outer"
    assert inner["args"]["id"] == "run-3" and inner["args"]["fun"] == "g"
    assert inner["ph"] == "X" and inner["cat"] == "span"
    # wall clock in, wall clock out
    assert abs(around["ts"] - (end - 0.04) * 1e6) < 50
    assert abs(around["dur"] - 40e3) < 50 and now * 1e6 <= around["ts"]
    # nested records are credited once: to the one around them, and
    # through it to the open span
    assert abs(around["args"]["self_us"] - 20e3) < 50
    assert abs(o["args"]["self_us"] - (outer.dur_ns / 1e3 - 40e3 - 500)) < 50
    tot = tracing.totals()
    assert tot["t7.inner"]["count"] == 1
    assert abs(tot["t7.inner"]["total_ns"] - 20e6) < 50e3
    assert tot["t7.short"]["count"] == 1       # the accumulators count it


@pytest.mark.parametrize("after", [1, 4097], ids=["one", "a-buffer-and-one"])
def test_since_reads_what_a_call_wrote_into_full_buffers(after):
    """``tracing.since()`` on a ``_kept`` that is full at the mark:
    ``len(chrome_events())`` no longer grows there, and a position in
    the list, which is sorted by start, is no order of arrival. One span
    written after the mark comes back alone; of 4,097, which wrap the
    buffer past the mark, the newest 4,096 and nothing older."""
    kept = list(tracing._kept)
    try:
        for i in range(tracing._kept.maxlen):
            with tracing.span("t8.before", keep=True, n=i):
                pass
        assert len(tracing._kept) == tracing._kept.maxlen
        here = tracing.since()
        assert here.events() == []
        for i in range(after):
            with tracing.span("t8.after", keep=True, n=i):
                pass
        got = here.events()
        assert [e["name"] for e in got] == ["t8.after"] * min(
            after, tracing._kept.maxlen)
        assert [e["args"]["n"] for e in got] == list(range(after))[
            -tracing._kept.maxlen:]
        # a second mark reads from itself, the first still from its own
        assert tracing.since().events() == [] and here.events() == got
    finally:
        tracing._kept.clear()
        tracing._kept.extend(kept)


def test_record_goes_to_the_ring_under_the_flag(events_on):
    end = time.time()
    tracing.record("t7r.hot", end - 0.001, end, keep=False)
    assert [e["name"] for e in _mine("t7r.")] == ["t7r.hot"]


def _jax_events(fun):
    return [e for e in tracing.chrome_events()
            if e["name"].startswith("rtpu.jax.") and fun in e["args"]["fun"]]


@pytest.mark.parametrize("how", ["lower_compile", "plain_call"])
def test_watch_jax_records_each_programs_trace_lowering_and_compile(
        how, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    monkeypatch.setattr(tracing, "JAX_KEEP_S", 0.0)   # keep the short ones
    assert tracing.watch_jax() is True
    assert tracing.watch_jax() is True                # registers once
    assert monitoring.get_event_listeners().count(tracing._on_jax_event) == 1
    assert monitoring.get_event_time_span_listeners().count(
        tracing._on_jax_span) == 1
    assert monitoring.get_event_duration_listeners().count(
        tracing._on_jax_duration) == 1

    def body(x):
        return (jnp.sin(x) @ x).sum()

    body.__name__ = f"t8_{how}"
    f, x = jax.jit(body), jnp.ones((16, 16))
    c0 = tracing.totals()["rtpu.jax.compile"]["count"]
    with tracing.span("t8.setup", keep=True):
        if how == "lower_compile":
            f.lower(x).compile()
        else:
            f(x).block_until_ready()
    got = _jax_events(body.__name__)
    assert sorted(e["name"] for e in got) == [
        "rtpu.jax.compile", "rtpu.jax.lower", "rtpu.jax.trace"], got
    (cache,) = [e["args"]["cache"] for e in got
                if e["name"] == "rtpu.jax.compile"]
    assert cache in ("miss", "off")        # conftest gives a fresh cache
    assert tracing.totals()["rtpu.jax.compile"]["count"] == c0 + 1
    assert {e["args"]["parent"] for e in got} == {"t8.setup"}
    # a warmed-up program fires nothing
    if how == "plain_call":
        f(x).block_until_ready()
        assert len(_jax_events(body.__name__)) == 3
    assert isinstance(tracing.totals()["jax_cache_misses"], int)


def test_compile_span_says_whether_the_persistent_cache_had_the_program(
        tmp_path):
    code = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu import metrics
from ray_tpu.util import tracing
assert tracing.watch_jax()
def t9_step(x):
    return jnp.tanh(x @ x).sum()
jax.jit(t9_step)(jnp.ones((32, 32))).block_until_ready()
(ev,) = [e["args"] for e in tracing.chrome_events()
         if e["name"] == "rtpu.jax.compile" and "t9_step" in e["args"]["fun"]]
tot = tracing.totals()
print(json.dumps({"ev": ev, "hits": tot["jax_cache_hits"],
                  "misses": tot["jax_cache_misses"],
                  "served": metrics.REGISTRY.render()}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    runs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-3000:]
        runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert first["ev"]["cache"] == "miss" and "retrieval_s" not in first["ev"]
    assert first["hits"] == 0 and first["misses"] >= 1
    assert second["ev"]["cache"] == "hit"
    assert second["ev"]["retrieval_s"] > 0 and "saved_s" in second["ev"]
    assert second["hits"] >= 1 and second["misses"] == 0
    assert re.search(rf"^rtpu_span_jax_cache_hits {second['hits']}$",
                     second["served"], re.M)
    assert re.search(r"^rtpu_span_jax_cache_misses 0$", second["served"],
                     re.M)
    assert re.search(r"^rtpu_span_rtpu_jax_compile_count \d+$",
                     second["served"], re.M)
    assert re.search(r"^rtpu_span_rtpu_jax_compile_seconds_total [\d.e-]+$",
                     second["served"], re.M)


def test_span_lies_on_the_profilers_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    tracing.start_profile(str(tmp_path))
    try:
        with tracing.span("rtpu.t4.step", id="s1"):
            f(jnp.ones((64, 64))).block_until_ready()
    finally:
        tracing.stop_profile()
    # while the profiler ran the event was kept, with no flag
    assert [e["args"]["id"] for e in _mine("rtpu.t4.")] == ["s1"]
    (pb,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = [p for p in ProfileData.from_file(pb).planes
            if p.name == "/host:CPU"]
    names = {ev.name for p in host for ln in p.lines for ev in ln.events}
    assert "rtpu.t4.step" in names


def test_timeline_merges_spans_with_task_events(events_on):
    import ray_tpu
    from ray_tpu.core import runtime_context

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    try:
        ray_tpu.init(num_workers=1)

        @ray_tpu.remote
        def t(x):
            return x

        with tracing.span("rtpu.t5.batch", id="b1"):
            ray_tpu.get([t.remote(i) for i in range(3)])
        trace = ray_tpu.timeline()
        cats = {e["cat"] for e in trace}
        assert {"task", "span"} <= cats
        names = [e["name"] for e in trace if e["cat"] == "span"]
        assert {"rtpu.init", "rtpu.worker.spawn", "rtpu.t5.batch"} \
            <= set(names)
        assert trace == sorted(trace, key=lambda e: e["ts"])
        batch = next(e for e in trace if e["name"] == "rtpu.t5.batch")
        tasks = [e for e in trace if e["cat"] == "task"]
        # one clock: the tasks ran inside the span that waited for them
        assert all(batch["ts"] - 1e5 <= e["ts"] <= batch["ts"]
                   + batch["dur"] + 1e5 for e in tasks[-3:])
    finally:
        core = runtime_context.get_core_or_none()
        if core is not None:
            ray_tpu.shutdown()
        runtime_context.set_core(prev)
    assert "rtpu.runtime.shutdown" in [
        e["name"] for e in tracing.chrome_events()]


def test_fit_writes_the_gangs_spans(tmp_path):
    """``trace_spans.json`` of a CPU ``JaxTrainer.fit``: the driver's
    set-up spans in order and the workers' own, on one wall clock. (A fresh process: the
    runtime wants one.)"""
    code = f"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import ray_tpu
from ray_tpu import train
from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

def loop(config):
    import jax, jax.numpy as jnp
    from ray_tpu.util import tracing
    tracing.JAX_KEEP_S = 0.0        # a tiny program's trace is microseconds
    def t6_step(x):
        return (x * 2).sum()
    with tracing.span("t6.in_worker", keep=True):
        jax.jit(t6_step)(jnp.ones(8)).block_until_ready()
        train.report({{"x": 1.0}})

ray_tpu.init(num_workers=2)
r = JaxTrainer(loop, train_loop_config={{}},
               scaling_config=ScalingConfig(num_workers=2),
               jax_config=JaxConfig(platform="cpu"),
               run_config=RunConfig(name="run7", storage_path={str(tmp_path)!r})).fit()
assert r.error is None, r.error
ray_tpu.shutdown()
assert "jax" not in sys.modules
"""
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    with open(tmp_path / "run7" / "trace_spans.json") as f:
        ev = json.load(f)
    by = {}
    for e in ev:
        by.setdefault(e["name"], []).append(e)
    for name in ("rtpu.init", "rtpu.worker.spawn", "rtpu.train.start",
                 "rtpu.train.place", "rtpu.backend.on_start",
                 "rtpu.train.shutdown"):
        assert name in by, (name, sorted(by))
    start, place, on_start = (by[n][0] for n in (
        "rtpu.train.start", "rtpu.train.place", "rtpu.backend.on_start"))
    assert place["args"]["parent"] == on_start["args"]["parent"] \
        == "rtpu.train.start"
    assert start["args"]["id"].startswith("trial_")
    assert place["args"]["id"] == start["args"]["id"]
    end = lambda e: e["ts"] + e["dur"]  # noqa: E731
    assert start["ts"] <= place["ts"] and end(place) <= on_start["ts"] \
        and end(on_start) <= end(start) <= by["rtpu.train.shutdown"][0]["ts"]
    assert end(by["rtpu.init"][0]) <= start["ts"]
    assert ev == sorted(ev, key=lambda e: e["ts"])
    # every worker's ring came along, under its own pid, on the same clock
    workers = by["t6.in_worker"]
    assert len({e["pid"] for e in workers}) == 2
    assert start["pid"] not in {e["pid"] for e in workers}
    # ... on the same clock, each process's wall clock read through its
    # own anchor: the gang's function ends before the shutdown begins
    # (read 3 to 17 ms before it) and starts about when the start span
    # ends. The driver closes that span after it has sent the function
    # off, so on a busy CPU the span ends after the function began: by
    # 0.2 to 31.8 ms in eight readings with twelve busy processes on
    # eight cores, and by over 0.1 s once in a whole run (PR 54's run A,
    # which the 0.1 s that stood here failed). 0.5 s is fifteen times the
    # largest reading; a monotonic origin mistaken for the wall clock
    # would be off by the machine's uptime.
    late = max(max(end(start) - e["ts"],
                   end(e) - by["rtpu.train.shutdown"][0]["ts"])
               for e in workers)
    assert late <= 0.5e6, late
    # the backend watched jax before the train function ran: each
    # worker's program is there, traced, lowered and compiled, inside the
    # span that was open and before the session's first (kept) report
    for w in workers:
        mine = [e for e in ev if e["pid"] == w["pid"]
                and "t6_step" in e["args"].get("fun", "")]
        assert sorted(e["name"] for e in mine) == [
            "rtpu.jax.compile", "rtpu.jax.lower", "rtpu.jax.trace"], mine
        assert all(e["args"]["parent"] == "t6.in_worker" and w["ts"] <=
                   e["ts"] and end(e) <= end(w) for e in mine)
        assert mine[-1]["args"]["cache"] in ("hit", "miss", "off")
        (report,) = [e for e in by["rtpu.train.report"]
                     if e["pid"] == w["pid"]]
        assert max(end(e) for e in mine) <= report["ts"]
    assert start["pid"] not in {e["pid"] for e in ev
                                if e["name"].startswith("rtpu.jax.")}


def _generate(eng, n, tag):
    import numpy as np

    rng = np.random.default_rng(list(tag.encode()))   # prompts of its own
    reqs = [(f"{tag}{i}", [int(t) for t in rng.integers(1, 250, 20)], {})
            for i in range(n)]
    assert len(drain(eng, reqs)) == n
    return [rid for rid, _, _ in reqs]


COUNTERS = ("ticks", "inflight_depth_sum", "steps_dispatched", "steps",
            "admit_ns", "dispatch_ns", "reap_wait_ns", "sleep_ns",
            "slot_ticks_occupied", "slot_ticks_drained")


def test_engine_tick_counters_are_monotonic(paged_engine):
    eng = paged_engine
    s0 = eng.stats()
    _generate(eng, 6, "a")
    s1 = eng.stats()
    _generate(eng, 3, "b")
    time.sleep(0.05)
    s2 = eng.stats()
    for k in COUNTERS:
        assert s0[k] <= s1[k] <= s2[k], k
    for k in ("ticks", "steps", "steps_dispatched", "admit_ns",
              "dispatch_ns", "inflight_depth_sum", "slot_ticks_occupied"):
        assert s1[k] > s0[k], k
    assert s2["sleep_ns"] > s1["sleep_ns"] or s2["ticks"] > s1["ticks"]
    # dispatched counts at dispatch, steps at reap
    for s in (s1, s2):
        assert s["steps_dispatched"] >= s["steps"]
        assert s["slot_ticks_drained"] <= s["slot_ticks_occupied"]
    # idle again: every chunk dispatched has been reaped
    assert s2["steps_dispatched"] == s2["steps"]
    assert s2["inflight_chunks"] == 0
    # the tick's phases are spans, counted whether or not kept
    tot = tracing.totals()
    for name in ("rtpu.engine.admit", "rtpu.engine.dispatch",
                 "rtpu.engine.reap", "rtpu.engine.sleep",
                 "rtpu.engine.prefill", "rtpu.engine.submit",
                 "rtpu.engine.first_token", "rtpu.engine.finish"):
        assert tot[name]["count"] > 0, name
    assert tot["rtpu.engine.prefill"]["count"] >= 9 * 2   # 20 tokens / 16
    assert tot["rtpu.engine.finish"]["count"] >= 9


def test_engine_trace_hook_and_request_ids(paged_engine, tmp_path):
    eng = paged_engine
    assert eng.report()["trace"]["state"] == "off"
    eng.start_trace(str(tmp_path))

    def wait_for(state):
        deadline = time.time() + 120
        while time.time() < deadline:
            tr = eng.report()["trace"]
            assert tr["error"] is None, tr
            if tr["state"] == state:
                return tr
            time.sleep(0.05)
        raise AssertionError(f"trace never became {state}")

    assert wait_for("on")["dir"] == str(tmp_path)
    (rid,) = _generate(eng, 1, "traced")
    eng.stop_trace()
    wait_for("off")
    assert glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    # inside the profiler's window every span was kept; one request's
    # carry its id from submit to finish
    mine = [e["name"] for e in eng.timeline() if e["args"]["id"] == rid]
    assert mine[0] == "rtpu.engine.submit" and mine[-1] == \
        "rtpu.engine.finish"
    assert "rtpu.engine.first_token" in mine
    assert mine.count("rtpu.engine.prefill") == 2
    before = len(eng.timeline())
    _generate(eng, 1, "untraced")
    assert len(eng.timeline()) == before        # the ring is off again
    # the engine watched jax from its constructor on: the programs it
    # compiled before any request are on the timeline by name
    compiled = {e["args"]["fun"] for e in eng.timeline()
                if e["name"] == "rtpu.jax.compile"}
    assert any("prefill" in f for f in compiled), compiled
    assert any("decode" in f for f in compiled), compiled


def test_metrics_registry_serves_the_owners_counters():
    from ray_tpu import metrics
    from ray_tpu.serve.llm_engine import LLMEngine

    # private: an engine registers at construction, and the registry
    # serves the one constructed last
    with private_engine(LLMEngine, **SMALLEST) as eng:
        _generate(eng, 1, "scraped")
        text = metrics.REGISTRY.render()
        ticks = int(re.search(r"^rtpu_engine_ticks (\d+)$", text,
                              re.M).group(1))
        assert 0 < ticks <= eng.stats()["ticks"]
    assert "# TYPE rtpu_engine_inflight_depth_sum gauge" in text
    assert re.search(r"^rtpu_span_rtpu_engine_admit_count \d+$", text, re.M)
    assert re.search(r"^rtpu_span_rtpu_engine_admit_seconds_total [\d.e-]+$",
                     text, re.M)
    # a source is read at scrape time, and one that raises is left out
    box = {"n": 1}
    metrics.REGISTRY.register_source("rtpu_t9", lambda: dict(box, s="x"))
    assert "rtpu_t9_n 1\n" in metrics.REGISTRY.render()
    box["n"] = 5
    text = metrics.REGISTRY.render()
    assert "rtpu_t9_n 5\n" in text and "rtpu_t9_s" not in text
    metrics.REGISTRY.register_source("rtpu_t9", lambda: 1 / 0)
    assert "rtpu_t9" not in metrics.REGISTRY.render()


def test_train_session_serves_the_last_reported_moe_counters():
    """``rtpu_train_moe_*``: the last ``moe_rows_routed``,
    ``moe_rows_held`` (where a layer holds a share of its experts) and
    ``moe_rows_passed`` (the rows its passes took),
    ``moe_expert_load_max_over_mean`` and ``moe_router_bias_abs_max`` (a
    router balanced by a bias) a loop put into ``train.report``; a loop
    that reports neither serves neither."""
    from ray_tpu import metrics
    from ray_tpu.train.session import TrainContext, _TrainSession

    def loop():
        from ray_tpu import train
        train.report({"loss": 1.0})
        train.report({"loss": 0.9, "moe_rows_routed": 196608,
                      "moe_expert_load_max_over_mean": 4.5})
        train.report({"loss": 0.8, "moe_rows_routed": 196608,
                      "moe_rows_held": 12288, "moe_rows_passed": 13824,
                      "moe_expert_load_max_over_mean": 4.25,
                      "moe_router_bias_abs_max": 0.057,
                      "moe_other": 1})

    s = _TrainSession(loop, {}, TrainContext())
    from ray_tpu.train import session as session_mod
    saved, session_mod._session = session_mod._session, s
    try:
        s.start()
        assert s.next_result(timeout=10).metrics == {"loss": 1.0}
        s.next_result(timeout=10)       # the loop is in its second report
        assert "rtpu_train_moe" not in metrics.REGISTRY.render().split(
            "rtpu_train_reports")[0]
        s.next_result(timeout=10)
        assert s.next_result(timeout=10).done
        text = metrics.REGISTRY.render()
    finally:
        session_mod._session = saved
    assert "rtpu_train_moe_rows_routed 196608\n" in text
    assert "rtpu_train_moe_rows_held 12288\n" in text
    assert "rtpu_train_moe_rows_passed 13824\n" in text
    assert "rtpu_train_moe_expert_load_max_over_mean 4.25\n" in text
    assert "rtpu_train_moe_router_bias_abs_max 0.057\n" in text
    assert "rtpu_train_moe_other" not in text and "rtpu_train_loss" not in text
    assert "rtpu_train_reports 3\n" in text


def _train_step_text(scoped: bool = True, model: str = "llama",
                     **cfg_kw) -> str:
    """The optimized HLO of a tiny adamw train step, compiled for shapes
    alone."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama, mixtral, olmoe

    mod, cls = {"llama": (llama, llama.LlamaConfig),
                "mixtral": (mixtral, mixtral.MixtralConfig),
                "olmoe": (olmoe, olmoe.OlmoeConfig)}[model]
    cfg = cls.tiny(vocab_size=128, attn_impl="reference", **cfg_kw)
    params = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mod.loss_fn(cfg, p, batch))(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    plain = contextlib.nullcontext()
    saved = jax.named_scope
    if not scoped:
        jax.named_scope = lambda name: plain
    # as ensure_compile_cache sets it: without it the run's persistent
    # cache hands the unscoped step the scoped one's program, whenever
    # the compile was slow enough to be cached
    key = "jax_compilation_cache_include_metadata_in_key"
    saved_key = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt, batch).compile().as_text()
    finally:
        jax.named_scope = saved
        jax.config.update(key, saved_key)


def _instructions(text):
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", text, re.M))


def test_named_scopes_change_no_instruction_of_the_train_step():
    with_scopes, without = _train_step_text(True), _train_step_text(False)
    for scope in ("embed", "attn_qkv", "flash", "attn_out", "mlp",
                  "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', with_scopes), scope
    assert not re.search(r'op_name="[^"]*/mlp/', without)
    assert _strip(with_scopes) == _strip(without)
    assert _instructions(_strip(with_scopes)) > 200   # instructions are left
    assert with_scopes != without


def test_lfm2_train_step_names_its_scopes_and_the_bias_update():
    """The scopes ``benchmark/lib/hybrid_flops.py`` reads, in the compiled
    step of a model with convolution layers and a bias-balanced router:
    ``short_conv`` over ``conv_in`` / ``conv_mix`` / ``conv_out`` forward
    and backward, ``moe_bias_update`` inside ``moe_route`` after the
    optimizer, and the family's own; and they change no instruction."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import lfm2

    cfg = lfm2.Lfm2Config.tiny(vocab_size=128, attn_impl="reference",
                               experts_held=(0, 4))
    params = jax.eval_shape(lambda k: lfm2.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, lfm2.trainable(params))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}

    def text(scoped):
        # a step of its own: jit would hand the second trace the first's
        def step(params, opt, batch):
            owned = lfm2.trainable(params)
            (loss, aux), grads = jax.value_and_grad(
                lambda t: lfm2.loss_terms(cfg, lfm2.with_trainable(params, t),
                                          batch), has_aux=True)(owned)
            updates, opt = tx.update(grads, opt, owned)
            params = lfm2.with_trainable(params,
                                         optax.apply_updates(owned, updates))
            params = lfm2.update_router_bias(cfg, params, aux["expert_counts"])
            return params, opt, loss, lfm2.router_bias_abs_max(params)

        plain = contextlib.nullcontext()
        saved = jax.named_scope
        if not scoped:
            jax.named_scope = lambda name: plain
        # as _train_step_text: the cache's key must hold the metadata
        key = "jax_compilation_cache_include_metadata_in_key"
        saved_key = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            return jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt, batch).compile().as_text()
        finally:
            jax.named_scope = saved
            jax.config.update(key, saved_key)

    with_scopes, without = text(True), text(False)
    for scope in ("embed", "attn_qkv", "flash", "attn_out", "mlp",
                  "head_loss", "moe_route", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', with_scopes), scope
    for inner in ("conv_in", "conv_mix", "conv_out"):
        assert re.search(rf'op_name="[^"]*jvp\(short_conv\)/{inner}/',
                         with_scopes), inner
        assert re.search(
            rf'op_name="[^"]*transpose\(jvp\(short_conv\)\)/{inner}/',
            with_scopes), inner
    assert re.search(r'op_name="[^"]*/moe_route/moe_bias_update/',
                     with_scopes)
    assert not re.search(r'op_name="[^"]*(short_conv|moe_bias_update)',
                         without)
    assert _strip(with_scopes) == _strip(without)
    assert _instructions(_strip(with_scopes)) > 200


@pytest.mark.parametrize("model", ["llama", "olmoe", "mixtral"])
def test_a_plan_of_full_is_the_program_of_explicit_full(model):
    """``remat_policy="auto"`` where the device reports no memory limit
    (here: llama's plan and OLMoE's), and in a forward that has no plan
    (Mixtral's), is "full": the checkpoint names are inert, the routed
    layer's two among them, and the step compiles to the text of the
    explicit policy. A kept level is another program."""
    auto = _strip(_train_step_text(model=model, remat=True))
    assert auto == _strip(_train_step_text(model=model, remat=True,
                                           remat_policy="full"))
    assert _instructions(auto) > 200
    if model != "mixtral":
        assert auto != _strip(_train_step_text(model=model, remat=True,
                                               remat_policy="level4"))


def test_remat_auto_passes_where_a_set_policy_is_refused():
    """Mixtral's forward and the pipeline schedule have no plan: they run
    full remat and refuse a ladder level somebody set, through one helper
    with one message; the default is not refused, and neither is
    ``scan_layers=False`` (the walker honours it for every caller). The
    forwards that have a plan (OLMoE's since PR 33) take a set level."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, mixtral, olmoe
    from ray_tpu.parallel import MeshSpec, build_mesh

    for cfg in (mixtral.MixtralConfig.tiny(),
                mixtral.MixtralConfig.tiny(remat=True),
                mixtral.MixtralConfig.tiny(remat_policy="full"),
                mixtral.MixtralConfig.tiny(scan_layers=False)):
        assert cfg.remat_policy in ("auto", "full")
        assert llama.remat_level_without_plan(cfg) == (
            "full" if cfg.remat else None)
    cfg = llama.LlamaConfig.tiny(attn_impl="reference", remat=True)
    assert cfg.remat_policy == "auto"
    mesh = build_mesh(MeshSpec({"pp": 2}), devices=jax.devices()[:2])
    batch = {"tokens": jax.ShapeDtypeStruct((4, 33), jnp.int32)}
    n0 = len(_mine("rtpu.train.remat_plan"))
    loss = jax.eval_shape(
        lambda p, b: llama.loss_fn_pp(cfg, p, b, mesh, 2),
        llama.init_shapes(cfg), batch)
    assert loss.shape == ()
    assert len(_mine("rtpu.train.remat_plan")) == n0   # no plan to report
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)

    def refused():
        return pytest.raises(ValueError, match="this forward has no plan")

    for policy in ("level1", "level4"):
        moe = mixtral.MixtralConfig.tiny(remat_policy=policy)
        with refused():
            jax.eval_shape(lambda p, t: mixtral.forward(moe, p, t),
                           mixtral.init_params(moe, jax.random.PRNGKey(0)),
                           tokens)
        with refused():
            llama.loss_fn_pp(llama.LlamaConfig.tiny(remat_policy=policy),
                             None, batch, mesh, 2)
        planned = olmoe.OlmoeConfig.tiny(remat=True, remat_policy=policy)
        n0 = len(_mine("rtpu.train.remat_plan"))
        jax.eval_shape(lambda p, t: olmoe.forward(planned, p, t),
                       olmoe.init_params(planned, jax.random.PRNGKey(0)),
                       tokens)
        (ev,) = _mine("rtpu.train.remat_plan")[n0:]
        assert ev["args"]["level"] == policy


@pytest.mark.parametrize("model", ["llama", "olmoe", "laguna", "lfm2",
                                   "granite", "olmo_hybrid"])
def test_remat_plan_is_one_kept_span_of_a_traced_program(model):
    """Tracing a train step of a planned forward writes its remat plan
    once, as a kept span (no flag, no profiler window), with what it chose
    and why: a level and the bytes a layer keeps, by kind where the stack
    has kinds."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (granite, laguna, lfm2, llama, olmo_hybrid,
                                olmoe)

    mod, cls = {"llama": (llama, llama.LlamaConfig),
                "olmo_hybrid": (olmo_hybrid, olmo_hybrid.OlmoHybridConfig),
                "olmoe": (olmoe, olmoe.OlmoeConfig),
                "laguna": (laguna, laguna.LagunaConfig),
                "lfm2": (lfm2, lfm2.Lfm2Config),
                "granite": (granite, granite.GraniteConfig)}[model]
    assert not config.task_events_enabled
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}

    def trace(**kw):
        cfg = cls.tiny(attn_impl="reference", **kw)
        shapes = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        n0 = len(_mine("rtpu.train.remat_plan"))
        jax.eval_shape(jax.grad(lambda p, b: mod.loss_fn(cfg, p, b)),
                       shapes, batch)
        return shapes, _mine("rtpu.train.remat_plan")[n0:]

    assert trace()[1] == []
    shapes, (ev,) = trace(remat=True)
    args = dict(ev["args"])
    need = args.pop("need_bytes")
    assert args.pop("self_us") >= 0
    pattern = getattr(cls.tiny(), "pattern", None)
    kinds = dict.fromkeys(pattern) if pattern else None

    def by_kind(one, of=lambda kind: None):
        return {k: of(k) or one for k in kinds} if kinds else one

    assert args == {"id": None, "parent": None, "level": by_kind("full"),
                    "saved_bytes_per_layer": by_kind(0),
                    "capacity_bytes": None,
                    "layers": by_kind(2, pattern.count if pattern else None)}
    # at least the four copies of the parameters the train state holds
    assert need > 4 * 4 * llama.num_params(shapes)
    _, (ev,) = trace(remat=True, remat_policy="level3")
    assert ev["args"]["level"] == by_kind("level3")
    saved = ev["args"]["saved_bytes_per_layer"]
    assert all(v > 0 for v in (saved.values() if kinds else [saved]))


def test_flash_tiles_is_one_kept_span_of_a_traced_call():
    """A traced kernel call writes what its loops will do once, as a kept
    span (no flag, no profiler window): the blocks it chose, the tiles a
    head visits, those among them that apply the mask, and the share of
    the visited scores the mask keeps. The reference path has no tiles."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention, tile_plan

    assert not config.task_events_enabled

    def trace(seq_q, seq_k, **kw):
        q = jax.ShapeDtypeStruct((1, seq_q, 4, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, seq_k, 2, 128), jnp.bfloat16)
        n0 = len(_mine("rtpu.flash.tiles"))
        # forward and all three backward kernels of one call
        jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, interpret=True, **kw).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q, k, k)
        return [{k_: v for k_, v in e["args"].items()
                 if k_ not in ("id", "parent", "self_us")}
                for e in _mine("rtpu.flash.tiles")[n0:]]

    assert trace(1024, 1024, use_pallas=False) == []
    (causal,) = trace(4096, 4096, use_pallas=True)
    assert causal == {"seq_q": 4096, "seq_k": 4096, "block_q": 512,
                      "block_k": 512, "window": None, "head_dim": 128,
                      "tiles_visited": 36,
                      "tiles_edge": 8,
                      "kept_share": tile_plan(4096, 4096, 512, 512)[
                          "kept_share"]}
    # with blocks as wide as the band no tile of it is whole
    (band,) = trace(8192, 8192, use_pallas=True, window=512)
    assert (band["block_q"], band["block_k"], band["window"]) == (
        512, 512, 512)
    assert (band["tiles_visited"], band["tiles_edge"]) == (31, 31)
    assert 0.50 < band["kept_share"] < 0.51
    # the span carries the head size: LFM2's 64 beside the others' 128
    q64 = jax.ShapeDtypeStruct((1, 1024, 4, 64), jnp.bfloat16)
    n0 = len(_mine("rtpu.flash.tiles"))
    jax.eval_shape(lambda q: flash_attention(q, q, q, interpret=True,
                                             use_pallas=True), q64)
    (small,) = _mine("rtpu.flash.tiles")[n0:]
    assert small["args"]["head_dim"] == 64
    # explicit blocks stay the caller's; keys ahead of the queries
    (chunk,) = trace(512, 2048, use_pallas=True, block_q=256, block_k=512)
    assert (chunk["block_q"], chunk["block_k"]) == (256, 512)
    assert (chunk["tiles_visited"], chunk["tiles_edge"]) == (8, 2)


@pytest.mark.parametrize("form", ["xla_walk", "pallas"])
def test_scan_plan_is_one_kept_span_of_a_traced_call(form, monkeypatch):
    """A traced selective scan writes what it will do once, as a kept span
    (no flag, no profiler window), as ``rtpu.flash.tiles`` is written:
    sequence, chunk, chunks, how many a step takes, heads, head size,
    state, groups, the form that runs (XLA's walk on the CPU, the kernels
    on a TPU backend) and the float32 bytes it puts in HBM, the decay
    matrices' beside what all chunks at once would be."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    assert not config.task_events_enabled
    if form == "pallas":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def trace(seq, **kw):
        x = jax.ShapeDtypeStruct((1, seq, 64, 64), jnp.bfloat16)
        dt = jax.ShapeDtypeStruct((1, seq, 64), jnp.float32)
        bc = jax.ShapeDtypeStruct((1, seq, 1, 128), jnp.bfloat16)
        n0 = len(_mine("rtpu.ssm.scan_plan"))
        # forward and backward of one call
        jax.eval_shape(jax.grad(lambda x, dt, B, C: ssm.ssd_scan(
            x, dt, -jnp.ones((64,)), B, C, **kw)[0].astype(
                jnp.float32).sum(), argnums=(0, 1, 2, 3)), x, dt, bc, bc)
        return [{k_: v for k_, v in e["args"].items()
                 if k_ not in ("id", "parent", "self_us")}
                for e in _mine("rtpu.ssm.scan_plan")[n0:]]

    (cell,) = trace(32768)
    (short,) = trace(1000, chunk=128)
    shared = {"seq": 32768, "chunk": 256, "chunks": 128, "heads": 64,
              "head_dim": 64, "state": 128, "groups": 1, "form": form,
              "decay_bytes_all_chunks": 2 ** 31}
    if form == "pallas":
        steps = 128 // ssm.KERNEL_CHUNKS
        assert cell == dict(
            shared, walk=None, steps=steps, states_kept=steps,
            chunks_a_call=ssm.KERNEL_CHUNKS,
            heads_a_block=ssm.KERNEL_HEADS, decay_bytes_in_hbm=0,
            float32_bytes_in_hbm=(steps + 1) * 2 ** 21 + 5 * 2 ** 23
            + 2 ** 25)
        assert (short["form"], short["chunk"], short["chunks"]) == (
            "pallas", 128, 8)
        return
    assert cell == dict(shared, walk=8, steps=16, states_kept=16,
                        chunks_a_call=8, heads_a_block=None,
                        decay_bytes_in_hbm=2 ** 27,
                        float32_bytes_in_hbm=2 ** 27)
    assert (short["chunk"], short["chunks"], short["walk"],
            short["steps"]) == (128, 8, 8, 1)


def test_ssm_conv_plan_is_written_once_per_traced_call(monkeypatch):
    """``rtpu.ssm.conv_plan``: one kept span per traced call of
    ``causal_conv_silu``, forward and backward together. On the CPU it
    names XLA's form and no blocks; where the backend is a TPU, the blocks
    of ``ops/conv.taps_plan`` and the bytes their copies move: at the
    cell's shapes 8 blocks of 4,096 positions by 34 of 128 channels, 0.58
    GB forward (each block and the tile before it in, a block out) and
    0.89 GB backward beside the least 0.57 and 0.86."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    assert not config.task_events_enabled

    def trace(seq, dtype=jnp.bfloat16):
        u = jax.ShapeDtypeStruct((1, 8512, seq), dtype)
        w = jax.ShapeDtypeStruct((4352, 4), dtype)
        bias = jax.ShapeDtypeStruct((4352,), dtype)
        n0 = len(_mine("rtpu.ssm.conv_plan"))
        jax.eval_shape(jax.grad(lambda u, w, bias: sum(
            a.astype(jnp.float32).sum() for a in ssm.causal_conv_silu(
                u, w, bias, first=4096, sizes=(4096, 128, 128))),
            argnums=(0, 1, 2)), u, w, bias)
        return [{k_: v for k_, v in e["args"].items()
                 if k_ not in ("id", "parent", "self_us")}
                for e in _mine("rtpu.ssm.conv_plan")[n0:]]

    (cpu,) = trace(32768)
    assert cpu == {"seq": 32768, "channels": 4352, "taps": 4,
                   "form": "xla_taps", "block_rows": None,
                   "block_channels": None, "blocks": None,
                   "halo_rows": None, "bytes_moved_fwd": None,
                   "bytes_moved_bwd": None}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (cell,) = trace(32768)
    assert cell == {"seq": 32768, "channels": 4352, "taps": 4,
                    "form": "pallas", "block_rows": 4096,
                    "block_channels": 128, "blocks": 8 * 34,
                    "halo_rows": 128,
                    "bytes_moved_fwd": 8 * 34 * (2 * 4096 + 128) * 128 * 2,
                    "bytes_moved_bwd": 8 * 34 * 3 * (4096 + 128) * 128 * 2
                    + 5 * 4352 * 128 * 4}
    assert cell["bytes_moved_fwd"] < 1.02 * 2 * 32768 * 4352 * 2
    (short,) = trace(1000, jnp.float32)
    assert (short["block_rows"], short["blocks"]) == (1024, 34)
    # under a mesh the call keeps XLA's form, whatever the backend
    n0 = len(_mine("rtpu.ssm.conv_plan"))
    jax.eval_shape(lambda u, w, bias: ssm.causal_conv_silu(
        u, w, bias, mesh=object()), jax.ShapeDtypeStruct(
            (2, 256, 64), jnp.float32), jax.ShapeDtypeStruct(
            (256, 4), jnp.float32), jax.ShapeDtypeStruct((256,), jnp.float32))
    assert _mine("rtpu.ssm.conv_plan")[n0]["args"]["form"] == "xla_taps"


@pytest.mark.parametrize("op", ["ssm", "gdn"])
def test_train_session_serves_the_last_reported_scan_counter(op):
    """``rtpu_train_ssm_state_abs_max`` (a selective scan's) and
    ``rtpu_train_gdn_state_abs_max`` (a delta rule's): the last value a
    loop put into ``train.report`` beside the routed layers' counters; a
    loop that does not report it serves none."""
    from ray_tpu import metrics
    from ray_tpu.train.session import (STEP_COUNTERS, TrainContext,
                                       _TrainSession)

    assert {"ssm_state_abs_max", "gdn_state_abs_max"} <= set(STEP_COUNTERS)
    assert len(set(STEP_COUNTERS)) == len(STEP_COUNTERS) == 12
    assert {"kda_state_abs_max", "kda_log_decay_min"} <= set(STEP_COUNTERS)

    def loop():
        from ray_tpu import train
        train.report({"loss": 1.0})
        train.report({"loss": 0.9, f"{op}_state_abs_max": 7.25})
        train.report({"loss": 0.8, f"{op}_state_abs_max": 9.5,
                      f"{op}_other": 1})

    s = _TrainSession(loop, {}, TrainContext())
    from ray_tpu.train import session as session_mod
    saved, session_mod._session = session_mod._session, s
    try:
        s.start()
        s.next_result(timeout=10)       # the loop is in its second report
        assert f"rtpu_train_{op}" not in metrics.REGISTRY.render().split(
            "rtpu_train_reports")[0]
        s.next_result(timeout=10)
        s.next_result(timeout=10)
        assert s.next_result(timeout=10).done
        text = metrics.REGISTRY.render()
    finally:
        session_mod._session = saved
    assert f"rtpu_train_{op}_state_abs_max 9.5\n" in text
    assert f"rtpu_train_{op}_other" not in text


def test_granite_train_step_names_its_scopes_and_counts_its_state():
    """The optimized train step of a stack with scan layers carries the
    five ``ssm_*`` scopes beside the family's, and hands out the counter
    ``ssm_state_abs_max`` as one float32 scalar."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import granite

    cfg = granite.GraniteConfig.tiny(vocab_size=128, attn_impl="reference",
                                     remat=True)
    params = jax.eval_shape(lambda k: granite.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}

    def step(params, opt, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: granite.loss_terms(cfg, p, batch), has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt, loss,
                aux["ssm_state_abs_max"])

    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt, batch)
    assert lowered.out_info[3].shape == ()
    assert lowered.out_info[3].dtype == jnp.float32
    text = lowered.compile().as_text()
    for scope in ("embed", "ssm_in", "ssm_conv", "ssm_scan", "ssm_norm",
                  "ssm_out", "attn_qkv", "flash", "attn_out", "mlp",
                  "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope


def test_gdn_rule_plan_is_one_kept_span_of_a_traced_call(monkeypatch):
    """A traced ``gated_delta_rule`` writes what it will do once, as a kept
    span (no flag, no profiler window), as ``rtpu.ssm.scan_plan`` is written:
    sequence, chunk, chunks, the value heads, the key heads and how they were
    joined, a head's key and value sizes, the form that runs, what a step of it
    takes, the states a backward keeps and the float32 bytes the form puts in
    HBM beside what all chunks' pair matrices at once would: XLA's walk on the
    CPU and under a mesh, the kernels on a TPU backend without one."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    assert not config.task_events_enabled

    def trace(seq, **kw):
        qk = jax.ShapeDtypeStruct((1, seq, 30, 96), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, seq, 30, 192), jnp.bfloat16)
        gb = jax.ShapeDtypeStruct((1, seq, 30), jnp.float32)
        n0 = len(_mine("rtpu.gdn.rule_plan"))
        # forward and backward of one call
        jax.eval_shape(jax.grad(lambda q, k, v, g, beta: delta.
                                gated_delta_rule(q, k, v, g, beta, **kw)[0]
                                .astype(jnp.float32).sum(),
                                argnums=(0, 1, 2, 3, 4)), qk, qk, v, gb, gb)
        return [{k_: v_ for k_, v_ in e["args"].items()
                 if k_ not in ("id", "parent", "self_us")}
                for e in _mine("rtpu.gdn.rule_plan")[n0:]]

    (cell,) = trace(32768)
    one = 30 * 4 * (4 * 64 * 64 + 192 * 96)
    walk = {"seq": 32768, "chunk": 64, "chunks": 512, "walk": 8,
            "steps": 64, "heads": 30, "key_heads": 30, "joined": None,
            "key_dim": 96, "value_dim": 192, "form": "xla_walk",
            "decay": "head",
            "heads_a_block": None, "chunks_a_call": 8, "operands": None,
            "states_kept": 64, "float32_bytes_in_hbm": 8 * one,
            "float32_bytes_all_chunks": 512 * one}
    assert cell == walk
    (short,) = trace(1000, chunk=128)
    assert (short["chunk"], short["chunks"], short["walk"],
            short["steps"]) == (128, 8, 4, 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (kernels,) = trace(32768)
    assert kernels == dict(
        walk, form="pallas", walk=None, heads_a_block=15,
        operands="positions_last",
        float32_bytes_in_hbm=65 * 30 * 192 * 96 * 4 + 3 * 30 * 32768 * 4)
    (sharded,) = trace(32768, mesh=object())
    assert sharded == walk


def test_gdn_conv_plan_is_written_once_per_traced_mixer(monkeypatch):
    """``rtpu.gdn.conv_plan``: the taps of a delta-rule layer write
    ``causal_conv_silu``'s span under their own name, once a traced mixer,
    and ``rtpu.ssm.conv_plan`` not at all. Where the backend is a TPU, at
    the cell's shapes: 8 blocks of 4,096 positions by 180 of 64 channels
    (what divides 5,760, 2,880 and 5,760) over q, k and v's 11,520."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    assert not config.task_events_enabled
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    h, H, K, V = 3840, 30, 96, 192
    p = {"g_in": (h, 2 * H * (V + K) + 2 * H), "g_conv": (2 * H * K + H * V, 4),
         "g_dt_bias": (H,), "g_A_log": (H,), "g_norm": (V,),
         "g_out": (H * V, h)}
    p = {k_: jax.ShapeDtypeStruct(s, jnp.bfloat16) for k_, s in p.items()}
    n0, m0 = len(_mine("rtpu.gdn.conv_plan")), len(_mine("rtpu.ssm.conv_plan"))
    jax.eval_shape(jax.grad(lambda p, x: delta.gated_delta_mixer(
        x, p, heads=H, key_dim=K, value_dim=V)[0].astype(jnp.float32).sum()),
        p, jax.ShapeDtypeStruct((1, 32768, h), jnp.bfloat16))
    (ev,) = _mine("rtpu.gdn.conv_plan")[n0:]
    assert len(_mine("rtpu.ssm.conv_plan")) == m0
    args = ev["args"]
    assert (args["form"], args["seq"], args["channels"], args["taps"]) == (
        "pallas", 32768, 11520, 4)
    assert (args["block_rows"], args["block_channels"], args["blocks"]) == (
        4096, 64, 8 * 180)
    assert args["bytes_moved_fwd"] < 1.04 * 2 * 32768 * 11520 * 2


def test_olmo_hybrid_train_step_names_its_scopes_and_counts_its_state():
    """The optimized train step of a stack with delta-rule layers carries
    the five ``gdn_*`` scopes beside the family's, and hands out the
    counter ``gdn_state_abs_max`` as one float32 scalar."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark.cells import train_delta
    from ray_tpu.models import olmo_hybrid

    cfg = olmo_hybrid.OlmoHybridConfig.tiny(
        vocab_size=128, attn_impl="reference", remat=True)
    params = jax.eval_shape(lambda k: olmo_hybrid.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    lowered = jax.jit(train_delta.make_step(olmo_hybrid, cfg, tx),
                      donate_argnums=(0, 1)).lower(params, opt, batch)
    assert lowered.out_info[3].shape == ()
    assert lowered.out_info[3].dtype == jnp.float32
    text = lowered.compile().as_text()
    for scope in ("embed", "gdn_in", "gdn_conv", "gdn_rule", "gdn_norm",
                  "gdn_out", "attn_qkv", "flash", "attn_out", "mlp",
                  "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope


def test_every_kernel_and_serving_program_has_a_name():
    """A profiler trace names a Pallas call by its ``name`` and a jitted
    program by its function's: none may be anonymous."""
    src = {}
    for rel in ("ops/attention.py", "ops/paged_attention.py",
                "serve/llm_engine.py", "serve/paged_engine.py",
                "models/llama_paged.py", "models/llama_decode.py"):
        with open(os.path.join(REPO, "ray_tpu", rel)) as f:
            src[rel] = f.read()
    names = []
    for rel in ("ops/attention.py", "ops/paged_attention.py"):
        calls = [m.start() for m in re.finditer(r"pl\.pallas_call\(",
                                                src[rel])]
        assert calls
        for at in calls:
            m = re.search(r'\bname="(\w+)"', src[rel][at:at + 1500])
            assert m, (rel, src[rel][at:at + 80])
            names.append(m.group(1))
    assert sorted(names) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd",
                             "flash_kv_bwd_dkv", "flash_kv_bwd_dq",
                             "flash_kv_fwd", "paged_attn"]
    # a window layer's calls are named apart from the causal ones
    for name in names[:3]:
        assert f'else "{name.replace("flash_", "flash_win_")}"' in \
            src["ops/attention.py"]
    for rel, text in src.items():
        assert not re.search(r"jax\.jit\(\s*lambda", text), rel


def test_compile_cache_key_sees_a_programs_metadata():
    """jax leaves metadata out of the persistent cache's key by default,
    and a named scope is metadata: a program cached before its scopes
    were added would come back without them. ``ensure_compile_cache``
    turns the option on, for a later ``import jax`` (the environment) and
    for a jax already imported (its config)."""
    import jax

    from ray_tpu.core import compile_cache

    env = {}
    compile_cache.ensure_compile_cache(env)
    assert env["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] == "1"
    assert "jax_compilation_cache_include_metadata_in_key" in \
        jax.config.values
    saved = {k: os.environ.get(k) for k in (
        compile_cache.ENV_VAR,
        "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY")}
    saved_dir = jax.config.jax_compilation_cache_dir
    try:
        os.environ[compile_cache.ENV_VAR] = saved_dir or "/nonexistent-x"
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        compile_cache.ensure_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else \
                os.environ.__setitem__(k, v)


def test_mla_shapes_is_one_kept_span_of_a_traced_layer():
    """A traced latent-attention layer writes what it is once, as a kept
    span (no flag, no profiler window): the heads it holds and of how
    many, both ranks, the three head widths and the softmax scale with
    yarn's ``m ** 2`` in it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v2

    assert not config.task_events_enabled
    cfg = deepseek_v2.DeepseekV2Config.deepseek_v2(
        num_layers=2, vocab_size=256, num_heads=32, heads_of=128,
        experts_held=(0, 8), attn_impl="reference", remat=False)
    params = jax.eval_shape(lambda k: deepseek_v2.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n0 = len(_mine("rtpu.mla.shapes"))
    jax.eval_shape(lambda p, t: deepseek_v2.forward(cfg, p, t)[0], params,
                   jax.ShapeDtypeStruct((1, 64), jnp.int32))
    spans = [{k: v for k, v in e["args"].items()
              if k not in ("id", "parent", "self_us")}
             for e in _mine("rtpu.mla.shapes")[n0:]]
    assert len(spans) == 2                       # one a kind of layer
    assert spans[0] == spans[1]
    scale = spans[0].pop("softmax_scale")
    assert abs(scale - 192 ** -0.5 * (0.1 * 0.707 * 3.6888794541 + 1) ** 2
               ) < 1e-9 and abs(scale - 0.114721) < 1e-6
    assert spans[0] == {"heads": 32, "heads_of": 128, "q_lora_rank": 1536,
                        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                        "qk_rope_head_dim": 64, "v_head_dim": 128}


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "one-key"])
def test_flash_tiles_of_unequal_widths_carries_both(shared):
    """The ``flash_kv_*`` kernels' span says what the equal-width span
    says and both widths besides: the keys' (a query's) under
    ``head_dim``, the values' and how many of the key's dims the heads
    share; an equal-width call's span has neither."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention, tile_plan

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    n0 = len(_mine("rtpu.flash.tiles"))
    jax.eval_shape(jax.grad(lambda q, k, v, kx: flash_attention(
        q, k, v, k_shared=kx, use_pallas=True, interpret=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)),
        S(1, 2048, 4, 192), S(1, 2048, 4, 128 if shared else 192),
        S(1, 2048, 4, 128), S(1, 2048, 64) if shared else None)
    (span,) = _mine("rtpu.flash.tiles")[n0:]
    args = {k: v for k, v in span["args"].items()
            if k not in ("id", "parent", "self_us")}
    assert args == {"seq_q": 2048, "seq_k": 2048, "block_q": 512,
                    "block_k": 512, "window": None, "head_dim": 192,
                    "value_dim": 128, "shared_key_dim": 64 if shared else 0,
                    **tile_plan(2048, 2048, 512, 512)}
    n0 = len(_mine("rtpu.flash.tiles"))
    jax.eval_shape(lambda q: flash_attention(q, q, q, use_pallas=True,
                                             interpret=True),
                   S(1, 2048, 4, 128))
    (equal,) = _mine("rtpu.flash.tiles")[n0:]
    assert not {"value_dim", "shared_key_dim"} & set(equal["args"])


def test_deepseek_v2_train_step_names_its_scopes_and_counts_its_rows():
    """The optimized train step of a stack of latent-attention layers
    carries the four ``mla_*`` scopes and the routed mixture's beside the
    family's, and hands out the routed layers' expert counts, from which
    ``moe_rows_held`` and ``moe_rows_passed`` are read as Laguna's are."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import deepseek_v2
    from ray_tpu.train.session import STEP_COUNTERS

    cfg = deepseek_v2.DeepseekV2Config.tiny(
        vocab_size=128, attn_impl="reference", remat=True,
        experts_held=(4, 4))
    params = jax.eval_shape(lambda k: deepseek_v2.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(tx.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}

    def step(params, opt, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: deepseek_v2.loss_terms(cfg, p, batch),
            has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return (optax.apply_updates(params, updates), opt, loss,
                aux["expert_counts"])

    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt, batch)
    assert lowered.out_info[3].shape == (2, 16)
    text = lowered.compile().as_text()
    for scope in ("embed", "mla_q", "mla_kv", "mla_rope", "flash", "mla_out",
                  "mlp", "moe_route", "moe_dispatch", "moe_experts",
                  "moe_combine", "moe_shared", "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope
    assert {"moe_rows_routed", "moe_rows_held", "moe_rows_passed"} <= set(
        STEP_COUNTERS)
    counts = np.zeros((2, 16), np.int64)
    counts[:, 4:8] = 30, 10, 0, 8
    assert int(deepseek_v2.rows_held(cfg, counts)) == 96
    # two layers' held rows in whole passes of ``_held_chunk`` rows
    assert deepseek_v2.rows_passed(cfg, np.where(
        np.arange(16) < 4, 0, counts + 6)) % 256 == 0


def test_latent_and_module_plans_are_kept_spans_and_the_counter_is_served():
    """A traced step of a stack with mixtures in a latent and a prediction
    module writes ``rtpu.moe.latent_plan`` (one a traced mixture, all alike)
    and ``rtpu.train.mtp_plan`` once; its program carries the scopes
    ``moe_latent``, ``mtp_join`` and ``mtp_head`` beside the scan's and the
    mixture's; ``mtp_cross_entropy`` is one of the counters a session
    serves (``rtpu_train_mtp_cross_entropy``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import metrics
    from ray_tpu.models import nemotron_h
    from ray_tpu.train.session import (STEP_COUNTERS, TrainContext,
                                       _TrainSession)
    from ray_tpu.util import tracing

    cfg = nemotron_h.Nemotron_hConfig.tiny(experts_held=(4, 4),
                                           attn_impl="reference")
    params = jax.eval_shape(lambda k: nemotron_h.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 34), jnp.int32)}
    here = tracing.since()
    lowered = jax.jit(lambda p, b: nemotron_h.loss_terms(cfg, p, b)).lower(
        params, batch)
    spans = {}
    for e in here.events():
        spans.setdefault(e["name"], []).append(e["args"])
    plans = spans["rtpu.moe.latent_plan"]
    assert len(plans) == 3                   # two mixtures and the module's
    assert {(p["hidden"], p["latent"], p["experts"], p["held"], p["top_k"],
             p["act"], p["rows_a_pass"]) for p in plans} == {
        (64, 32, 16, 4, 4, "relu2", 256)}
    (module,) = spans["rtpu.train.mtp_plan"]
    assert (module["depth"], module["pattern"], module["loss_scale"],
            module["head_shared"]) == (1, ["attention", "moe"], 0.1, True)
    text = lowered.compile().as_text()
    for scope in ("ssm_scan", "ssm_norm", "moe_route", "moe_latent",
                  "moe_shared", "moe_experts", "mtp_join", "mtp_head",
                  "head_loss"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope
    assert re.search(r'op_name="[^"]*[/(]mtp[/)][^"]*moe_latent', text)
    assert "mtp_cross_entropy" in STEP_COUNTERS

    def loop():
        from ray_tpu import train
        train.report({"loss": 1.0, "mtp_cross_entropy": 5.5})

    s = _TrainSession(loop, {}, TrainContext())
    from ray_tpu.train import session as session_mod
    saved, session_mod._session = session_mod._session, s
    try:
        s.start()
        s.next_result(timeout=10)
        assert s.next_result(timeout=10).done
        text = metrics.REGISTRY.render()
    finally:
        session_mod._session = saved
    assert "rtpu_train_mtp_cross_entropy 5.5\n" in text
