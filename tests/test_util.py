"""Tests for ray_tpu.cancel and ray_tpu.util (ActorPool, Queue).

Mirrors the reference's python/ray/tests/test_cancel.py,
test_actor_pool.py, and test_queue.py coverage.
"""

import time

import pytest

from ray_tpu.exceptions import TaskCancelledError
from ray_tpu.util import ActorPool, Empty, Full, Queue


# ----------------------------------------------------------------- cancel

def test_cancel_queued_task(rt):
    @rt.remote
    def sleeper(x):
        time.sleep(30)
        return x

    @rt.remote
    def quick():
        return 1

    # Saturate the pool so later submissions stay queued.
    blockers = [sleeper.remote(i) for i in range(8)]
    victim = sleeper.remote(99)
    rt.cancel(victim)
    with pytest.raises(TaskCancelledError):
        rt.get(victim, timeout=10)
    for b in blockers:
        rt.cancel(b, force=True)


def test_cancel_running_task_force(rt):
    @rt.remote
    def hang():
        time.sleep(60)

    ref = hang.remote()
    time.sleep(0.5)  # let it start
    rt.cancel(ref, force=True)
    with pytest.raises(TaskCancelledError):
        rt.get(ref, timeout=10)


def test_cancel_running_task_interrupt(rt):
    @rt.remote
    def hang():
        time.sleep(60)

    ref = hang.remote()
    time.sleep(0.5)
    rt.cancel(ref)  # SIGINT -> KeyboardInterrupt in the worker
    with pytest.raises(TaskCancelledError):
        rt.get(ref, timeout=10)


def test_cancel_dep_waiting_task(rt):
    @rt.remote
    def slow_dep():
        time.sleep(30)
        return 1

    @rt.remote
    def consumer(x):
        return x

    dep = slow_dep.remote()
    ref = consumer.remote(dep)
    rt.cancel(ref)
    rt.cancel(dep, force=True)
    with pytest.raises(TaskCancelledError):
        rt.get(ref, timeout=10)


def test_cancel_finished_task_is_noop(rt):
    @rt.remote
    def f():
        return 7

    ref = f.remote()
    assert rt.get(ref) == 7
    rt.cancel(ref)  # no-op
    assert rt.get(ref) == 7


# -------------------------------------------------------------- ActorPool

def test_actor_pool_map(rt):
    @rt.remote
    class Doubler:
        def double(self, x):
            return 2 * x

    pool = ActorPool([Doubler.remote() for _ in range(2)])
    out = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
    assert out == [0, 2, 4, 6, 8, 10, 12, 14]


def test_actor_pool_map_unordered(rt):
    @rt.remote
    class Worker:
        def work(self, x):
            time.sleep(0.05 if x % 2 else 0.0)
            return x

    pool = ActorPool([Worker.remote() for _ in range(2)])
    out = list(pool.map_unordered(lambda a, v: a.work.remote(v), range(6)))
    assert sorted(out) == [0, 1, 2, 3, 4, 5]


def test_actor_pool_submit_get_next(rt):
    @rt.remote
    class Sq:
        def sq(self, x):
            return x * x

    pool = ActorPool([Sq.remote()])
    pool.submit(lambda a, v: a.sq.remote(v), 3)
    pool.submit(lambda a, v: a.sq.remote(v), 4)
    assert pool.get_next() == 9
    assert pool.get_next() == 16
    assert not pool.has_next()


def test_actor_pool_push_pop(rt):
    @rt.remote
    class A:
        def f(self, x):
            return x

    a1, a2 = A.remote(), A.remote()
    pool = ActorPool([a1])
    assert pool.has_free()
    popped = pool.pop_idle()
    assert popped is a1
    pool.push(a2)
    pool.submit(lambda a, v: a.f.remote(v), 5)
    assert pool.get_next() == 5


# ------------------------------------------------------------------ Queue

def test_queue_basic(rt):
    q = Queue()
    q.put(1)
    q.put("two")
    assert q.qsize() == 2
    assert q.get() == 1
    assert q.get() == "two"
    assert q.empty()


def test_queue_nowait_and_maxsize(rt):
    q = Queue(maxsize=2)
    q.put_nowait(1)
    q.put_nowait(2)
    assert q.full()
    with pytest.raises(Full):
        q.put_nowait(3)
    with pytest.raises(Full):
        q.put(3, timeout=0.2)
    assert q.get_nowait() == 1
    q.put_nowait(3)
    assert q.get_nowait_batch(2) == [2, 3]
    with pytest.raises(Empty):
        q.get_nowait()
    with pytest.raises(Empty):
        q.get(timeout=0.2)


def test_queue_across_tasks(rt):
    q = Queue()

    @rt.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return n

    @rt.remote
    def consumer(q, n):
        return [q.get(timeout=10) for _ in range(n)]

    p = producer.remote(q, 5)
    c = consumer.remote(q, 5)
    assert rt.get(p) == 5
    assert sorted(rt.get(c)) == [0, 1, 2, 3, 4]


def test_queue_batch_put(rt):
    q = Queue(maxsize=3)
    q.put_nowait_batch([1, 2])
    with pytest.raises(Full):
        q.put_nowait_batch([3, 4])
    q.put_nowait_batch([3])
    assert q.qsize() == 3


def test_multiprocessing_pool_shim(rt):
    import ray_tpu.util.multiprocessing as mp

    def sq(x):
        return x * x

    with mp.Pool(processes=2) as pool:
        assert pool.map(sq, range(10)) == [x * x for x in range(10)]
        assert pool.starmap(lambda a, b: a + b, [(1, 2), (3, 4)]) == [3, 7]
        r = pool.apply_async(sq, (6,))
        assert r.get(timeout=30) == 36
        assert sorted(pool.imap_unordered(sq, range(5))) == [0, 1, 4, 9, 16]
        assert list(pool.imap(sq, range(5))) == [0, 1, 4, 9, 16]


def test_jax_predictor_batch_inference(rt, tmp_path):
    import os
    import pickle

    import numpy as np

    import ray_tpu.data as rd
    from ray_tpu.train.predictor import JaxPredictor, predict_batches

    # "checkpoint": a linear model w=3
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt)
    with open(os.path.join(ckpt, "params.pkl"), "wb") as f:
        pickle.dump({"w": np.float32(3.0)}, f)

    def apply_fn(params, x):
        return params["w"] * x

    ds = rd.from_numpy(np.arange(32, dtype=np.float32))
    out = predict_batches(
        ds, JaxPredictor, batch_size=8, concurrency=1,
        predictor_kwargs={"checkpoint": ckpt, "apply_fn": apply_fn})
    rows = sorted(out.take_all(), key=lambda r: r["data"])
    assert rows[5]["predictions"] == 15.0
    assert len(rows) == 32


# ------------------------------------------------------------------ joblib


def test_joblib_backend(rt):
    """register_ray_tpu() makes joblib.Parallel fan out over the
    distributed Pool shim (reference: ray.util.joblib.register_ray);
    exceptions propagate; n_jobs=1 falls back to joblib's sequential
    backend."""
    import math

    joblib = pytest.importorskip("joblib")
    from joblib import Parallel, delayed

    from ray_tpu.util.joblib import register_ray_tpu

    register_ray_tpu()
    with joblib.parallel_backend("ray_tpu", n_jobs=2):
        out = Parallel()(delayed(math.factorial)(i) for i in range(10))
    assert out == [math.factorial(i) for i in range(10)]

    def boom(i):
        if i == 3:
            raise ValueError("kaboom")
        return i

    with pytest.raises(ValueError, match="kaboom"):
        with joblib.parallel_backend("ray_tpu", n_jobs=2):
            Parallel()(delayed(boom)(i) for i in range(6))

    with joblib.parallel_backend("ray_tpu", n_jobs=1):
        assert Parallel()(delayed(abs)(-i) for i in range(3)) == [0, 1, 2]


def test_apply_async_callbacks(rt):
    """Pool.apply_async callback/error_callback (stdlib parity — the
    joblib backend drives retrieval through these)."""
    import threading

    import ray_tpu.util.multiprocessing as mp

    done = threading.Event()
    got = []
    with mp.Pool(processes=1) as pool:
        pool.apply_async(lambda x: x * 7, (6,),
                         callback=lambda v: (got.append(v), done.set()))
        assert done.wait(30) and got == [42]

        err = threading.Event()
        errs = []
        pool.apply_async(lambda: 1 / 0,
                         callback=lambda v: errs.append(("ok", v)),
                         error_callback=lambda e: (errs.append(e),
                                                   err.set()))
        assert err.wait(30)
        assert isinstance(errs[0], Exception)


# ----------------------------------------------------------------- tqdm

def test_tqdm_multiplexes_concurrent_task_bars(rt):
    """Four tasks render progress bars concurrently through the driver's
    multiplexer without interleaving corruption: every rendered line is a
    complete bar line (reference: tqdm_ray)."""
    import io
    import re

    from ray_tpu.util import tqdm as tqdm_ray

    buf = io.StringIO()
    tqdm_ray.instance(sink=buf)

    @rt.remote
    def work(i):
        for _ in tqdm_ray.tqdm(range(30), desc=f"shard-{i}"):
            time.sleep(0.005)
        return i

    assert rt.get([work.remote(i) for i in range(4)],
                  timeout=60) == [0, 1, 2, 3]

    deadline = time.time() + 10
    while time.time() < deadline:
        tqdm_ray.instance().flush()
        done = re.findall(r"(shard-\d): \|#+\| 30/30 \[100%\].*done",
                          buf.getvalue())
        if len(set(done)) == 4:
            break
        time.sleep(0.1)
    else:
        raise AssertionError(
            "bars never completed:\n" + buf.getvalue()[-2000:])

    # strip ANSI control sequences; every remaining line is one whole bar
    plain = re.sub(r"\x1b\[[0-9;]*[A-Za-z]", "", buf.getvalue())
    for line in plain.replace("\r", "\n").split("\n"):
        if not line.strip():
            continue
        assert re.fullmatch(
            r"shard-\d: \|[#-]+\| \d+/30 \[\s*\d+%\] [\d.]+it/s( done)?",
            line.strip()), repr(line)


# ------------------------------------------- the suite's own rules (conftest)


def _pytest_on(tmp_path, body, *args):
    """Run ``body`` as a test file of its own under tests/conftest.py
    (loaded as a plugin: the file lives outside the checkout)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (tmp_path / "test_probe.py").write_text(body)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([str(tmp_path), repo]))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "tests.conftest", "--strict-markers",
         *args, "test_probe.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)


def test_a_test_over_the_limit_fails_alone_and_the_run_goes_on(tmp_path):
    # the constant, patched by a plugin of one line
    (tmp_path / "one_second.py").write_text(
        "import tests.conftest\ntests.conftest.TEST_LIMIT_S = 1\n")
    p = _pytest_on(tmp_path, "import time\n"
                   "def test_sleeps():\n    time.sleep(60)\n"
                   "def test_after_it():\n    pass\n", "-p", "one_second")
    assert p.returncode == 1, p.stdout + p.stderr
    assert "1 failed, 1 passed" in p.stdout
    # the message names the test, the limit and where the test stood
    assert "test_probe.py::test_sleeps ran over the per-test limit of 1 s " \
           "in test_sleeps" in p.stdout
    assert "test_probe.py:3" in p.stdout
    # and every thread's stack went to the test's stderr just before
    assert "most recent call first" in p.stdout


def test_slow_is_a_registered_mark_that_the_drivers_command_deselects(
        tmp_path):
    p = _pytest_on(tmp_path, "import pytest\n"
                   "@pytest.mark.slow\ndef test_long():\n    pass\n"
                   "def test_short():\n    pass\n", "-m", "not slow")
    assert p.returncode == 0, p.stdout + p.stderr   # --strict-markers
    assert "1 passed, 1 deselected" in p.stdout
