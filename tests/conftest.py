"""Shared fixtures.

JAX-dependent tests run on a virtual 8-device CPU mesh (the reference's
analogue is the fake multi-node cluster fixtures in
python/ray/tests/conftest.py); the env vars must be set before jax import,
hence they live here at collection time.
"""

import os

# Force CPU regardless of the ambient TPU env: tests exercise sharding on a
# virtual 8-device CPU mesh; the chip is checked by chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax's persistent compile cache, one directory a run (xdist's workers
# and every process they start inherit it): what a run compiles twice,
# a rebuilt gang's step, it loads the second time, and nothing comes
# back from an earlier run. XLA:CPU programs cached by an earlier run
# can hang in the virtual devices' collectives when loaded (the worker
# then dies at the rendezvous timeout, in a different test each run).
# Every program goes there, however quick its compile (jax keeps those
# over a second): the references run op by op and a file's one-operator
# programs are the next file's, so of the eight models' files' 21 minutes
# of CPU under six workers 3.5 were compiles a worker had made already
# (CHANGES.md, PR 54). The process that named the directory removes it
# when the run ends.
_cache_is_this_runs = "JAX_COMPILATION_CACHE_DIR" not in os.environ
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache", f"run-{os.getpid()}"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

# transformers imports TensorFlow when it finds it, ten seconds in every
# process that loads an HF checkpoint; nothing here uses it
os.environ.setdefault("USE_TF", "0")

import contextlib
import faulthandler
import shutil
import signal
import sys
import threading

import pytest

from tests.engines import (_dense_engine, _paged_engine,  # noqa: F401
                           dense_engine, paged_engine)

# One limit for every test, for its setup, its body and its teardown
# each. The heaviest case, ``test_cell_steps_compile.py::
# test_laguna_cell_step_compiles_within_a_v5e_chip``, took 103.5 s in the
# driver's whole run of PR 53's tree and 105 to 138 s in the builder's
# six of PR 54's (the two machines run a whole suite at the same pace;
# 160 s once on the parent's tree), so the limit stands at about twice
# it; the next, LFM2's cell step, takes 62 to 113 s, and no other case
# 80. A test that runs into it fails alone, with the line it stood in,
# and the run goes on.
TEST_LIMIT_S = 240


class TestLimitExceeded(Exception):
    pass


def _limited(item):
    """Wraps one phase of one test. SIGALRM reaches Python code and
    every interruptible wait of the main thread (locks, joins, sleeps,
    sockets); it repeats, so a handler that swallows it is asked again."""
    def on_alarm(signum, frame):
        raise TestLimitExceeded(
            f"{item.nodeid} ran over the per-test limit of "
            f"{TEST_LIMIT_S} s in {frame.f_code.co_name} "
            f"({frame.f_code.co_filename}:{frame.f_lineno})")

    if threading.current_thread() is not threading.main_thread():
        return (yield)
    # for the record: every thread's stack, just before the alarm
    # unwinds the main one
    faulthandler.dump_traceback_later(TEST_LIMIT_S * 0.99, exit=False)
    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S, TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_limited)
pytest_runtest_call = pytest.hookimpl(wrapper=True)(_limited)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True)(_limited)


# xdist's --dist loadfile hands whole files to the workers, each worker
# holding the file it runs and the next. The files go in the order of
# their names: no file holds more than 6% of the run's test-seconds
# (ROADMAP D10 has the table), and a list of the heaviest, which every
# model's PR had to edit, bought 4.3% of the wall (974 and 976 s against
# 1,018 s, the builder's whole runs of PR 54), less than the box moves
# between two runs of one tree.
def pytest_sessionfinish(session):
    if _cache_is_this_runs:         # xdist's workers found it named
        shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"],
                      ignore_errors=True)


def pytest_configure(config):
    # xdist would sort the files by how many tests each holds, and the
    # files of a few long cases (the compiles for a described chip, the
    # gangs of processes) would all come last
    config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "slow: too long for tier-1 even at its smallest; the "
        "driver's -m 'not slow' leaves it out")


# ``util/tracing`` keeps the last 4,096 kept spans of a process, and a
# worker runs several files in one process: a test that wants "the spans
# this call wrote" asks ``tracing.since()`` before the call and reads its
# ``events()`` after (right in full buffers too), so nobody needs the
# buffers cleared between modules.


# Modules that exercise the concurrency surface hardest run with the
# lock-order sanitizer armed: every runtime lock built inside them is a
# DebugLock, so an acquisition-order inversion or a callback fired
# under a tracked lock fails the test at the offending site instead of
# hanging CI. The env var makes spawned workers arm themselves too.
_SANITIZED_MODULES = {"test_dag_spin", "test_drain", "test_fault_tolerance",
                      "test_ha", "test_job", "test_netem",
                      "test_regressions", "test_wal_replay"}


@pytest.fixture(autouse=True, scope="module")
def _lock_sanitizer(request):
    name = request.module.__name__.rpartition(".")[2]
    if name not in _SANITIZED_MODULES:
        yield
        return
    from ray_tpu.util import debug_lock

    os.environ["RTPU_SANITIZE"] = "1"
    debug_lock.arm()
    try:
        yield
    finally:
        debug_lock.disarm()
        debug_lock.reset()
        os.environ.pop("RTPU_SANITIZE", None)


# The chaos suites additionally run under the deterministic interleaving
# fuzzer (ray_tpu.tools.race): seeded preemptions drive the runtime into
# adversarial thread schedules where the armed sanitizer — and the
# suites' own assertions — can see ordering bugs. Bounded so the 1-core
# CI box stays inside the tier-1 budget: one fixed seed, a preemption
# cap per thread, and only the in-process control plane instrumented
# (GCS/worker subprocesses are exercised by RTPU_SANITIZE instead).
# Override with RTPU_INTERLEAVE=<seed>[:<n>] to replay a failing seed
# printed by a sweep, or to widen the schedule search locally.
_INTERLEAVED_MODULES = {"test_drain", "test_fault_tolerance", "test_ha",
                        "test_job", "test_netem", "test_wal_replay"}
_INTERLEAVE_SEED = 1  # default chaos-suite schedule; env var overrides
_INTERLEAVE_MAX_PREEMPTIONS = 200


@pytest.fixture(autouse=True, scope="module")
def _interleaver(request):
    name = request.module.__name__.rpartition(".")[2]
    if name not in _INTERLEAVED_MODULES:
        yield
        return
    from ray_tpu.tools import race

    parsed = race.parse_env()
    seed = parsed[0] if parsed else _INTERLEAVE_SEED
    race.arm(seed, preempt_prob=0.02,
             max_preemptions=_INTERLEAVE_MAX_PREEMPTIONS,
             trace_current=False)
    try:
        yield
    finally:
        race.disarm()


@pytest.fixture(scope="module")
def rt():
    """A running ray_tpu runtime shared per test module."""
    import ray_tpu

    ray_tpu.init(num_workers=4, object_store_memory=256 << 20)
    yield ray_tpu
    ray_tpu.shutdown()


@contextlib.contextmanager
def own_runtime(num_workers=4):
    """A runtime of a module's own, whatever core an earlier module of
    the process left installed; serve, if the module used it, goes down
    with it. For a module-scoped fixture: ``with own_runtime(): yield``."""
    import ray_tpu
    from ray_tpu.core import runtime_context

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    ray_tpu.init(num_workers=num_workers, object_store_memory=256 << 20)
    try:
        yield
    finally:
        if "ray_tpu.serve.api" in sys.modules:
            sys.modules["ray_tpu.serve.api"].shutdown()
        core = runtime_context.get_core_or_none()
        if core is not None:
            core.shutdown()
        runtime_context.set_core(prev)


@contextlib.contextmanager
def own_cluster(num_nodes, **kw):
    """A cluster of node processes of a test's own, up and connected as
    the process's core; whatever core was installed before comes back."""
    from ray_tpu.core import runtime_context
    from ray_tpu.core.cluster.fixture import Cluster

    prev = runtime_context.get_core_or_none()
    runtime_context.set_core(None)
    c = Cluster(num_nodes=num_nodes, **kw)
    try:
        assert c.wait_for_nodes(num_nodes, timeout=120)
        c.connect()
        yield c
    finally:
        c.shutdown()
        runtime_context.set_core(prev)
