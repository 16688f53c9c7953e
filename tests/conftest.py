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

import pytest

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
