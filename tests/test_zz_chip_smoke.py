"""chip_smoke.py's phases at tiny size on CPU workers, and the pieces the
chip path rests on: chip detection, worker env, compile-cache placement,
prompt refusal of a TPU request no chip backs.
"""

import os
import subprocess
import sys
import time

import pytest

import chip_smoke
from ray_tpu.core import compile_cache
from ray_tpu.core.resources import (TpuDetectionError, TpuSliceTopology,
                                    scan_tpu_chips)
from ray_tpu.core.worker_env import sanitize_cpu_worker_env, tpu_worker_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# "tiny" is decided here, in the test: the preset's own sizes, spelled
# out because the phases read the head shape and vocab from the config
TINY = {"preset": "tiny", "vocab_size": 256, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "dtype": "float32",
        "param_dtype": "float32"}
# the phases check the route through the runtime, not the engines'
# breadth (tests/test_serve*.py): the fewest programs phase_serve's
# prompts fit in — one slot, single-step chunks, one prefill bucket
# (the paged engine's long prompt takes several chunks of it)
DENSE = {"num_slots": 1, "max_len": 32, "prefill_buckets": [31],
         "chunk_steps": 1}
PAGED = {"num_slots": 1, "max_len": 64, "prefill_buckets": [16],
         "chunk_steps": 1, "page_size": 16}
CPU = {"num_cpus": 0.1}


# ---------------------------------------------------------- the phases
# One test a phase: each is a process (or two) that imports jax and
# compiles, seconds apiece, and a failure names its phase.


@pytest.fixture(scope="module")
def smoke_rt(rt):
    from ray_tpu import serve

    try:
        yield
    finally:
        serve.shutdown()


def test_phase_kernels_tiny_on_cpu_workers(smoke_rt):
    rep = chip_smoke.phase_kernels(TINY, CPU, "cpu", interpret=True,
                                   seq=128, page=16, device_count=8)
    assert rep["mosaic_calls"] == {"flash_fwd": 0, "flash_grad": 0,
                                   "paged": 0}


@pytest.mark.parametrize("engine,cfg", [("paged", PAGED), ("dense", DENSE)],
                         ids=["paged", "dense"])
def test_phase_serve_tiny_on_cpu_workers(smoke_rt, engine, cfg):
    rep = chip_smoke.phase_serve(
        f"smoke-{engine}", engine, TINY, cfg, CPU, "cpu",
        mosaic_programs=[], prompt_lens=[5, 20],
        long_prompt_len=50, n_new=4, device_count=8)
    assert rep["platform"] == "cpu" and rep["first_error"] is None
    assert chip_smoke._pid_gone(rep["pid"])


# the four-chip phases' code on virtual devices: replicas side by side,
# and one worker whose state is born sharded over fsdp=4


def test_phase_serve_replicas_tiny_on_cpu_workers(smoke_rt):
    reps = chip_smoke.phase_serve_replicas(
        "smoke-2x1", "dense", TINY, DENSE, CPU, "cpu", 2, n_new=4,
        device_count=8, prompt_len=10)
    assert len({r["pid"] for r in reps}) == 2


TRAIN = {"batch": 4, "seq": 32, "steps": 3, "lr": 1e-2, "seed": 0}


@pytest.fixture(scope="module")
def one_device_losses(smoke_rt):
    return chip_smoke.phase_train(
        "smoke-train", TINY, TRAIN, {"num_workers": 1},
        {"platform": "cpu"}, "cpu", 1, None, min_mosaic_calls=0)["losses"]


def test_phase_train_tiny_on_cpu_workers(one_device_losses):
    assert one_device_losses[-1] < one_device_losses[0]


def test_phase_train_fsdp4_tiny_on_cpu_workers(one_device_losses):
    four = chip_smoke.phase_train(
        "smoke-train-1x4", TINY, TRAIN, {"num_workers": 1},
        {"platform": "cpu", "cpu_devices_per_worker": 4}, "cpu", 4,
        {"fsdp": 4}, min_mosaic_calls=0)
    assert max(abs(a - b) for a, b in zip(one_device_losses, four["losses"])
               ) < 1e-3   # f32 on CPU; the chip's bf16 band is LOSS_TOL


def test_smoke_rejects_what_a_fallback_would_pass():
    # an exception VALUE in collect() is not a reply
    with pytest.raises(chip_smoke.SmokeFailure, match="not a dict"):
        chip_smoke._check_reply("p", ValueError("request rejected"), 4, 256)
    with pytest.raises(chip_smoke.SmokeFailure, match="token ids"):
        chip_smoke._check_reply("p", {"tokens": [1, 2, 999]}, 3, 256)
    # a device that is not the one asked for
    rep = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    with pytest.raises(chip_smoke.SmokeFailure, match="no accelerator"):
        chip_smoke._check_device("p", rep, "tpu", 1)
    # a program that took the reference path where a kernel was expected
    with pytest.raises(chip_smoke.SmokeFailure, match="Mosaic"):
        chip_smoke._check_mosaic("p", {"prefill[128]": 0, "decode": 0},
                                 ["prefill[128]"])
    chip_smoke._check_mosaic("p", {"prefill[128]": 1, "decode": 0},
                             ["prefill[128]"])
    # a train step compiled without the flash kernels, a loss that is
    # not finite, one that does not fall, state stacked on device 0
    step = {"platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 4, "mosaic_calls": 4,
            "activation_all_gathers": [], "state_bytes": 400,
            "state_bytes_in_use": [100, 100, 100, 100]}
    ok = [{**step, "loss": 11.8}, {**step, "loss": 9.5}]
    chip_smoke._check_train("t", ok, 2, "tpu", 4, 3)
    for bad, why in (
            ([{**h, "mosaic_calls": 0} for h in ok], "Mosaic"),
            ([ok[0], {**step, "loss": float("nan")}], "non-finite"),
            ([ok[1], ok[0]], "did not fall"),
            ([{**h, "state_bytes_in_use": [400, 0, 0, 0]} for h in ok],
             "not divided"),
            ([{**h, "activation_all_gathers": ["4,2048,8,64"]}
              for h in ok], "all-gathered")):
        with pytest.raises(chip_smoke.SmokeFailure, match=why):
            chip_smoke._check_train("t", bad, 2, "tpu", 4, 3)


def test_engine_keeps_its_first_compile_error():
    from ray_tpu.serve.llm_engine import LLMEngine

    class Broken(LLMEngine):
        def _precompile(self):
            raise RuntimeError("mosaic refused the kernel")

    # private: the assertion is on what _precompile's failure leaves
    e = Broken(model_config={"preset": "tiny"}, num_slots=2, max_len=32,
               prefill_buckets=[16], chunk_steps=1)
    try:
        deadline = time.monotonic() + 60
        while not e.report()["ready"]:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        err = e.report()["first_error"]
        assert err.startswith("precompile:")
        assert "mosaic refused the kernel" in err
    finally:
        e.shutdown()


def test_script_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RTPU_TPU_TOPOLOGY", None)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU chip" in p.stderr and "device nodes under /dev" in p.stderr
    assert '"ok"' not in p.stdout


# ------------------------------------------------- TPU request, no chip


def test_tpu_request_without_chips_fails_promptly(rt):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_engine import LLMEngine
    from ray_tpu.util import placement_group

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    t0 = time.monotonic()
    with pytest.raises(ValueError, match="Chip detection saw"):
        A.options(num_tpus=1).remote()
    with pytest.raises(ValueError, match="Chip detection saw"):
        placement_group([{"CPU": 1, "TPU": 1}])
    # through the controller (a worker): the refusal reaches serve.run's
    # caller as the reason, not as a health-wait timeout
    dep = serve.deployment(engine=True, num_tpus=1, name="no-chip")(
        LLMEngine).bind(model_config={"preset": "tiny"})
    try:
        with pytest.raises(Exception, match="asks for 1 TPU chip"):
            serve.run(dep, timeout=60)
    finally:
        serve.shutdown()
    assert time.monotonic() - t0 < 30


# ------------------------------------------------------ chip detection


def _fake_host(tmp_path, pci, vfio=(), accel=()):
    """pci: {bdf: (vendor, device, iommu_group)}."""
    dev, sysr = tmp_path / "dev", tmp_path / "sys"
    (dev / "vfio").mkdir(parents=True)
    (dev / "vfio" / "vfio").touch()
    for g in vfio:
        (dev / "vfio" / str(g)).touch()
    for bdf, (vendor, device, group) in pci.items():
        d = sysr / "bus/pci/devices" / bdf
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
        g = sysr / "kernel/iommu_groups" / str(group)
        g.mkdir(parents=True, exist_ok=True)
        os.symlink(g, d / "iommu_group")
    for i, bdf in accel:
        (dev / f"accel{i}").touch()
        c = sysr / "class/accel" / f"accel{i}"
        c.mkdir(parents=True)
        os.symlink(sysr / "bus/pci/devices" / bdf, c / "device")
    return str(dev), str(sysr)


V5E = {f"0000:00:0{i}.0": ("0x1ae0", "0x0063", g)
       for i, g in zip((8, 9, "a", "b"), (1, 0, 2, 3))}


def test_detect_none_found(tmp_path, monkeypatch):
    monkeypatch.delenv("RTPU_TPU_TOPOLOGY", raising=False)
    dev, sysr = _fake_host(tmp_path, {})
    assert TpuSliceTopology.detect(dev, sysr) is None
    chips, seen = scan_tpu_chips(dev, sysr)
    assert chips == [] and "none" in seen


def test_detect_counts_device_nodes_not_pci_functions(tmp_path, monkeypatch):
    # the one-chip sandbox of a four-chip host: four PCI functions, one node
    monkeypatch.delenv("RTPU_TPU_TOPOLOGY", raising=False)
    dev, sysr = _fake_host(tmp_path, V5E, vfio=[3])
    topo = TpuSliceTopology.detect(dev, sysr)
    assert (topo.generation, topo.num_chips) == ("v5e", 1)


def test_detect_four_chip_host_and_accel_nodes(tmp_path, monkeypatch):
    monkeypatch.delenv("RTPU_TPU_TOPOLOGY", raising=False)
    dev, sysr = _fake_host(tmp_path / "a", V5E, vfio=[0, 1, 2, 3])
    topo = TpuSliceTopology.detect(dev, sysr)
    assert (topo.pod_type, topo.grid) == ("v5e-4", (2, 2))
    v4 = {"0000:00:04.0": ("0x1ae0", "0x005e", 7)}
    dev, sysr = _fake_host(tmp_path / "b", v4, accel=[(0, "0000:00:04.0")])
    assert TpuSliceTopology.detect(dev, sysr).pod_type == "v4-1"


def test_detect_ignores_foreign_vfio_and_refuses_unknown_tpu(tmp_path,
                                                             monkeypatch):
    monkeypatch.delenv("RTPU_TPU_TOPOLOGY", raising=False)
    nic = {"0000:00:05.0": ("0x8086", "0x1572", 4)}
    dev, sysr = _fake_host(tmp_path / "a", nic, vfio=[4])
    assert TpuSliceTopology.detect(dev, sysr) is None
    odd = {"0000:00:05.0": ("0x1ae0", "0x9999", 4)}
    dev, sysr = _fake_host(tmp_path / "b", odd, vfio=[4])
    with pytest.raises(TpuDetectionError, match="0x9999"):
        TpuSliceTopology.detect(dev, sysr)


def test_topology_override_must_name_its_generation(monkeypatch):
    monkeypatch.setenv("RTPU_TPU_TOPOLOGY", "v5e-8")
    assert TpuSliceTopology.detect().pod_type == "v5e-8"
    monkeypatch.setenv("RTPU_TPU_TOPOLOGY", "8")
    with pytest.raises(ValueError, match="generation"):
        TpuSliceTopology.detect()


# ---------------------------------------------------------- worker env


@pytest.mark.parametrize("ambient", [None, "tpu", "tpu,cpu", "cpu"])
def test_cpu_workers_never_get_the_chip(ambient):
    env = {} if ambient is None else {"JAX_PLATFORMS": ambient}
    sanitize_cpu_worker_env(env)
    assert env["JAX_PLATFORMS"] == "cpu"


def test_tpu_worker_env_confines_a_partial_host():
    assert tpu_worker_env([0, 1, 2, 3], 4) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3", "RTPU_TPU_CHIPS": "0,1,2,3"}
    one = tpu_worker_env([2], 4)
    assert one["TPU_VISIBLE_CHIPS"] == "2"
    assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert one["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert tpu_worker_env([0, 1], 4)["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    # no verified shape for three chips: visible chips only
    assert set(tpu_worker_env([0, 1, 2], 4)) == {"TPU_VISIBLE_CHIPS",
                                                 "RTPU_TPU_CHIPS"}


# ------------------------------------------------------- compile cache


def test_compile_cache_set_is_left_alone():
    env = {compile_cache.ENV_VAR: "/x"}
    assert compile_cache.ensure_compile_cache(env) == "/x"
    # no other directory; the one thing added tells jax to key a cached
    # program on its metadata too (tests/test_tracing.py says why)
    assert env == {compile_cache.ENV_VAR: "/x",
                   "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY": "1"}


def test_compile_cache_unset_is_one_fixed_dir_in_the_checkout():
    env = {}
    path = compile_cache.ensure_compile_cache(env)
    assert path == os.path.join(REPO, ".jax_compile_cache")
    assert env[compile_cache.ENV_VAR] == path
    # read, not asked of git: an unpacked archive has no .git
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_compile_cache_same_in_tpu_worker_env_and_spawned_worker(rt):
    import ray_tpu
    from ray_tpu.core import runtime_context

    core = runtime_context.get_core()
    saved = os.environ.pop(compile_cache.ENV_VAR, None)
    try:
        expect = os.path.join(REPO, ".jax_compile_cache")
        # the env a TPU worker is spawned with carries it ...
        assert core._pool_env(tpu=True, extra_env=None)[
            compile_cache.ENV_VAR] == expect
        # ... an outside setting survives the copy untouched ...
        os.environ[compile_cache.ENV_VAR] = "/x"
        assert core._pool_env(tpu=True, extra_env=None)[
            compile_cache.ENV_VAR] == "/x"
        del os.environ[compile_cache.ENV_VAR]

        # ... and a worker process resolves the same path by itself
        @ray_tpu.remote
        def resolve():
            from ray_tpu.core.compile_cache import ensure_compile_cache

            return ensure_compile_cache({})

        assert ray_tpu.get(resolve.remote(), timeout=30) == expect
    finally:
        if saved is not None:
            os.environ[compile_cache.ENV_VAR] = saved
        else:
            os.environ.pop(compile_cache.ENV_VAR, None)
