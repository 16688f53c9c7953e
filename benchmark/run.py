#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, with ``--trace
1``, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Which cell runner, generator and metric readers run is decided by the
names in ``BENCHMARK.json`` and the files they point to (see
``benchmark/lib/spec.py``); nothing here knows a workload by name. This
process never imports jax: the chips belong to the workers the runtime
spawns. It exits non-zero, printing no result, where the runtime finds
fewer TPU chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

T_START_WALL = time.time()   # wall clock: a worker compares with it
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _environment() -> str:
    """What the workers inherit: this checkout on the import path, the
    compile cache at a fixed place inside the checkout (unless the
    machine names one), and a cache that also keeps the small programs,
    so that a second run compiles nothing."""
    pp = os.environ.get("PYTHONPATH", "")
    if ROOT not in pp.split(os.pathsep):
        os.environ["PYTHONPATH"] = ROOT + (os.pathsep + pp if pp else "")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_compile_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    tmp = os.path.join(ROOT, ".bench_tmp")
    os.makedirs(tmp, exist_ok=True)
    return tmp


def reduce_trace_in_child(trace_dir: str) -> dict:
    """The reduction needs jax's trace reader; a child pinned to the CPU
    runs it, after the chip's owner has gone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.lib.trace", trace_dir], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=300)
    if p.returncode != 0:
        raise RuntimeError(f"trace reduction failed:\n{p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             overrides: dict | None = None,
             bench_file: str = "BENCHMARK.json") -> dict:
    """``overrides`` is for the CPU rehearsals under benchmark/tests: a
    tiny model_config, engine sizes and CPU resources. ``bench_file`` is
    for benchmark/tests/run_candidate.py, which runs a cell that is not
    in ``BENCHMARK.json`` yet. The command line sets neither."""
    from benchmark.lib import spec

    tmp_dir = _environment()
    bench = spec.load_benchmark(ROOT, bench_file)
    ctx = spec.resolve_cell(bench, workload, ROOT)
    chips = ctx["cell"]["chips"]
    ctx.update(
        seed=seed, seconds=seconds, trace=trace, tmp_dir=tmp_dir,
        t_start_wall=T_START_WALL, platform="tpu",
        devices=chips, model_config=ctx["config"]["model_config"],
        resources={"num_tpus": chips},
        scaling={"num_workers": 1, "use_tpu": True,
                 "chips_per_worker": chips},
        jax_config={"platform": "tpu"})
    for k, v in (overrides or {}).items():
        if k in ("traffic", "config"):
            ctx[k] = {**ctx[k], **v}
        else:
            ctx[k] = v
    out = spec.cell_runner(ctx["traffic"]["family"]).run(ctx)
    if "jax" in sys.modules:
        raise RuntimeError("the benchmark's parent process imported jax")

    obs = out["obs"]
    obs["setup_s"] = out["setup_s"]
    obs["device"] = out["device"]
    device = {"platform": out["device"]["platform"],
              "kind": out["device"]["device_kind"],
              "count": out["device"]["device_count"],
              "memory_peak_bytes": max(
                  b or 0 for b in out["device"]["memory_peak_bytes"])}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"]}
    if trace:
        reduced = reduce_trace_in_child(out["trace_dir"])
        obs["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["metrics"] = spec.read_metrics(ctx["per_layer"], obs)
        line["breakdown"] = reduced.pop("breakdown")
        if not reduced["busy_s"] > 0:
            raise RuntimeError("the trace holds no device operation")
    else:
        line["metrics"] = spec.read_metrics(ctx["end_to_end"], obs)
    line["device"] = device
    return line


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    line = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
