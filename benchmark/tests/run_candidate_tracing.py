#!/usr/bin/env python3
"""Runs a cell of ``benchmark/candidates.json`` with the per-layer entries
of ``benchmark/candidates.tracing.json`` appended, same arguments as
``run_candidate.py``:

    python3 benchmark/tests/run_candidate_tracing.py --workload serve-chat-closed --seed 1 --seconds 50 --trace 1

The merged file is written under ``.bench_tmp/`` for the run; neither
source file is changed. For the builder; the driver never runs it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402
from benchmark.lib import spec  # noqa: E402


def merged(root: str = ROOT) -> dict:
    bench = spec.load_benchmark(root, "benchmark/candidates.json")
    extra = spec.load_benchmark(root, "benchmark/candidates.tracing.json")
    bench["per_layer"] = bench["per_layer"] + extra["per_layer"]
    return bench


def merged_file(root: str = ROOT) -> str:
    bench = merged(root)
    out = os.path.join(root, ".bench_tmp", "candidates.merged.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(bench, f)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    print(json.dumps(R.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                                bench_file=merged_file())), flush=True)
