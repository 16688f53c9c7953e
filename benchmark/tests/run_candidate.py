#!/usr/bin/env python3
"""Runs a cell of ``benchmark/candidates.json`` (built and rehearsed, not
in ``BENCHMARK.json``) with the command's own arguments:

    python3 benchmark/tests/run_candidate.py --workload serve-chat-closed --seed 1 --seconds 50 --trace 0

For the builder of the PR that proves such a cell; the driver never runs
it.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark import run as R  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("--workload", required=True)
ap.add_argument("--seed", type=int, required=True)
ap.add_argument("--seconds", type=float, required=True)
ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
a = ap.parse_args()
print(json.dumps(R.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                            bench_file="benchmark/candidates.json")),
      flush=True)
