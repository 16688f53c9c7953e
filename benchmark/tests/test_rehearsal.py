"""Each cell end to end at a tiny size on CPU workers (a process each:
the runtime wants a fresh one). About half a minute a cell."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("cell", ["chat", "docqa", "1chip", "fsdp4"])
def test_cell_runs_tiny_on_cpu(cell):
    p = subprocess.run([sys.executable, os.path.join(HERE, "rehearse.py"),
                        cell], capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and "setup_s" in line["metrics"]
    assert line["device"]["platform"] == "cpu"
    assert "compilations inside the window: 0" in p.stdout


def test_the_command_fails_without_a_chip():
    root = os.path.dirname(os.path.dirname(HERE))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "train-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
