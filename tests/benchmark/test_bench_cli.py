"""``benchmark/run.py`` never falls back to the CPU."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "not a TPU" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no-such.cell",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0 and '"correct"' not in p.stdout
