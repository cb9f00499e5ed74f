"""``run.py`` refuses to measure anywhere but on a TPU: with JAX held to
the CPU it exits non-zero and prints no result line."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(BENCH / "run.py"), *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_no_tpu_no_result(trace):
    p = _run("--workload", CELLS[0], "--seed", str(2**31 + 5),
             "--seconds", "1", "--trace", trace)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_workload_no_result():
    p = _run("--workload", "no_such.cell", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
