"""run.py refuses to produce a result off the chip and outside a checkout."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
ARGS = ["--workload", "production256-x8.train", "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_only_the_benchmark_files_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                      "__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert "no repro package" in out.stderr
    assert out.stdout == ""


@pytest.mark.parametrize("workload", ["no-such-cell"])
def test_unknown_cell_no_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "no workload" in out.stderr
