"""bench/run.py refuses to run where it cannot measure: without a TPU,
and in a directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchtiny import ROOT  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ARGS = ["--workload", BENCH["workloads"][0]["name"], "--seed",
        "3000000123", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *BENCH["command"][1:], *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.startswith("{")
                   for line in proc.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    _no_result(proc)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    _no_result(_run(tmp_path))
