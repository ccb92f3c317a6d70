"""Every cell's traffic driver runs a whole benchmark run on the CPU at a
reduced size (bench/run.py's main, the look for a chip replaced by the
CPU device) and comes out correct against the cell's own limits."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny as B  # noqa: E402


@pytest.mark.parametrize("workload", [w["name"] for w in B.workloads()])
def test_cell_runs_correct_at_cpu_size(tmp_path, workload):
    root = B.tiny_root(tmp_path, workload)
    rc, line, err = B.run_cell(root, seconds=1.0)
    assert rc == 0
    assert line["correct"], (line, err[-3000:])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["info"]["compiles_in_window"] == 0
    for name, m in line["metrics"].items():
        assert m["value"] > 0, name
    assert "setup_s" in line["metrics"]
