"""BENCHMARK.json against the benchmark's contract, and every name in it
against the file it resolves to."""

import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from benchtiny import ROOT  # noqa: E402

from harness import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(WORKLOADS)
        for w in m["workloads"]:
            assert (m["moves"] not in e2e or "workloads" not in e2e[m["moves"]]
                    or w in e2e[m["moves"]]["workloads"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves(workload):
    cell = spec.load_cell(workload, ROOT)
    assert cell.traffic["driver"]
    drv = cell.driver()
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(drv, fn)), fn
    assert callable(cell.reference().rms_norm)
    assert cell.limits, "a cell without limits cannot be correct"
    assert any(m["name"] != "setup_s" for m in cell.e2e)
    assert cell.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", METRICS)
def test_metric_has_reader(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    mod = spec.load_module(path, "reader_" + metric.replace(".", "_"))
    assert callable(mod.read)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_what_runs(entry):
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    cfg = spec.arch_config(config)
    for key, field in spec.ARCH_KEYS.items():
        if key in config:
            assert getattr(cfg, field) == config[key], key
    assert cfg.head_dim * cfg.n_heads == cfg.d_model
