"""A benchmark tree at a size the CPU holds, for the tests of bench/.

``make_root`` copies ``bench/`` into a temporary root, adds a tiny dense
configuration (same architecture and reference as the real ones, widths
cut for the CPU only) and one workload of the given mix, and writes a
``BENCHMARK.json`` naming it.  ``run_cell`` drives ``bench/run.py``'s
``main`` there with the look for a chip replaced by the CPU device.
"""

from __future__ import annotations

import contextlib
import io
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

TINY = {
    "name": "tiny", "source": "test only: smollm-135m's block at CPU size",
    "program_arch": "smollm-135m", "reference": "dense_lm",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": True,
}


def real_config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def make_root(tmp: Path, traffic: dict, limits: dict, *,
              config: dict = None, e2e=None) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    cfg = dict(config or TINY)
    cfg.setdefault("optimizer", real_config("smollm-135m")["optimizer"])
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / "tiny.cell.json").write_text(
        json.dumps(limits))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": real["run_seconds"],
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.cell", "config": "tiny",
                       "traffic": "tiny", "chips": 1, "why": "test"}],
        "end_to_end": [dict(m, workloads=["tiny.cell"])
                       for m in real["end_to_end"]
                       if m["name"] in (e2e or ()) or m["name"] == "setup_s"],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu_chip(cell):
    import jax
    return jax.devices("cpu")[:1]


def run_cell(root: Path, *, seed=123456789012, seconds=0.5, trace=0):
    """Run the tiny cell; returns (rc, last-line JSON, stderr)."""
    sys.path.insert(0, str(root / "bench"))
    from importlib import util
    spec = util.spec_from_file_location("bench_run_under_test",
                                        root / "bench" / "run.py")
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from repro.launch import compile_cache
    out, err = io.StringIO(), io.StringIO()
    keep = compile_cache.enable_compilation_cache
    # the persistent cache is the chip's: a test compiles for the CPU only
    compile_cache.enable_compilation_cache = lambda: None
    try:
        with contextlib.redirect_stdout(out), \
                contextlib.redirect_stderr(err):
            rc = mod.main(["--workload", "tiny.cell", "--seed", str(seed),
                           "--seconds", str(seconds), "--trace",
                           str(trace)], find_chip=cpu_chip, root=root)
    finally:
        compile_cache.enable_compilation_cache = keep
    sys.path.remove(str(root / "bench"))
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), err.getvalue()


#: each driver's traffic cut to a size the CPU runs in seconds
TINY_TRAFFIC = {
    "tune": {"batch": 4, "seq": 32},
    "train": {"batch": 4, "seq": 32, "kv_chunk": 16},
    "serve": {"slots": 4, "s_max": 128, "prompt_buckets": [16, 32, 64],
              "rate_per_s": 4.0, "drain_s": 30, "check_tokens": 40,
              "prompt_len": {"median": 20, "sigma": 0.6, "min": 4,
                             "max": 64},
              "output_len": {"median": 8, "sigma": 0.5, "min": 2,
                             "max": 24}},
}


#: the tuner's answers are timings, and at CPU size its kernels take
#: microseconds on a shared host: the tiny tree judges them by the median
#: configuration's prediction error, loosely (a tuner that predicts
#: nothing reads 1), in place of the cell's own answer limits
TINY_ANSWERS = {"pred_err_median": {"limit": 0.5}}


def workloads():
    return json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]


def traffic_of(workload: str) -> dict:
    w = {x["name"]: x for x in workloads()}[workload]
    return json.loads((ROOT / "bench" / "traffic"
                       / f"{w['traffic']}.json").read_text())


def limits_of(workload: str) -> dict:
    return json.loads((ROOT / "bench" / "limits"
                       / f"{workload}.json").read_text())


def tiny_root(tmp: Path, workload: str) -> Path:
    """The workload's mix at CPU size, with the cell's own limits."""
    traffic = traffic_of(workload)
    traffic.update(TINY_TRAFFIC[traffic["driver"]])
    limits = limits_of(workload)
    if traffic["driver"] == "tune":
        limits = {k: v for k, v in limits.items()
                  if not k.startswith(("pred_err", "winner_loss"))}
        limits.update(TINY_ANSWERS)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = [m["name"] for m in real["end_to_end"]
           if workload in m.get("workloads", ())]
    return make_root(tmp, traffic, limits, e2e=e2e)


def tiny_cell(tmp: Path, workload: str):
    from harness import spec
    return spec.load_cell("tiny.cell", tiny_root(tmp, workload))


def first_workload(driver: str) -> str:
    for w in workloads():
        t = json.loads((ROOT / "bench" / "traffic"
                        / f"{w['traffic']}.json").read_text())
        if t["driver"] == driver:
            return w["name"]
    raise KeyError(driver)
