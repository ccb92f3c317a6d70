"""Find a cell's files by the names in ``BENCHMARK.json``.

A workload names a configuration and a traffic mix; everything else is a
file found by name, so a later cell, mix or metric is added by adding
files and entries:

- ``configs[].file``                 the configuration as run (JSON)
- ``bench/traffic/<traffic>.json``   the mix's parameters; its ``driver``
                                     names ``bench/drivers/<driver>.py``
- ``bench/limits/<workload>.json``   the limit of each number compared
- ``bench/metrics/<metric>.py``      one per-layer metric's reader
- ``bench/reference/<reference>.py`` the configuration's plain reference
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

#: configuration-file keys (the source's names) -> ``ArchConfig`` fields
ARCH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    e2e: list
    per_layer: list
    bench: dict
    dir: Path = BENCH

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def driver(self):
        return load_module(self.dir / "drivers" / f"{self.traffic['driver']}.py",
                           f"bench_driver_{self.traffic['driver']}")

    def reference(self):
        ref = self.config["reference"]
        return load_module(self.dir / "reference" / f"{ref}.py",
                           f"bench_reference_{ref}")

    def readers(self):
        """{metric name: reader module} for this cell's per-layer metrics."""
        return {m["name"]: load_module(self.dir / "metrics" / f"{m['name']}.py",
                                       "bench_metric_" + m["name"]
                                       .replace(".", "_"))
                for m in self.per_layer}


def _reports(metric: dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    bench_dir = root / "bench"
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, workload=w, config=config, traffic=traffic,
                limits=limits, e2e=e2e, per_layer=per_layer, bench=bench,
                dir=bench_dir)


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file, registered
    under the file's own name so entry points that take an arch name
    (``LMStudy``) find it.  Every mapped key of the file must be what the
    resulting config runs."""
    from repro import configs as C
    base = C.get_config(config["program_arch"])
    changes = {field: config[key] for key, field in ARCH_KEYS.items()
               if key in config}
    cfg = dataclasses.replace(base, name=config["name"], **changes)
    for key, field in ARCH_KEYS.items():
        if key in config and getattr(cfg, field) != config[key]:
            raise ValueError(f"{config['name']}: {key} runs as "
                             f"{getattr(cfg, field)}, file says {config[key]}")
    C.ARCHS[cfg.name] = cfg
    return cfg
