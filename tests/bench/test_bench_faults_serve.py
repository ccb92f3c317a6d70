"""The serving cell's comparison catches a broken timed path: a whole run
at CPU size, with a fault planted in the engine, comes out not correct,
and so does the control."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny as B  # noqa: E402

from repro.models.model import Model  # noqa: E402
from repro.serve.engine import Engine  # noqa: E402

WORKLOAD = B.first_workload("serve")


def _token_altered(mp):
    real = Engine._sample
    calls = {"n": 0}

    def sample(self, logits):
        calls["n"] += 1
        tok = real(self, logits)
        return (tok + 1) % logits.shape[-1] if calls["n"] % 7 == 0 else tok
    mp.setattr(Engine, "_sample", sample)


def _cache_unchanged(mp):
    real = Model.decode_step

    def decode(self, params, cache, t, batch):
        logits, _ = real(self, params, cache, t, batch)
        return logits, cache
    mp.setattr(Model, "decode_step", decode)


@pytest.mark.parametrize("fault", [_token_altered, _cache_unchanged])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    rc, line, err = B.run_cell(B.tiny_root(tmp_path, WORKLOAD), seconds=1.0)
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def test_control_is_not_correct(tmp_path):
    """At smollm's widths (2 layers, 4,096 rows of vocabulary) and with
    300 served tokens compared, as the cell compares: the tiny model's
    widths are too small for fp8's error to reach the cell's limit."""
    from harness import spec
    from harness.spans import Recorder
    w = {x["name"]: x for x in B.workloads()}[WORKLOAD]
    cfg = dict(B.real_config(w["config"]), num_hidden_layers=2,
               vocab_size=4096)
    traffic = dict(B.traffic_of(WORKLOAD), **B.TINY_TRAFFIC["serve"])
    traffic.update(rate_per_s=20.0, check_tokens=300)
    root = B.make_root(tmp_path, traffic, B.limits_of(WORKLOAD),
                       config=cfg)
    cell = spec.load_cell("tiny.cell", root)
    drv = cell.driver()
    st = drv.setup(cell, Recorder(traced=False), 1)
    drv.window(st, 3.0)
    drv.release(st)
    lim = {k: v["limit"] for k, v in cell.limits.items()}
    prog = drv.check(st)
    assert all(prog[k] <= lim[k] for k in prog), prog
    ctrl = drv.check(st, drv.control(st))
    assert any(ctrl[k] > lim[k] for k in ctrl), ctrl
    assert np.isfinite(list(ctrl.values())).all()
