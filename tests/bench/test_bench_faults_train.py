"""The training cell's comparison catches a broken timed path: a whole
run at CPU size, with a fault planted in the program's train step, comes
out not correct, and so do the control and the half-batch fault read the
way the chip readings are taken (bench/calibrate.py)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny as B  # noqa: E402

from repro.train import step as TS  # noqa: E402

WORKLOAD = B.first_workload("train")


def _wrap_step(mp, change):
    real = TS.make_train_step

    def make(model, rules, tc):
        inner = real(model, rules, tc)
        return lambda params, opt, batch: change(inner, params, opt, batch)
    mp.setattr(TS, "make_train_step", make)


def _state_unchanged(inner, params, opt, batch):
    _, _, met = inner(params, opt, batch)
    return params, opt, met


def _half_batch(inner, params, opt, batch):
    return inner(params, opt, {k: v[: v.shape[0] // 2]
                               for k, v in batch.items()})


def _answer_altered(inner, params, opt, batch):
    p, o, met = inner(params, opt, batch)
    final = {"ln": p["final"]["ln"] + 0.01}
    return dict(p, final=final), o, met


@pytest.mark.parametrize("change", [_state_unchanged, _half_batch,
                                    _answer_altered])
def test_fault_is_not_correct(tmp_path, monkeypatch, change):
    _wrap_step(monkeypatch, change)
    rc, line, err = B.run_cell(B.tiny_root(tmp_path, WORKLOAD), seconds=0.5)
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def test_control_and_half_batch_readings_fail(tmp_path):
    from harness.spans import Recorder
    cell = B.tiny_cell(tmp_path, WORKLOAD)
    drv = cell.driver()
    st = drv.setup(cell, Recorder(traced=False), 3000000078)
    drv.release(st)
    lim = {k: v["limit"] for k, v in cell.limits.items()}
    prog = drv.check(st)
    assert all(prog[k] <= lim[k] for k in prog), prog
    for produce in (drv.control(st), drv.half_batch(st)):
        got = drv.check(st, produce)
        assert any(got[k] > lim[k] for k in got), got
