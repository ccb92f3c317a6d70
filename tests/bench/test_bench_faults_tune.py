"""The tuning cells' comparison catches a broken timed path: a whole run
at CPU size, with a fault planted in the program underneath, comes out
not correct, and so does the control."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny as B  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.models import layers as ML  # noqa: E402
from repro.train import optim  # noqa: E402
from repro.tune.selective import SelectiveTimer  # noqa: E402

WORKLOAD = B.first_workload("tune")


def _answer_altered(mp):
    real = ML.ffn_block
    mp.setattr(ML, "ffn_block", lambda p, x, cfg: 1.05 * real(p, x, cfg))


def _half_batch(mp):
    real = ML.attn_block

    def half(p, x, cfg, **kw):
        out, kv = real(p, x, cfg, **kw)
        keep = jnp.arange(x.shape[0])[:, None, None] < x.shape[0] // 2
        return jnp.where(keep, out, 0.0) * 2.0, kv
    mp.setattr(ML, "attn_block", half)


def _state_unchanged(mp):
    real = optim.adamw_update

    def unchanged(cfg, params, grads, state):
        _, _, metrics = real(cfg, params, grads, state)
        return params, state, metrics
    mp.setattr(optim, "adamw_update", unchanged)


def _skip_all(mp):
    mp.setattr(SelectiveTimer, "_should_execute",
               lambda self, sig, freq: False)


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch,
                                   _state_unchanged, _skip_all])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    rc, line, err = B.run_cell(B.tiny_root(tmp_path, WORKLOAD), seconds=0.5)
    assert rc == 0
    assert line["correct"] is False, line["checks"]


def test_control_is_not_correct(tmp_path):
    from harness.spans import Recorder
    cell = B.tiny_cell(tmp_path, WORKLOAD)
    drv = cell.driver()
    st = drv.setup(cell, Recorder(traced=False), 3000000077)
    drv.window(st, 0.5)
    drv.release(st)
    prog = drv.check(st)
    ctrl = drv.check(st, drv.control(st))
    lim = {k: v["limit"] for k, v in cell.limits.items()}
    assert all(prog[k] <= lim[k] for k in prog), prog
    assert any(ctrl[k] > lim[k] for k in ctrl), ctrl


def test_answer_faults_read_what_they_plant(tmp_path):
    """The tuner's answers under each planted fault, on answers of known
    times: trials that execute nothing predict 0 (error 1), and a campaign
    that picks its slowest configuration loses 1 - fastest / slowest."""
    cell = B.tiny_cell(tmp_path, WORKLOAD)
    drv = cell.driver()

    class St:
        answers = [[[1.0, 1.05], [2.0, 1.9], [4.0, 4.2]], [[1.5, 1.5]]]
    prog = drv.answer_numbers(St.answers)
    assert prog["pred_err_max"] == pytest.approx(0.05)
    assert prog["winner_loss_max"] == 0.0
    skip = drv.answer_numbers(drv.skip_all(St).answers(St.answers))
    assert skip["pred_err_max"] == skip["pred_err_median"] == 1.0
    worst = drv.answer_numbers(drv.worst_winner(St).answers(St.answers))
    assert worst["winner_loss_max"] == pytest.approx(0.75)
