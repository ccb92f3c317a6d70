"""Deterministic golden-report harness for the engine refactor.

Runs the exhaustive-autotune protocol over tiny versions of the three
op-mix-distinct case studies (SLATE Cholesky: nonblocking p2p; Capital:
sub-communicator collectives; CANDMC: blocking p2p + collectives) under all
five selective-execution policies, with a FULLY DETERMINISTIC cost model
(``bias_sigma=0`` removes the allocation-bias term; since PR 2 the bias
itself is also process-stable — crc32, not ``hash()`` — so even
bias_sigma>0 studies reproduce across processes and checkpoint resumes).

``compute_goldens()`` runs each study through ``AutotuneSession`` over a
``SimBackend`` and returns a nested dict of every ConfigRecord field.
``python -m tests.golden_runner`` (from the repo root, with PYTHONPATH=src)
regenerates ``tests/golden_reports.json``; the committed file was produced
by the PRE-refactor seed engine, so ``tests/test_golden_reports.py`` pins
the optimized engine to bit-identical protocol output.
"""

from __future__ import annotations

import json
import os

from repro.api import AutotuneSession, ConfigPoint, SearchSpace, SimBackend
from repro.core.policies import POLICIES, policy
from repro.linalg import candmc_qr, capital_cholesky, slate_cholesky
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden_reports.json")


def _studies():
    slate = SearchSpace(
        name="golden-slate", world_size=16, reset_between_configs=True,
        machine=KNL_STAMPEDE2,
        points=[
            ConfigPoint(
                name="slate-t64-la1", params={},
                payload=lambda w: slate_cholesky.make_program(
                    w, n=512, tile=64, lookahead=1, pr=4, pc=4)),
            ConfigPoint(
                name="slate-t128-la0", params={},
                payload=lambda w: slate_cholesky.make_program(
                    w, n=512, tile=128, lookahead=0, pr=4, pc=4)),
        ])
    capital = SearchSpace(
        name="golden-capital", world_size=8, reset_between_configs=False,
        machine=KNL_STAMPEDE2,
        points=[
            ConfigPoint(
                name="capital-b32-s1", params={},
                payload=lambda w: capital_cholesky.make_program(
                    w, n=256, block=32, strategy=1, grid_c=2)),
            ConfigPoint(
                name="capital-b64-s2", params={},
                payload=lambda w: capital_cholesky.make_program(
                    w, n=256, block=64, strategy=2, grid_c=2)),
        ])
    candmc = SearchSpace(
        name="golden-candmc", world_size=16, reset_between_configs=True,
        machine=KNL_STAMPEDE2,
        points=[
            ConfigPoint(
                name="candmc-b16-g4x4", params={},
                payload=lambda w: candmc_qr.make_program(
                    w, m=1024, n=128, block=16, pr=4, pc=4)),
        ])
    return (slate, capital, candmc)


def golden_space(index: int = 1):
    """One tiny golden study — also the remote-worker spec used by the
    scheduler smoke tests and ``check.sh --stage scheduler``: ``python -m
    repro.api.worker --spec golden_runner:golden_space --spec-args
    '{"index": 1}'`` (with tests/ on PYTHONPATH)."""
    return _studies()[index]


def compute_goldens() -> dict:
    out = {}
    for space in _studies():
        srec = {}
        for pol in POLICIES:
            cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                           bias_sigma=0.0)
            rep = AutotuneSession(space, SimBackend(timer=cm.sample),
                                  policy=policy(pol, tolerance=0.25),
                                  trials=2, seed=0).run()
            srec[pol] = [
                {"name": r.name, "full_time": r.full_time,
                 "predicted": r.predicted, "rel_error": r.rel_error,
                 "comp_error": r.comp_error,
                 "selective_cost": r.selective_cost,
                 "full_cost": r.full_cost, "executed": r.executed,
                 "skipped": r.skipped, "predictions": r.predictions}
                for r in rep.records]
        out[space.name] = srec
    return out


def main():
    goldens = compute_goldens()
    with open(GOLDEN_PATH, "w") as f:
        json.dump(goldens, f, indent=1, sort_keys=True)
    print(f"wrote {GOLDEN_PATH}")


if __name__ == "__main__":
    main()
