"""Tuner behaviour on the paper's case studies (CI scale, fast subsets),
through ``AutotuneSession`` over a ``SimBackend``."""

import numpy as np
import pytest

from repro.api import AutotuneSession, SimBackend
from repro.core.policies import policy
from repro.linalg.studies import capital_cholesky_study
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2


def tiny_capital_study(n_configs=4):
    return capital_cholesky_study("ci").subset(n_configs)


def _tune(space, pol, **kw):
    return AutotuneSession(space, SimBackend(), policy=pol, **kw).run()


def test_exhaustive_tuner_speedup_and_optimum():
    rep = _tune(tiny_capital_study(), policy("eager", tolerance=0.3),
                trials=3, seed=0)
    assert rep.speedup > 1.5
    assert rep.optimum_quality >= 0.95
    assert all(r.rel_error < 0.6 for r in rep.records)


def test_error_decreases_with_tolerance():
    errs = {}
    for tol in (1.0, 0.05):
        rep = _tune(tiny_capital_study(), policy("online", tolerance=tol),
                    trials=3, seed=1)
        errs[tol] = rep.mean_error
    assert errs[0.05] <= errs[1.0] + 0.02


def test_apriori_charges_offline_pass():
    t_apriori = _tune(tiny_capital_study(2),
                      policy("apriori", tolerance=0.3), trials=2, seed=0)
    t_cond = _tune(tiny_capital_study(2),
                   policy("conditional", tolerance=0.3), trials=2, seed=0)
    # the offline pass is charged to apriori's autotuning time
    assert t_apriori.selective_tuning_time > \
        0.9 * t_cond.selective_tuning_time


def test_racing_prunes_and_finds_optimum():
    space = tiny_capital_study()
    rep = _tune(space, policy("online", tolerance=0.3), trials=1, seed=0,
                search="racing", search_options={"max_rounds": 6})
    # racing must not benchmark every config every round
    assert rep.extra["total_iterations"] < 6 * len(space)
    assert rep.extra["best"] in {p.name for p in space.points}


def test_extrapolate_policy_skips_more():
    """policy(extrapolate=True) must not lose the optimum and should skip
    at least as many kernels as the plain policy (CANDMC subset)."""
    from repro.linalg.studies import candmc_qr_study

    reps = {}
    for extra in (False, True):
        reps[extra] = _tune(candmc_qr_study("ci").subset(3),
                            policy("online", tolerance=0.3,
                                   extrapolate=extra),
                            trials=2, seed=0)
    assert reps[True].optimum_quality >= 0.99
    sel = {k: r.selective_tuning_time for k, r in reps.items()}
    assert sel[True] <= sel[False] * 1.05


def test_cost_model_allocation_bias_reproducible():
    cm0 = CostModel(KNL_STAMPEDE2, allocation=0, seed=5)
    cm0b = CostModel(KNL_STAMPEDE2, allocation=0, seed=5)
    cm1 = CostModel(KNL_STAMPEDE2, allocation=1, seed=5)
    from repro.core.signatures import comp_sig
    sig = comp_sig("gemm", 64, 64, 64)
    assert cm0._bias_of(sig) == cm0b._bias_of(sig)
    assert cm0._bias_of(sig) != cm1._bias_of(sig)
    rng = np.random.default_rng(0)
    ts = [cm0.sample(sig, rng) for _ in range(50)]
    assert np.std(ts) > 0          # noise present
    assert min(ts) > 0
