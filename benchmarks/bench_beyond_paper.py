"""Beyond-paper features: (a) racing search driven by the paper's own CIs,
(b) per-family input-size extrapolation (paper §VIII future work), wired to
the CANDMC study where the shrinking trailing matrix makes per-signature
modeling weakest.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.api import AutotuneSession, SimBackend
from repro.core.models import Extrapolator
from repro.core.policies import policy
from repro.linalg.studies import STUDIES, candmc_qr_study

from .common import fmt_table, save_rows


def bench_racing(fast=True):
    """Racing vs exhaustive on the Capital study: same winner, less cost."""
    rows = []
    pol = policy("online", tolerance=0.25)
    for study_name in ("capital-cholesky", "slate-cholesky"):
        space = STUDIES[study_name]("ci")
        rep_ex = AutotuneSession(space, SimBackend(), policy=pol, trials=3,
                                 seed=0).run()
        rep_rc = AutotuneSession(
            space, SimBackend(), policy=pol, trials=1, seed=0,
            search="racing",
            search_options={"max_rounds": 4 if fast else 8}).run()
        best = rep_rc.extra["best"]
        racing_iters = rep_rc.extra["total_iterations"]
        exhaustive_iters = 3 * len(space)
        rows.append({
            "study": study_name,
            "exhaustive_best": rep_ex.true_best.name,
            "racing_best": best,
            "agree": best == rep_ex.chosen.name
            or best == rep_ex.true_best.name,
            "exhaustive_iters": exhaustive_iters,
            "racing_iters": racing_iters,
            "iter_reduction": exhaustive_iters / max(racing_iters, 1),
        })
    print("\n== racing search (beyond paper) ==")
    print(fmt_table(rows, ("study", "exhaustive_best", "racing_best",
                           "agree", "exhaustive_iters", "racing_iters",
                           "iter_reduction")))
    save_rows("racing", rows)
    return rows


def bench_extrapolation(fast=True):
    """Fit t ~ a*flops + b*bytes + c per op family on CANDMC's kernels;
    validate on held-out (larger) signatures."""
    space = candmc_qr_study("ci")
    run = SimBackend().open(space, policy("conditional", tolerance=0.25),
                            seed=0)
    rt, critter = run.runtime, run.critter
    # collect statistics from two full executions of the first config
    prog = space.points[0].payload(run.world)
    for _ in range(2):
        rt.run(prog, force_execute=True, update_stats=True)
    kbar = critter.pooled_kbar()

    rows = []
    fams = {}
    for sig, stats in kbar.items():
        if stats.n >= 2:
            fams.setdefault((sig.kind, sig.name), []).append((sig, stats))
    for fam, entries in sorted(fams.items()):
        if len(entries) < 5:
            continue
        # hold out the largest-flops signature, fit on the rest
        from repro.core.signatures import flops_of, bytes_of
        entries = sorted(entries, key=lambda e: flops_of(e[0])
                         + bytes_of(e[0]))
        held_sig, held_stats = entries[-1]
        ex = Extrapolator(min_signatures=4, max_rel_err=1.0)
        ex.refit(dict(entries[:-1]))
        pred = ex.predict(held_sig)
        if pred is None:
            continue
        t_hat, unc = pred
        rows.append({
            "family": f"{fam[0]}:{fam[1]}",
            "n_fit_sigs": len(entries) - 1,
            "held_out": str(held_sig),
            "true_ms": held_stats.mean * 1e3,
            "pred_ms": t_hat * 1e3,
            "rel_err": abs(t_hat - held_stats.mean) / held_stats.mean,
            "model_unc": unc,
        })
    print("\n== input-size extrapolation (paper §VIII future work) ==")
    print(fmt_table(rows, ("family", "n_fit_sigs", "true_ms", "pred_ms",
                           "rel_err", "model_unc")))
    good = [r for r in rows if r["rel_err"] < 0.5]
    print(f"  {len(good)}/{len(rows)} families extrapolate the held-out "
          f"(largest) signature within 50%")
    save_rows("extrapolation", rows)
    return rows


def bench_extrapolate_policy(fast=True):
    """End-to-end effect of policy(extrapolate=True) on CANDMC — the study
    whose shrinking trailing matrix defeats per-signature modeling."""
    rows = []
    for tol in ((0.25,) if fast else (0.5, 0.25, 0.125)):
        for extra in (False, True):
            rep = AutotuneSession(candmc_qr_study("ci"), SimBackend(),
                                  policy=policy("online", tolerance=tol,
                                                extrapolate=extra),
                                  trials=3, seed=0).run()
            rows.append({"tolerance": tol, "extrapolate": extra,
                         "speedup": rep.speedup,
                         "mean_error": rep.mean_error,
                         "optimum_quality": rep.optimum_quality})
    print("\n== extrapolate policy on CANDMC (end to end) ==")
    print(fmt_table(rows, ("tolerance", "extrapolate", "speedup",
                           "mean_error", "optimum_quality")))
    save_rows("extrapolate_policy", rows)
    return rows


def run(fast=True):
    r1 = bench_racing(fast)
    r2 = bench_extrapolation(fast)
    r3 = bench_extrapolate_policy(fast)
    return r1 + r2 + r3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    run(fast=not args.full)


if __name__ == "__main__":
    main()
