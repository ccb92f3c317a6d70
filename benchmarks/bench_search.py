"""Model-guided search: grid coverage vs winner quality.

Sweeps the ``model_guided`` driver's measurement budget (``top_k``
survivors handed to racing) against the exhaustive reference on the
CI-scale paper grids and records, per budget, how much of the grid was
actually measured and how close the landed winner is to the true optimum
(``quality = t_best / t_winner`` over the exhaustive per-point times).

Two grids, two bank provenances:

- **capital-cholesky** seeds the copula from the committed transfer
  artifact (``results/capital-cholesky-ci_stats_bank.json``) — the
  cross-session warm start the PR-8 acceptance gate pins;
- **slate-cholesky** self-harvests its bank from the exhaustive
  reference run, the "tune once, model forever" loop for a study with
  no recorded history.

Run: ``PYTHONPATH=src python -m benchmarks.bench_search``
(or through ``benchmarks.run --sections search``).
"""

from __future__ import annotations

import argparse
import os
import time
from typing import List, Optional, Sequence

from repro.api import AutotuneSession, SimBackend, StatisticsBank
from repro.linalg.studies import STUDIES

from .common import ART, fmt_table, save_rows

COLS = ("study", "run", "top_k", "dispatched", "coverage", "winner",
        "matches", "quality", "pruned", "bench_wall_s")


def _study_rows(study: str, scale: str, top_ks: Sequence[int],
                bank: Optional[StatisticsBank], *, policy: str,
                tolerance: float, trials: int, seed: int) -> List[dict]:
    space = STUDIES[study](scale)

    def session(**kw):
        return AutotuneSession(space, backend=SimBackend(), policy=policy,
                               tolerance=tolerance, trials=trials, **kw)

    full = session(search="exhaustive",
                   collect_stats=bank is None).run()
    times = {r.name: r.predicted for r in full.records}
    t_best = min(times.values())
    if bank is None:
        bank = full.stats_bank()        # self-harvested reference bank
        provenance = "self-harvested"
    else:
        provenance = "committed artifact"
    rows = [{
        "study": study, "run": "exhaustive", "top_k": len(space),
        "dispatched": len(space), "coverage": 1.0,
        "winner": full.chosen.name, "matches": True, "quality": 1.0,
        "pruned": 0, "bench_wall_s": round(full.wall_s, 1),
    }]
    print(f"{study}: exhaustive reference over {len(space)} points, "
          f"winner {full.chosen.name!r}, bank {len(bank)} kernels "
          f"({provenance})")

    for k in top_ks:
        guided = session(
            search="model_guided",
            search_options={"banks": [bank], "seed": seed, "top_k": k,
                            "max_coverage": 1.0}).run()
        winner = guided.extra["best"]
        rows.append({
            "study": study, "run": "model-guided", "top_k": k,
            "dispatched": len(guided.extra["dispatched"]),
            "coverage": guided.extra["coverage"],
            "winner": winner, "matches": winner == full.chosen.name,
            "quality": t_best / times[winner],
            "pruned": len(guided.extra["roofline_pruned"]),
            "bench_wall_s": round(guided.wall_s, 1),
        })
    return rows


def run(scale: str = "ci", top_ks: Sequence[int] = (1, 2, 4, 8),
        policy: str = "eager", tolerance: float = 0.25,
        trials: int = 2, seed: int = 0) -> List[dict]:
    t0 = time.time()
    committed = os.path.join(ART, "capital-cholesky-ci_stats_bank.json")
    rows = _study_rows(
        "capital-cholesky", scale, top_ks,
        StatisticsBank.load(committed) if os.path.exists(committed)
        else None,
        policy=policy, tolerance=tolerance, trials=trials, seed=seed)
    rows += _study_rows("slate-cholesky", scale, top_ks, None,
                        policy=policy, tolerance=tolerance, trials=trials,
                        seed=seed)

    print(f"\n== model-guided search: coverage vs winner quality "
          f"({scale} scale, {policy} @ {tolerance}) ==")
    print(fmt_table(rows, COLS))

    # acceptance: at every budget the winner must stay within 1% of the
    # exhaustive optimum — the sampler may measure less, never choose worse
    bad = [(r["study"], r["top_k"]) for r in rows if r["quality"] < 0.99]
    if bad:
        raise SystemExit("search acceptance failed: winner quality "
                         f"< 0.99 at {bad}")
    least = min((r for r in rows if r["run"] == "model-guided"),
                key=lambda r: r["coverage"])
    print(f"\nleanest budget: {least['study']} top_k={least['top_k']} "
          f"measured {least['coverage']:.1%} of the grid at quality "
          f"{least['quality']:.3f}")
    print(f"total wall: {time.time() - t0:.1f}s")
    save_rows("search", rows)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="ci", choices=["ci", "paper"])
    ap.add_argument("--top-ks", type=int, nargs="*", default=[1, 2, 4, 8])
    ap.add_argument("--policy", default="eager")
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(scale=args.scale, top_ks=args.top_ks, policy=args.policy,
        tolerance=args.tolerance, trials=args.trials, seed=args.seed)


if __name__ == "__main__":
    main()
