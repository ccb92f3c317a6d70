"""Cold-vs-warm autotuning: cross-study statistics transfer on Capital.

Measures what ``repro.api.transfer`` buys on the paper's Capital Cholesky
study (the study whose kernels recur across configurations — the eager
policy's home turf, §VI.B):

1. **cold**  — a fresh eager study at the base tolerance, collecting its
   per-kernel statistics bank (saved under ``results/`` for reuse — e.g.
   warm-starting the minutes-to-hours SLATE@1024 / CANDMC@4096 paper-scale
   sweep points from a recorded CI-scale artifact);
2. **warm**  — the same study seeded with that bank: already-confident
   kernels start in the skip regime, so the study must select the SAME
   configuration while executing measurably fewer kernel invocations;
3. **warm-tight** — transfer across the tolerance grid: the base-tolerance
   bank seeding a tighter-tolerance study (the next sweep point), the
   common warm-start during a paper-protocol epsilon sweep.

Run: ``PYTHONPATH=src python -m benchmarks.bench_transfer``
(or through ``benchmarks.run --sections transfer``).
"""

from __future__ import annotations

import argparse
import os
import time
from typing import List, Optional

from repro.api import AutotuneSession, SimBackend
from repro.linalg.studies import STUDIES

from .common import ART, fmt_table, save_rows

COLS = ("run", "policy", "tolerance", "chosen", "executed", "skipped",
        "selective_time", "mean_error", "speedup", "bench_wall_s")


def _row(tag: str, result) -> dict:
    return {
        "run": tag, "policy": result.policy,
        "tolerance": result.tolerance, "chosen": result.chosen.name,
        "executed": sum(r.executed for r in result.records),
        "skipped": sum(r.skipped for r in result.records),
        "selective_time": result.selective_tuning_time,
        "mean_error": result.mean_error, "speedup": result.speedup,
        "bench_wall_s": round(result.wall_s, 1),
    }


def run(study: str = "capital-cholesky", scale: str = "ci",
        policy: str = "eager", tolerance: float = 0.25,
        tight_tolerance: float = 0.0625, trials: int = 3,
        discount: float = 0.5,
        bank_path: Optional[str] = None) -> List[dict]:
    space = STUDIES[study](scale)

    def session(**kw):
        return AutotuneSession(space, backend=SimBackend(), policy=policy,
                               trials=trials, **kw)

    t0 = time.time()
    cold = session(tolerance=tolerance, collect_stats=True).run()
    bank = cold.stats_bank()
    if bank_path is None:
        os.makedirs(ART, exist_ok=True)
        bank_path = os.path.join(ART, f"{study}-{scale}_stats_bank.json")
    bank.save(bank_path)
    print(f"cold study: {time.time() - t0:.1f}s, bank {len(bank)} kernels "
          f"-> {bank_path}")

    warm = session(tolerance=tolerance, prior=bank,
                   prior_discount=discount).run()
    warm_tight = session(tolerance=tight_tolerance, prior=bank,
                         prior_discount=discount).run()

    rows = [_row("cold", cold), _row("warm", warm),
            _row("warm-tight", warm_tight)]
    print(f"\n== transfer: {study} ({scale} scale, {policy}, "
          f"discount {discount}) ==")
    print(fmt_table(rows, COLS))

    same = warm.chosen.name == cold.chosen.name
    fewer = rows[1]["executed"] < rows[0]["executed"]
    print(f"\nwarm selects the cold winner: {same}; "
          f"executed {rows[0]['executed']} -> {rows[1]['executed']} "
          f"({'OK' if fewer else 'NO SAVINGS'}); selective time "
          f"{rows[0]['selective_time']:.3g}s -> "
          f"{rows[1]['selective_time']:.3g}s")
    if not (same and fewer):
        raise SystemExit("transfer acceptance failed: warm study must "
                         "keep the winner and execute fewer kernels")
    save_rows("transfer", rows)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--study", default="capital-cholesky",
                    choices=list(STUDIES))
    ap.add_argument("--scale", default="ci", choices=["ci", "paper"])
    ap.add_argument("--policy", default="eager")
    ap.add_argument("--tolerance", type=float, default=0.25)
    ap.add_argument("--tight", type=float, default=0.0625)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--discount", type=float, default=0.5)
    ap.add_argument("--bank", default=None,
                    help="where to save the harvested statistics bank")
    args = ap.parse_args()
    run(study=args.study, scale=args.scale, policy=args.policy,
        tolerance=args.tolerance, tight_tolerance=args.tight,
        trials=args.trials, discount=args.discount, bank_path=args.bank)


if __name__ == "__main__":
    main()
