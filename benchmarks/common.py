"""Shared benchmark utilities: epsilon sweeps, tables, JSON dumps.

Sweeps run through ``repro.api.AutotuneSession`` — ``workers=N`` forks one
process per in-flight sweep point (bit-identical to serial, merged in grid
order) and ``checkpoint=path`` makes long sweeps resumable.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional, Sequence

from repro.api import AutotuneSession, SimBackend, StudyResult
from repro.core.policies import POLICIES

ART = os.path.join(os.path.dirname(__file__), "results")

EPS_FULL = (1.0, 0.5, 0.25, 0.125, 0.0625, 0.03125)
EPS_FAST = (1.0, 0.25, 0.0625)


def sweep_session(make_study, *, trials: int = 3, scale: str = "ci",
                  prior=None,
                  prior_discount: float = 0.5) -> AutotuneSession:
    """Session over a paper study; ``make_study(scale)`` is one of
    ``repro.linalg.studies.STUDIES``.  ``prior`` is a ``StatisticsBank``
    warm-starting every study of the sweep (repro.api.transfer);
    ``prior_discount=1.0`` keeps its full evidence (same-machine,
    same-cost-model banks need no widening)."""
    return AutotuneSession(make_study(scale),
                           backend=SimBackend(), trials=trials,
                           prior=prior, prior_discount=prior_discount)


def sweep_study(make_study, *, policies: Sequence[str] = POLICIES,
                eps: Sequence[float] = EPS_FAST, trials: int = 3,
                seeds: Sequence[int] = (0,), allocations=(0,),
                scale: str = "ci", workers: int = 1,
                checkpoint: Optional[str] = None,
                prior=None, prior_discount: float = 0.5,
                share_stats: bool = False,
                deterministic: bool = False,
                executor=None) -> List[dict]:
    """The paper's measurement protocol (§VI.A): for each policy x epsilon
    (x allocation), run the full exhaustive autotune and record speedup,
    mean prediction error, optimum quality.  ``workers=0`` means one per
    CPU; ``share_stats``/``deterministic``/``executor`` pass through to
    ``AutotuneSession.sweep`` (mid-sweep statistics sharing; remote
    workers)."""
    if workers <= 0:
        # floor of 2 so single-core boxes still go through the fork pool
        # (bit-identical to serial) instead of silently degenerating
        workers = max(os.cpu_count() or 1, 2)
    session = sweep_session(make_study, trials=trials, scale=scale,
                            prior=prior, prior_discount=prior_discount)
    results = session.sweep(policies=policies, tolerances=eps, seeds=seeds,
                            allocations=allocations, workers=workers,
                            checkpoint=checkpoint, share_stats=share_stats,
                            deterministic=deterministic, executor=executor)
    return [result_row(r) for r in results]


def result_row(r: StudyResult) -> dict:
    row = r.row()
    row.update(seed=r.seed, allocation=r.allocation,
               chosen=r.chosen.name,
               bench_wall_s=round(r.wall_s, 1))
    return row


def fmt_table(rows: List[dict], cols: Sequence[str], *,
              floatfmt: str = "{:.3g}") -> str:
    widths = {c: max(len(c), *(len(_fmt(r.get(c), floatfmt))
                               for r in rows)) for c in cols}
    head = " | ".join(c.ljust(widths[c]) for c in cols)
    sep = "-|-".join("-" * widths[c] for c in cols)
    body = "\n".join(
        " | ".join(_fmt(r.get(c), floatfmt).ljust(widths[c]) for c in cols)
        for r in rows)
    return f"{head}\n{sep}\n{body}"


def _fmt(v, floatfmt):
    if isinstance(v, float):
        return floatfmt.format(v)
    return str(v)


def save_rows(name: str, rows: List[dict]):
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, name + ".json"), "w") as f:
        json.dump(rows, f, indent=1)
