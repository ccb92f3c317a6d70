"""Find the serving cell's knee: the highest offered rate the engine
sustains without a growing backlog.  Run on the chip, once, when the
serving cell is defined (the cell's rate is then fixed in its traffic
file at about four fifths of the knee).

    python3 bench/tools/serve_sweep.py --workload serve.smollm-135m.steady \\
        --rates 1,2,3,4 --seconds 30 --seed 7

One process: the cell's set-up once, then one open-loop window per rate
(each drained before the next).  Per rate it prints the requests due and
finished, TTFT p50 over the first and the second half of the window (a
backlog that grows shows as a second half slower than the first), TTFT and
TPOT p95, and the engine steps taken.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import spec  # noqa: E402
from harness.spans import Recorder  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, BENCH.parent)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    drv = cell.driver()
    st = drv.setup(cell, Recorder(traced=False), args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        res = drv.window(st, args.seconds)
        tm = sorted(st.timings)
        half = args.seconds / 2
        first = [x[1] for x in tm if x[0] < half]
        second = [x[1] for x in tm if x[0] >= half]
        row = {"rate_per_s": rate, "due": res["attempted"],
               "done": res["attempted"] - res["failed"],
               "ttft_p50_first_half_ms": 1e3 * float(np.median(first))
               if first else None,
               "ttft_p50_second_half_ms": 1e3 * float(np.median(second))
               if second else None,
               "elapsed_s": res["elapsed_s"]}
        row.update(res["metrics"])
        row.update(res["info"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
