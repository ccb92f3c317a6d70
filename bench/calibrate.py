"""Readings that set a cell's limits: the program's and the control's
numbers on many seeds, in one process (the benchmark's runs never run
this).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--window 0]

For each seed: set-up as a run makes it from that seed, an optional
window at the cell's own load, the program's state freed, then the
numbers compared from the program's timed path, with the driver's
control (the plain reference in the precision below the one the
configuration states) put in its place, and with each of the driver's
planted ``FAULTS``.  Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import spec  # noqa: E402
from harness.spans import Recorder  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--window", type=float, default=0.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = spec.load_cell(args.workload, BENCH.parent)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    drv = cell.driver()
    for seed in seeds:
        st = drv.setup(cell, Recorder(traced=False), seed)
        row = {"workload": cell.name, "seed": seed}
        if args.window:
            row["window"] = drv.window(st, args.window)
            row["answers"] = getattr(st, "answers", None)
        drv.release(st)

        def read(name, produce=None):
            row[name] = drv.check(st, produce)
            row[name].update(getattr(st, "check_info", {}))
        read("program")
        read("control", drv.control(st))
        for name, fault in getattr(drv, "FAULTS", {}).items():
            read(name, fault(st))
        print(json.dumps(row, default=float), flush=True)
        del st
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
