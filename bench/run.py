"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; the mix names its driver (``bench/drivers/<driver>.py``),
which sets the cell up (every compile is set-up), runs the measured
window, frees the program's state and returns the numbers that decide
``correct``.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces a short window (the mix's ``trace_seconds``) and
reports its per-layer metrics, each read by ``bench/metrics/<name>.py``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with its limit, also printed as
the last lines of stderr.  Without a TPU, or with fewer chips than the
cell asks for, or a chip missing from the peak table, it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import spec  # noqa: E402
from harness.peaks import peak_for  # noqa: E402
from harness.spans import Recorder  # noqa: E402


class NoChip(SystemExit):
    pass


def look_for_chip(cell) -> list:
    """The chips this cell runs on; exits when they are not there."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX finds {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell asks for {cell.chips} chips, found {len(devs)}")
    peak_for(devs[0].device_kind)
    return devs[:cell.chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class Context:
    """What a per-layer metric's reader sees."""

    def __init__(self, cell, rec, trace, peak):
        self.cell, self.trace, self.peak = cell, trace, peak
        self.counters = rec.counters
        self.kernel_costs = rec.kernel_costs


def compare(checks: dict, limits: dict) -> dict:
    """Each number beside its limit; a number with no limit fails."""
    out = {}
    for name, value in checks.items():
        lim = limits.get(name, {}).get("limit")
        ok = lim is not None and value == value and value <= lim
        out[name] = {"value": value, "limit": lim, "ok": bool(ok)}
    return out


def main(argv=None, *, find_chip=look_for_chip, root=ROOT) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, root)
    import repro  # noqa: F401  (the program: a checkout without it fails)
    devs = find_chip(cell)
    dev = devs[0]
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import jax

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    rec = Recorder(traced=bool(args.trace))
    drv = cell.driver()
    st = drv.setup(cell, rec, args.seed)
    setup_s = time.perf_counter() - T_START
    n_compiles = len(compiles)

    reduction, trace_dir = None, None
    if args.trace:
        from harness.trace import find_xplane, reduce_trace
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        with rec.span("bench window"):
            res = drv.window(st, float(cell.traffic["trace_seconds"]))
        jax.profiler.stop_trace()
    else:
        res = drv.window(st, args.seconds)
    rec.counters["compiles_in_window"] = len(compiles) - n_compiles
    peak_bytes = memory_peak(devs)
    if trace_dir:
        reduction = reduce_trace(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    drv.release(st)
    checks = compare(drv.check(st), cell.limits)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    line = {"correct": False, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": {}, "device": device}
    peak = peak_for(dev.device_kind) if dev.platform == "tpu" else None
    if args.trace:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        ctx = Context(cell, rec, reduction, peak)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        for name, reader in cell.readers().items():
            value = reader.read(ctx)
            if value is not None:
                line["metrics"][name] = {"value": value, "unit": units[name]}
        line["breakdown"] = {"device_ops": reduction.top_ops,
                             "idle_gaps": reduction.idle_gaps}
    else:
        units = {m["name"]: m["unit"] for m in cell.e2e}
        values = dict(res["metrics"], setup_s=setup_s)
        for m in cell.e2e:
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": units[m["name"]]}
    line["correct"] = (all(c["ok"] for c in checks.values())
                       and line["failed"] == 0 and line["attempted"] > 0)
    line["info"] = dict(res.get("info", {}),
                        compiles_in_window=rec.counters["compiles_in_window"],
                        window_s=res.get("elapsed_s"),
                        **getattr(st, "check_info", {}))
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    sys.stdout.flush()
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
