"""Trace one cell's window and split its device idle time by the host
span over it, the program's own spans included.

    python3 bench/tools/trace_split.py --workload serve.smollm-135m.steady \\
        --seed 7 [--out split.serve.json]

Run it on the chip.  One process: the cell's set-up, then one traced
window of the mix's ``trace_seconds``, as ``bench/run.py --trace 1`` runs
it, reduced twice: by ``harness/trace.py`` (the accepted reduction) and by
``harness/idle_split.py``.  Before the set-up it times one
``jax.profiler.TraceAnnotation`` entered and left with no profiler session
and with one.  It prints one JSON object: the accepted idle share and
idle gaps, ``self_idle_s``, the offset in use and the flows' bounds on
it, every span's count, device and wall seconds, the spans per second
of the window, and the readers ``idle_engine.serve``,
``decode_ms.serve``, ``admit_ms.serve``, ``idle_stats.tune`` and
``idle_session.tune`` over the split (``bench/metrics/<name>.py``);
then the idle split and the readers again with the ops placed at the
least offset the flows allow.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import idle_split, spec  # noqa: E402
from harness.peaks import peak_for  # noqa: E402
from harness.spans import Recorder  # noqa: E402
from harness.trace import find_xplane, reduce_trace  # noqa: E402
from run import Context  # noqa: E402

READERS = ("idle_engine.serve", "decode_ms.serve", "admit_ms.serve",
           "idle_stats.tune", "idle_session.tune")


def span_cost_us(n: int, traced: bool) -> float:
    """Seconds per span entered and left, in us, with or without a
    profiler session."""
    import jax
    tmp = tempfile.mkdtemp(prefix="span-cost-") if traced else None
    if traced:
        jax.profiler.start_trace(tmp)
    t0 = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("tuner.decide"):
            pass
    dt = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
        shutil.rmtree(tmp, ignore_errors=True)
    return 1e6 * dt / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, BENCH.parent)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    cost = {"off_us_per_span": span_cost_us(200_000, False),
            "on_us_per_span": span_cost_us(20_000, True)}

    rec = Recorder(traced=True)
    drv = cell.driver()
    st = drv.setup(cell, rec, args.seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(trace_dir)
    with rec.span("bench window"):
        res = drv.window(st, float(cell.traffic["trace_seconds"]))
    jax.profiler.stop_trace()
    path = find_xplane(trace_dir)
    red = reduce_trace(path)
    split = idle_split.split_trace(path)
    at_bound = idle_split.split_trace(path, at_bound=True)
    shutil.rmtree(trace_dir, ignore_errors=True)

    peak = peak_for(dev.device_kind)
    modules = {name: spec.load_module(BENCH / "metrics" / f"{name}.py",
                                      "split_" + name.replace(".", "_"))
               for name in READERS}

    def summary(sp):
        kernel = [n for n in sp.spans if n.startswith("kernel ")]
        wall = sum(sp.spans[n]["wall_s"] for n in kernel)
        ctx = Context(cell, rec, sp, peak)
        return {
            "offsets_ns": sp.offsets_ns,
            "self_idle_pct": {k: 100.0 * v / sp.window_s for k, v in
                              sorted(sp.self_idle_s.items(),
                                     key=lambda kv: -kv[1])},
            "kernel_idle_of_wall_pct": 100.0 * sum(
                sp.self_idle_s[n] for n in kernel) / wall if wall else None,
            "idle_gaps": sp.idle_gaps,
            "readers": {n: m.read(ctx) for n, m in modules.items()}}

    n_spans = sum(r["count"] for r in split.spans.values())
    n_program = sum(r["count"] for n, r in split.spans.items()
                    if n.startswith(idle_split.PROGRAM_PREFIXES))
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": dev.device_kind, "attempted": res["attempted"],
        "failed": res["failed"], **cost,
        "window_s": split.window_s, "busy_s": split.busy_s,
        "idle_pct": 100.0 * red.idle_share, "idle_gaps": red.idle_gaps,
        "offset_bounds_ns": split.offset_bounds_ns,
        "spans": split.spans, "spans_per_s": n_spans / split.window_s,
        "program_spans_per_s": n_program / split.window_s,
        "counters": dict(rec.counters),
        "split": summary(split), "at_bound": summary(at_bound),
    }
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
