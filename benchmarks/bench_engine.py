"""Engine throughput benchmark: events/sec of the discrete-event hot path.

Measures the simulator itself (not the paper's speedup metrics): one full
execution plus several selective iterations of the SLATE Cholesky study
program at world sizes 16/64/256, reporting simulated events per wall-clock
second.  Emits ``BENCH_engine.json`` at the repository root so the perf
trajectory is tracked from PR 1 onward; ``scripts/check.sh --stage engine``
gates a quick run's warm AND cold throughput against the committed baseline
(best-of-3 must reach CHECK_RATIO, default 50% — coarse because the CI box
swings 2-4x).

Throughput metrics per world size (PR 4 added the cold split; a fifth
field, ``events_per_sec_cold_scalar``, records the same-session reference
the batched-cold ratio is taken against: ``Runtime.run_scalar
(force_execute=True)``, the recorded program, recording pass included,
through the scalar interceptions with one timer draw per event — the
seed engine's interleaved pass it used to time is gone):

- ``events_per_sec``              — all iterations;
- ``events_per_sec_warm``         — selective iterations after warmup: the
  steady-state interception hot path (PR-1 target);
- ``events_per_sec_cold``         — the first (recording + forced) run
  under the default cost model, whose straggler branch forces per-event
  scalar draws;
- ``events_per_sec_cold_batched`` — the same recording run with the
  straggler branch off, where the cold interpreter pre-draws every sample
  of the run in one vectorized call (PR-4 target).

PR 9 adds the compiled-warm split and the counter-RNG cold metric:

- ``events_per_sec_warm`` now measures the compiled warm program
  (segmented, vectorized replay — the default selective path);
- ``events_per_sec_warm_scalar`` — a same-session reference running the
  same protocol through ``Runtime.run_scalar`` (the scalar event-program
  interpreter); ``warm_speedup_vs_scalar`` is their ratio (the CI gate's
  compiled-throughput signal);
- ``events_per_sec_cold_counter`` — the straggler-ON recording run under
  the counter-based (Philox-style) RNG discipline, whose mixed
  normal/uniform draws batch per segment (the PR-5 residual fix);
  ``cold_counter_speedup_vs_scalar`` compares it to the legacy per-event
  scalar fallback at the same straggler setting;
- ``compiled`` — warm-program segmentation metadata (segment counts,
  fused events, batch sizes), also emitted into check_results.json.

PR 10 adds the cross-task program-cache metric:

- ``events_per_sec_cold_cached`` — the batched cold run against a warmed
  on-disk ``ProgramCache``: artifact deserialization replaces the
  recording pass (the replay path every sweep task after the first with
  a given geometry takes); ``cold_cached_speedup_vs_batched`` is its
  ratio over the record-from-scratch cold run.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_engine            # full sweep
    PYTHONPATH=src python -m benchmarks.bench_engine --quick    # ~10 s sanity
    PYTHONPATH=src python -m benchmarks.bench_engine --verify   # cold-path,
                       # compiled-path, counter-RNG and program-cache
                       # bit-identity assertions, then exit
    PYTHONPATH=src python -m benchmarks.bench_engine --out path.json
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import time

from repro.core.critter import Critter
from repro.core.policies import policy
from repro.linalg import slate_cholesky
from repro.simmpi.comm import World
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2
from repro.simmpi.runtime import (EV_BLOCK, EV_COLL, Runtime)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_engine.json")

# world_size -> (pr, pc, n, tile): the ci-scale SLATE Cholesky geometry
# scaled so per-rank work stays comparable across world sizes.
GEOMETRIES = {
    16: (4, 4, 4096, 256),
    64: (8, 8, 8192, 256),
    256: (16, 16, 16384, 256),
}


def _setup(world_size: int, *, pol: str, tol: float, seed: int,
           straggler_p=None, counter_rng: bool = False,
           program_cache=None):
    pr, pc, n, tile = GEOMETRIES[world_size]
    world = World(world_size)
    critter = Critter(world, policy(pol, tolerance=tol))
    kw = {} if straggler_p is None else {"straggler_p": straggler_p}
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=seed,
                   counter_rng=counter_rng, **kw)
    rt = Runtime(world, critter, cm.sample, seed=seed,
                 program_cache=program_cache)
    prog = slate_cholesky.make_program(world, n=n, tile=tile, lookahead=1,
                                       pr=pr, pc=pc)
    if program_cache is not None:
        from repro.simmpi.program import structural_fingerprint
        prog.program_key = structural_fingerprint(
            "bench-slate-cholesky", f"w{world_size}",
            {"n": n, "tile": tile, "lookahead": 1, "pr": pr, "pc": pc},
            world_size)
    return rt, prog


def _timed_cold(run, prog):
    """One forced iteration through ``run`` (``Runtime.run`` or
    ``Runtime.run_scalar``): its report and wall seconds."""
    t0 = time.perf_counter()
    res = run(prog, force_execute=True)
    return res, time.perf_counter() - t0


def bench_cold(world_size: int, *, pol: str = "online", tol: float = 0.25,
               seed: int = 0, straggler_p=0.0,
               counter_rng: bool = False, program_cache=None) -> dict:
    """One recording (forced) run in isolation — the batched cold path
    when ``straggler_p == 0`` (vectorized pre-draw), the scalar-fallback
    cold path otherwise (unless ``counter_rng=True``, where the
    counter-based draw discipline batches mixed normal/uniform draws even
    with stragglers on).

    With ``program_cache`` (PR 10) the run consults the cross-task
    program cache keyed by the geometry's structural fingerprint: against
    a warmed cache the recording pass is replaced by artifact replay, so
    the wall measures deserialization + forced execution."""
    rt, prog = _setup(world_size, pol=pol, tol=tol, seed=seed,
                      straggler_p=straggler_p, counter_rng=counter_rng,
                      program_cache=program_cache)
    res, dt = _timed_cold(rt.run, prog)
    return {"events": res.events, "wall_s": round(dt, 4),
            "events_per_sec": round(res.events / dt, 1),
            "straggler_p": straggler_p, "recordings": rt.recordings,
            "cache_hits": rt.cache_hits}


def _study_session(run, prog, *, selective_iters: int, warmup: int) -> dict:
    """One tuner-pattern session (1 forced + ``selective_iters`` selective
    iterations) through ``run`` (``Runtime.run`` or ``Runtime.run_scalar``)
    with per-iteration timings and the warm aggregate."""
    runs = []
    total_events = 0
    total_wall = 0.0
    warm_events = 0
    warm_wall = 0.0
    for i in range(1 + selective_iters):
        force = i == 0
        t0 = time.perf_counter()
        res = run(prog, force_execute=force)
        dt = time.perf_counter() - t0
        runs.append({"force_execute": force, "events": res.events,
                     "executed": res.executed, "skipped": res.skipped,
                     "wall_s": round(dt, 4),
                     "events_per_sec": round(res.events / dt, 1)})
        total_events += res.events
        total_wall += dt
        if i > warmup:
            warm_events += res.events
            warm_wall += dt
    return {
        "runs": runs,
        "total_events": total_events, "total_wall": total_wall,
        "warm_rate": round(warm_events / warm_wall, 1)
        if warm_wall > 0 else 0.0,
    }


def bench_study(world_size: int, *, pol: str = "online", tol: float = 0.25,
                selective_iters: int = 6, warmup: int = 2,
                seed: int = 0, cold_repeats: int = 3) -> dict:
    """One full (reference) execution followed by ``selective_iters``
    selective iterations — the tuner's per-configuration pattern — under
    the DEFAULT cost model (straggler branch on, so the cold run exercises
    the scalar-fallback draws), plus one isolated batched cold run
    (straggler branch off, vectorized pre-draw).

    PR 9: the selective iterations run through the compiled warm program
    (segmented vectorized replay); a second session over the same protocol
    through ``Runtime.run_scalar`` provides the same-session scalar-warm
    reference the compiled speedup is taken against, and the straggler
    cold pair (counter-RNG batched vs legacy scalar-fallback) measures the
    PR-5 residual fix.

    PR 10: ``events_per_sec_cold_cached`` measures the batched cold run
    against a warmed on-disk program cache — the recording pass is
    replaced by artifact deserialization (the cross-task replay path a
    sweep worker takes on every task after the first with a given
    geometry)."""
    import shutil
    import tempfile

    from repro.simmpi.program import ProgramCache

    pr, pc, n, tile = GEOMETRIES[world_size]
    rt, prog = _setup(world_size, pol=pol, tol=tol, seed=seed)
    comp = _study_session(rt.run, prog, selective_iters=selective_iters,
                          warmup=warmup)
    segmeta = rt.warm_meta(prog)
    rt, prog = _setup(world_size, pol=pol, tol=tol, seed=seed)
    scal = _study_session(rt.run_scalar, prog,
                          selective_iters=selective_iters, warmup=warmup)
    runs = comp["runs"]
    total_events = comp["total_events"]
    total_wall = comp["total_wall"]
    # batched-vs-scalar cold pairs: alternate the variants and keep
    # min-wall of each so the pairing survives the box's second-scale
    # throughput swings (a single A-then-B measurement can land A in a
    # slow patch and B in a fast one, inverting the ratio).  Two pairs:
    # straggler-off batched pre-draw vs the scalar reference, and
    # straggler-ON counter-RNG batched vs legacy scalar fallback (PR 9 —
    # the PR-5 residual: mixed normal/uniform draws batched per segment).
    b_walls, s_walls, cb_walls, cs_walls, cc_walls = [], [], [], [], []
    n_events = 0
    cache_dir = tempfile.mkdtemp(prefix="bench-progcache-")
    try:
        cache = ProgramCache(cache_dir)
        # warm the cache: one untimed recording run stores the artifact
        bench_cold(world_size, pol=pol, tol=tol, seed=seed,
                   straggler_p=0.0, program_cache=cache)
        for _ in range(cold_repeats):
            b = bench_cold(world_size, pol=pol, tol=tol, seed=seed,
                           straggler_p=0.0)
            rt, prog = _setup(world_size, pol=pol, tol=tol, seed=seed,
                              straggler_p=0.0)
            _, s_wall = _timed_cold(rt.run_scalar, prog)
            cb = bench_cold(world_size, pol=pol, tol=tol, seed=seed,
                            straggler_p=0.002, counter_rng=True)
            cs = bench_cold(world_size, pol=pol, tol=tol, seed=seed,
                            straggler_p=0.002, counter_rng=False)
            # drop the in-memory entry so the hit pays the real artifact
            # deserialization a fresh sweep worker pays, not a dict lookup
            cache._mem.clear()
            cc = bench_cold(world_size, pol=pol, tol=tol, seed=seed,
                            straggler_p=0.0, program_cache=cache)
            assert cc["recordings"] == 0 and cc["cache_hits"] == 1, (
                f"cached cold run did not replay from the cache: {cc}")
            b_walls.append(b["wall_s"])
            s_walls.append(round(s_wall, 4))
            cb_walls.append(cb["wall_s"])
            cs_walls.append(cs["wall_s"])
            cc_walls.append(cc["wall_s"])
            n_events = b["events"]
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    batched = round(n_events / min(b_walls), 1)
    scalar = round(n_events / min(s_walls), 1)
    ctr_batched = round(n_events / min(cb_walls), 1)
    ctr_scalar = round(n_events / min(cs_walls), 1)
    cached = round(n_events / min(cc_walls), 1)
    return {
        "study": "slate-cholesky", "policy": pol, "tolerance": tol,
        "world_size": world_size, "n": n, "tile": tile, "lookahead": 1,
        "total_events": total_events, "total_wall_s": round(total_wall, 4),
        "events_per_sec": round(total_events / total_wall, 1),
        "events_per_sec_warm": comp["warm_rate"],
        "events_per_sec_warm_scalar": scal["warm_rate"],
        "warm_speedup_vs_scalar": round(
            comp["warm_rate"] / scal["warm_rate"], 2)
        if scal["warm_rate"] > 0 else 0.0,
        "events_per_sec_cold": runs[0]["events_per_sec"],
        "events_per_sec_cold_batched": batched,
        "events_per_sec_cold_scalar": scalar,
        "cold_speedup_vs_scalar": round(batched / scalar, 2),
        "events_per_sec_cold_counter": ctr_batched,
        "cold_counter_speedup_vs_scalar": round(ctr_batched / ctr_scalar, 2),
        "events_per_sec_cold_cached": cached,
        "cold_cached_speedup_vs_batched": round(cached / batched, 2),
        "compiled": segmeta,
        "runs": runs,
    }


# -------------------------------------------------------- cold-path verify

def _canonical_events(prog) -> list:
    """Event-program tuples with engine objects replaced by stable keys so
    programs recorded by different Runtime/World instances compare."""
    out = []
    for ev in prog.events:
        k = ev[0]
        if k == EV_BLOCK:
            out.append((k, ev[1], tuple(ev[2].sids)))
        elif k == EV_COLL:
            out.append((k, ev[1], ev[2].ranks))
        else:
            out.append(ev)
    return out


def _record_program(world_size: int, *, straggler_p, seed: int = 0):
    rt, prog = _setup(world_size, pol="online", tol=0.25, seed=seed,
                      straggler_p=straggler_p)
    rt.run(prog, force_execute=True)
    return _canonical_events(rt._traces[prog])


def verify_cold_path(world_size: int = 16) -> dict:
    """Assert the batched cold path is a pure optimization.

    1. The recorded event program is identical whether the cold run drew
       its samples batched (straggler off) or through the scalar fallback
       (straggler on): recording is structural, timing-independent.
    2. A batched cold run and ``run_scalar``'s forced run (one scalar
       draw per event) over the same cost model produce bit-identical
       reports and leave the sampler RNG in the same state.

    Returns a small summary dict; raises AssertionError on any mismatch.
    Wired into ``--verify``, ``scripts/check.sh --stage engine`` and
    ``tests/test_cold_path.py``.
    """
    ev_batched = _record_program(world_size, straggler_p=0.0)
    ev_scalar = _record_program(world_size, straggler_p=0.002)
    assert ev_batched == ev_scalar, (
        "batched and unbatched cold runs recorded different event programs")

    reports = []
    states = []
    for scalar in (False, True):
        rt, prog = _setup(world_size, pol="online", tol=0.25, seed=0,
                          straggler_p=0.0)
        res = (rt.run_scalar if scalar else rt.run)(prog,
                                                    force_execute=True)
        reports.append({f: getattr(res, f) for f in _REPORT_FIELDS})
        states.append(rt._rng.bit_generator.state)
    assert reports[0] == reports[1], (
        f"batched cold report diverged: {reports[0]} vs {reports[1]}")
    assert states[0] == states[1], (
        "batched cold run consumed a different RNG stream")
    return {"world_size": world_size, "events": len(ev_batched),
            "report": reports[0]}


# ---------------------------------------------------- compiled-path verify

_REPORT_FIELDS = ("predicted_time", "wall_time", "crit_comp", "crit_comm",
                  "measured_time", "max_measured_comp", "executed",
                  "skipped", "events")


def _engine_snapshot(critter) -> tuple:
    """Full rank-state fingerprint: statistics mirrors, counts, path
    profiles, per-rank clocks, global skip state and every Welford
    accumulator — byte-exact, so any drift in the compiled replay shows."""
    S = critter.state
    return (S.mean_arr.tobytes(), S.freq.tobytes(), S.seen.tobytes(),
            S.skip_ok.tobytes(), S.iter_exec.tobytes(), S.clock.tobytes(),
            S.path_exec.tobytes(), S.path_comm.tobytes(),
            S.goff.tobytes(), S.gmean.tobytes(),
            sorted(critter.global_off),
            sorted((r, sid, st.n, st.mean, st.m2, st.total, st.min_t,
                    st.max_t)
                   for r in range(S.n_ranks)
                   for sid, st in S.kbar[r].items()))


def _selective_trace(world_size: int, *, pol: str, straggler_p: float,
                     scalar: bool, iters: int = 3) -> list:
    """Forced run + ``iters`` selective iterations, through ``run_scalar``
    when ``scalar`` else ``run``; returns per-iteration reports,
    per-iteration engine-state fingerprints and the final RNG
    bit-generator state."""
    rt, prog = _setup(world_size, pol=pol, tol=0.25, seed=0,
                      straggler_p=straggler_p)
    run = rt.run_scalar if scalar else rt.run
    trace = []
    for i in range(1 + iters):
        res = run(prog, force_execute=(i == 0))
        trace.append(tuple(getattr(res, f) for f in _REPORT_FIELDS))
        trace.append(_engine_snapshot(rt.critter))
    trace.append(rt._rng.bit_generator.state)
    return trace


def verify_compiled_path(world_size: int = 16) -> dict:
    """Assert the compiled (segmented, vectorized-replay) warm program is
    bit-identical to the scalar engine.

    For each policy x straggler-branch combination the tuner protocol
    (forced run + 3 selective iterations) is run two ways — ``run`` (the
    batched cold run, then the compiled warm program) and the scalar
    reference ``run_scalar`` — and both must agree on every iteration
    report field, the full engine
    state after every iteration (statistics, mean mirrors, counts, path
    profiles, clocks, Welford accumulators, global skip state) and the
    sampler RNG stream.  Raises AssertionError on any divergence.

    The full 5-policies x 3-studies matrix lives in
    ``tests/test_cold_path.py`` / ``tests/test_compiled_path.py``; this
    entry point is the quick in-process gate check.sh runs before timing.
    """
    checked = 0
    for pol in ("online", "eager"):
        for straggler_p in (0.0, 0.002):
            scalar = _selective_trace(world_size, pol=pol,
                                      straggler_p=straggler_p, scalar=True)
            comp = _selective_trace(world_size, pol=pol,
                                    straggler_p=straggler_p, scalar=False)
            for i, (a, b) in enumerate(zip(scalar, comp)):
                assert a == b, (f"compiled path diverged from run_scalar "
                                f"({pol}, straggler={straggler_p}, "
                                f"trace step {i})")
            checked += 1
    rt, prog = _setup(world_size, pol="online", tol=0.25, seed=0)
    meta = rt.warm_meta(prog)
    assert meta["segments"] > 0 and meta["fused_events"] > 0, (
        f"warm program recorded no fused segments: {meta}")
    return {"world_size": world_size, "configs": checked,
            "compiled": meta}


def verify_counter_rng(world_size: int = 16) -> dict:
    """Assert the counter-based (Philox-style) draw discipline is a pure
    optimization: (1) per-event scalar draws and per-segment batched
    draws over the same counter range are bit-identical, including the
    straggler branch; (2) with ``counter_rng=True`` the batched cold path
    and ``run_scalar`` produce identical reports and leave the draw
    cursor at the same index (the counter-mode analogue of the
    bit-generator state check); (3) selective iterations agree too."""
    import numpy as np
    from repro.core.signatures import Signature

    # (1) scalar sample() vs sample_block() over the same counter range,
    # straggler_p high enough that the straggler branch fires in-batch
    sigs = [Signature("comp", "potrf", (256,)),
            Signature("comp", "trsm", (256, 256)),
            Signature("comp", "gemm", (256, 256, 256)),
            Signature("comp", "syrk", (256, 256)),
            Signature("comm", "bcast", (131072, 16, 1))] * 40
    cm_a = CostModel(KNL_STAMPEDE2, allocation=0, seed=7,
                     straggler_p=0.05, counter_rng=True)
    cm_b = CostModel(KNL_STAMPEDE2, allocation=0, seed=7,
                     straggler_p=0.05, counter_rng=True)
    rng = np.random.default_rng(0)  # untouched in counter mode
    scalar_ts = [cm_a.sample(sig, rng) for sig in sigs]
    block_ts = cm_b.sample_block(sigs)
    assert block_ts is not None, "sample_block inactive in counter mode"
    assert scalar_ts == block_ts.tolist(), (
        "counter-RNG scalar and batched draws diverged")
    assert cm_a.draw_index == cm_b.draw_index == 3 * len(sigs), (
        f"draw cursors diverged: {cm_a.draw_index} vs {cm_b.draw_index}")

    # (2)+(3) batched cold + compiled selective vs run_scalar, counter mode,
    # straggler branch ON (the PR-5 residual configuration)
    cursors = []
    traces = []
    pr, pc, n, tile = GEOMETRIES[world_size]
    for scalar in (False, True):
        w = World(world_size)
        c = Critter(w, policy("online", tolerance=0.25))
        cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                       straggler_p=0.002, counter_rng=True)
        rt = Runtime(w, c, cm.sample, seed=0)
        run = rt.run_scalar if scalar else rt.run
        prog = slate_cholesky.make_program(w, n=n, tile=tile, lookahead=1,
                                           pr=pr, pc=pc)
        trace = []
        for i in range(3):
            res = run(prog, force_execute=(i == 0))
            trace.append(tuple(getattr(res, f) for f in _REPORT_FIELDS))
            trace.append(_engine_snapshot(c))
        traces.append(trace)
        cursors.append(cm.draw_index)
    for i, (a, b) in enumerate(zip(traces[0], traces[1])):
        assert a == b, f"counter-RNG cold/warm diverged at trace step {i}"
    assert cursors[0] == cursors[1], (
        f"counter-RNG draw cursors diverged: {cursors}")
    return {"world_size": world_size, "draws": cursors[0],
            "scalar_block_parity": len(sigs)}


def verify_program_cache(world_size: int = 16) -> dict:
    """Assert a program-cache hit is a pure optimization: the tuner
    protocol (forced run + 2 selective iterations) run three ways — cache
    miss (records + stores), cache hit against the warmed store (replays
    the deserialized artifact, zero recordings) and no cache at all —
    must agree on every iteration report, the full engine state after
    every iteration and the sampler RNG stream, and the replayed event
    program must be structurally identical to the recorded one.

    The full 5-policies x 3-studies x straggler matrix lives in
    ``tests/test_program_cache.py``; this entry point is the quick
    in-process gate ``check.sh --stage engine`` runs before timing."""
    from repro.simmpi.program import ProgramCache

    cache = ProgramCache()
    traces, events, recordings = [], [], []
    for use_cache in ("miss", "hit", "off"):
        rt, prog = _setup(world_size, pol="online", tol=0.25, seed=0,
                          straggler_p=0.002,
                          program_cache=cache if use_cache != "off"
                          else None)
        trace = []
        for i in range(3):
            res = rt.run(prog, force_execute=(i == 0))
            trace.append(tuple(getattr(res, f) for f in _REPORT_FIELDS))
            trace.append(_engine_snapshot(rt.critter))
        trace.append(rt._rng.bit_generator.state)
        traces.append(trace)
        events.append(_canonical_events(rt._get_program(prog)))
        recordings.append(rt.recordings)
    assert recordings == [1, 0, 1], (
        f"cache hit did not skip recording: {recordings}")
    assert cache.hits == 1 and cache.misses == 1, (
        f"unexpected cache traffic: {cache.stats()}")
    for i, (a, b) in enumerate(zip(traces[0], traces[1])):
        assert a == b, f"cache-hit replay diverged at trace step {i}"
    for i, (a, b) in enumerate(zip(traces[0], traces[2])):
        assert a == b, f"cache-miss run diverged from uncached at step {i}"
    assert events[0] == events[1] == events[2], (
        "replayed event program is not structurally identical")
    return {"world_size": world_size, "events": len(events[0]),
            "store": cache.stats()}


_RATE_FIELDS = ("events_per_sec", "events_per_sec_warm",
                "events_per_sec_warm_scalar",
                "events_per_sec_cold", "events_per_sec_cold_batched",
                "events_per_sec_cold_scalar",
                "events_per_sec_cold_counter",
                "events_per_sec_cold_cached")
_RATIO_FIELDS = ("warm_speedup_vs_scalar", "cold_speedup_vs_scalar",
                 "cold_counter_speedup_vs_scalar",
                 "cold_cached_speedup_vs_batched")


def run(world_sizes=(16, 64, 256), *, selective_iters: int = 6,
        best_of: int = 1) -> dict:
    """``best_of > 1`` repeats each world size's study and keeps the
    per-metric maxima (runs list from the best-warm repeat): the shared CI
    box swings 2-4x between moments, and best-of-N is the same noise
    reduction check.sh applies to its gate."""
    results = []
    for ws in world_sizes:
        reps = [bench_study(ws, selective_iters=selective_iters)
                for _ in range(best_of)]
        r = max(reps, key=lambda x: x["events_per_sec_warm"])
        for f in _RATE_FIELDS + _RATIO_FIELDS:
            r[f] = max(rep[f] for rep in reps)
        print(f"world={ws:4d}  events={r['total_events']:9d}  "
              f"wall={r['total_wall_s']:8.3f}s  "
              f"events/sec={r['events_per_sec']:10.1f}  "
              f"warm={r['events_per_sec_warm']:10.1f}  "
              f"(vs scalar {r['warm_speedup_vs_scalar']:.2f}x)  "
              f"cold={r['events_per_sec_cold']:9.1f}  "
              f"cold_batched={r['events_per_sec_cold_batched']:9.1f}  "
              f"(vs scalar {r['cold_speedup_vs_scalar']:.2f}x)  "
              f"cold_counter={r['events_per_sec_cold_counter']:9.1f}  "
              f"(vs scalar {r['cold_counter_speedup_vs_scalar']:.2f}x)  "
              f"cold_cached={r['events_per_sec_cold_cached']:9.1f}  "
              f"(vs batched {r['cold_cached_speedup_vs_batched']:.2f}x)")
        seg = r["compiled"]
        print(f"            compiled: {seg['segments']} segments, "
              f"{seg['fused_events']} fused events, "
              f"mean batch {seg['mean_batch']}, max {seg['max_batch']}")
        results.append(r)
    return {
        "meta": {
            "benchmark": "engine-throughput",
            "python": platform.python_version(),
            "machine": platform.machine(),
            "best_of": best_of,
        },
        "results": results,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="world 16+64 only, fewer iterations (~10 s)")
    ap.add_argument("--verify", action="store_true",
                    help="run the cold-path identity assertions and exit")
    ap.add_argument("--best-of", type=int, default=1,
                    help="repeat each world size N times, keep per-metric "
                         "maxima (noise reduction on shared boxes)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()
    if args.verify:
        summary = verify_cold_path()
        print(f"cold-path verify OK: {summary['events']} events, "
              f"report {summary['report']}")
        summary = verify_compiled_path()
        print(f"compiled-path verify OK: {summary['configs']} configs "
              f"bit-identical, compiled meta {summary['compiled']}")
        summary = verify_counter_rng()
        print(f"counter-RNG verify OK: {summary['draws']} draws, "
              f"scalar/block parity over "
              f"{summary['scalar_block_parity']} signatures")
        summary = verify_program_cache()
        print(f"program-cache verify OK: {summary['events']} events "
              f"replayed bit-identical, store {summary['store']}")
        return
    if args.quick:
        out = run(world_sizes=(16, 64), selective_iters=4,
                  best_of=args.best_of)
    else:
        out = run(best_of=args.best_of)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
