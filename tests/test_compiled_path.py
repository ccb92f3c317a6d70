"""Compiled warm program: bit-identity against the scalar engine.

PR 9 lowers the recorded event program into compiled segments: maximal
per-rank runs of computation events between skip-decision and
communication boundaries become head entries that batch-charge the whole
segment when every kernel in it is in the memoized-skip regime, and the
straggler-enabled cost model adopts a counter-based (Philox-style) RNG
discipline so mixed normal/uniform draws batch per segment.  These tests
pin the compiled path (what ``Runtime.run`` takes for warm-eligible
selective runs) to the scalar reference ``Runtime.run_scalar``,
requiring bit-identical reports, engine state and RNG streams — plus
segment-boundary edge cases the SLATE/Capital/CANDMC studies don't
produce on their own (comm-only programs, segments of a single event,
skip decisions flipping mid-program).

The full 5-policies x 3-studies x straggler matrix already runs the
compiled path implicitly in tests/test_cold_path.py, which also pins the
forced runs; the matrix here is the SLATE study's, plus the edge cases
that probe the warm-program lowering.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.critter import Critter, W_BHEAD, W_CHEAD
from repro.core.policies import POLICIES, policy
from repro.core.signatures import Signature
from repro.linalg import slate_cholesky
from repro.simmpi import Comp, Coll, Isend, Recv
from repro.simmpi.comm import World
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2
from repro.simmpi.runtime import Runtime

REPORT_FIELDS = ("predicted_time", "wall_time", "crit_comp", "crit_comm",
                 "measured_time", "max_measured_comp", "executed",
                 "skipped", "events")


def _slate(w):
    return slate_cholesky.make_program(w, n=512, tile=64, lookahead=1,
                                       pr=4, pc=4)


def _state_snapshot(critter):
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


def _trace(make, world_size, pol, *, straggler_p=0.0, scalar=False,
           counter_rng=False, iters=3, timer=None):
    """Forced run + ``iters`` selective iterations; per-iteration reports
    and state fingerprints plus the final RNG stream position, every
    iteration through ``run_scalar`` when ``scalar``, else ``run``."""
    w = World(world_size)
    c = Critter(w, policy(pol, tolerance=0.25))
    if timer is None:
        cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                       straggler_p=straggler_p, counter_rng=counter_rng)
        sample = cm.sample
    else:
        cm = None
        sample = timer
    rt = Runtime(w, c, sample, seed=3)
    prog = make(w)
    out = []
    run = rt.run_scalar if scalar else rt.run
    for i in range(1 + iters):
        res = run(prog, force_execute=(i == 0))
        out.append(tuple(getattr(res, f) for f in REPORT_FIELDS))
        out.append(_state_snapshot(c))
    out.append(cm.draw_index if counter_rng else
               rt._rng.bit_generator.state)
    return out, rt, prog


def _assert_traces_equal(a, b, label):
    for i, (x, y) in enumerate(zip(a, b)):
        assert x == y, f"{label}: divergence at trace step {i}"


# ------------------------------------------------- compiled vs interpreter

@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("straggler_p", [0.002, 0.0],
                         ids=["straggler-on", "straggler-off"])
def test_compiled_matches_scalar_interpreter(pol, straggler_p):
    comp, _, _ = _trace(_slate, 16, pol, straggler_p=straggler_p)
    scal, _, _ = _trace(_slate, 16, pol, straggler_p=straggler_p,
                        scalar=True)
    _assert_traces_equal(comp, scal, f"{pol}/straggler={straggler_p}")


def test_compiled_is_the_default_selective_path():
    """``run`` must actually build and run the warm program on selective
    iterations; ``run_scalar`` must not."""
    _, rt_c, prog_c = _trace(_slate, 16, "online")
    _, rt_s, prog_s = _trace(_slate, 16, "online", scalar=True)
    assert rt_c._traces[prog_c].warm is not None
    assert rt_s._traces[prog_s].warm is None


# ------------------------------------------------- segment-boundary edges

def _comm_only(w):
    wc = w.world_comm

    def program(rank, world):
        for _ in range(4):
            yield Coll("allreduce", wc, 4096)
            yield Coll("bcast", wc, 8192)
    return program


def test_comm_only_program_has_no_segments():
    """A program with no computation never opens a comp run: the warm
    program degenerates to per-event entries (zero segments) and still
    matches the scalar interpreter bit-for-bit."""
    comp, rt, prog = _trace(_comm_only, 8, "online")
    scal, _, _ = _trace(_comm_only, 8, "online", scalar=True)
    _assert_traces_equal(comp, scal, "comm-only")
    meta = rt.warm_meta(prog)
    assert meta["segments"] == 0 and meta["fused_events"] == 0
    assert meta["coll_entries"] == 8


def _single_event_segments(w):
    wc = w.world_comm

    def program(rank, world):
        for i in range(6):
            yield Comp("gemm", (64, 64, 64))      # lone comp: run of 1
            yield Coll("barrier", wc, 0)
    return program


def test_single_event_segments_never_fuse():
    """A comp run of one event gets no head entry (nothing to batch), so
    the warm program carries it as a plain W_COMP — and the charge is
    identical either way."""
    comp, rt, prog = _trace(_single_event_segments, 4, "online")
    scal, _, _ = _trace(_single_event_segments, 4, "online", scalar=True)
    _assert_traces_equal(comp, scal, "single-event segments")
    meta = rt.warm_meta(prog)
    assert meta["segments"] == 0 and meta["fused_events"] == 0
    assert meta["comp_entries"] == 24                  # 6 comps x 4 ranks
    warm = rt._traces[prog].warm
    heads = [e for e in warm.entries if e[0] in (W_CHEAD, W_BHEAD)]
    assert heads == []


def _flip_prone(w):
    wc = w.world_comm

    def program(rank, world):
        for i in range(8):
            # segment of 3: two stable kernels plus one noisy one whose
            # confidence interval never tightens below tolerance, so the
            # segment's skip guard fails and the compiled path must fall
            # back to per-event processing at the original positions
            # (the trailing float is the explicit-flops convention)
            yield Comp("gemm", (64, 64, 64))
            yield Comp("noisy", (8, 1e6))
            yield Comp("trsm", (64, 64))
            yield Coll("allreduce", wc, 1024)
    return program


def test_skip_decision_flips_mid_program():
    """Mixed skip/execute inside one segment: the noisy kernel stays
    unpredictable while its neighbours reach the skip regime, so the
    segment guard fails every iteration and charges event-by-event — in
    recorded order, drawing the exact RNG stream of the scalar engine."""
    def noisy_timer(sig, rng):
        if sig.kind == "comp" and sig.name == "noisy":
            return 1e-3 * (0.5 + rng.random() * 4.0)   # ~3x swings
        if sig.kind == "comp":
            return 1e-3 * (1.0 + 0.01 * rng.normal())
        return 1e-4

    comp, rt, prog = _trace(_flip_prone, 4, "online", iters=5,
                            timer=noisy_timer)
    scal, _, _ = _trace(_flip_prone, 4, "online", scalar=True, iters=5,
                        timer=noisy_timer)
    _assert_traces_equal(comp, scal, "flip-prone vs scalar reference")
    meta = rt.warm_meta(prog)
    assert meta["segments"] > 0                        # fusion did happen
    # the last selective iteration really did mix skips and executions
    final = comp[-3]
    assert 0 < final[6] < final[8], (
        f"expected mixed skip/execute, got {final[6]}/{final[8]}")


def test_eager_aggregation_inside_segments():
    """The eager policy re-aggregates global statistics at collectives —
    mid-replay, between segments.  The compiled path must observe the
    refreshed global skip set exactly as the scalar engine does."""
    comp, _, _ = _trace(_flip_prone, 4, "eager", iters=5)
    scal, _, _ = _trace(_flip_prone, 4, "eager", scalar=True, iters=5)
    _assert_traces_equal(comp, scal, "eager vs scalar reference")


# ------------------------------------------------------- counter-RNG path

def test_counter_scalar_vs_block_bit_identical():
    sigs = [Signature("comp", "gemm", (128, 128, 128)),
            Signature("comp", "potrf", (128,)),
            Signature("comm", "bcast", (65536, 8, 1))] * 30
    a = CostModel(KNL_STAMPEDE2, allocation=0, seed=11, straggler_p=0.05,
                  counter_rng=True)
    b = CostModel(KNL_STAMPEDE2, allocation=0, seed=11, straggler_p=0.05,
                  counter_rng=True)
    rng = np.random.default_rng(0)
    scalar = [a.sample(s, rng) for s in sigs]
    block = b.sample_block(sigs)
    assert block is not None
    assert scalar == block.tolist()
    assert a.draw_index == b.draw_index == 3 * len(sigs)
    # the host Generator is never touched in counter mode
    assert rng.bit_generator.state == \
        np.random.default_rng(0).bit_generator.state


def test_counter_mode_disables_legacy_batching():
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0, counter_rng=True)
    assert cm.batch_info([Signature("comp", "gemm", (64, 64, 64))]) is None
    legacy = CostModel(KNL_STAMPEDE2, allocation=0, seed=0)
    assert legacy.sample_block(
        [Signature("comp", "gemm", (64, 64, 64))]) is None


@pytest.mark.parametrize("pol", ["online", "eager"])
def test_counter_rng_cold_and_warm_bit_identical(pol):
    """With stragglers ON and counter mode, the batched cold path and the
    compiled warm path must match ``run_scalar`` — including the draw
    cursor, the counter-mode analogue of the bit-generator state (this is
    the PR-5 residual: the straggler cold path used to fall back to
    per-event scalar draws; now it batches through sample_block)."""
    cached, _, _ = _trace(_slate, 16, pol, straggler_p=0.002,
                          counter_rng=True)
    scal, _, _ = _trace(_slate, 16, pol, straggler_p=0.002,
                        counter_rng=True, scalar=True)
    _assert_traces_equal(cached, scal, f"counter/{pol}")
    assert cached[-1] == scal[-1] > 0       # draw cursors advanced, equal


def test_counter_cold_cursor_matches_live():
    """The recording (forced) run in counter mode pre-draws through
    sample_block — one bulk cursor advance that must land exactly where
    ``run_scalar``'s per-event draws leave its cursor (3 counter slots per
    drawn sample, whether the straggler branch fires or not)."""
    cursors = []
    for scalar in (False, True):
        w = World(16)
        c = Critter(w, policy("online", tolerance=0.25))
        cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                       straggler_p=0.002, counter_rng=True)
        rt = Runtime(w, c, cm.sample, seed=3)
        run = rt.run_scalar if scalar else rt.run
        run(_slate(w), force_execute=True)
        cursors.append(cm.draw_index)
    assert cursors[0] == cursors[1] > 0
    assert cursors[0] % 3 == 0


# ----------------------------------------------------------- meta sanity

def test_warm_meta_sanity():
    _, rt, prog = _trace(_slate, 16, "online")
    meta = rt.warm_meta(prog)
    assert meta["segments"] > 0
    assert meta["fused_events"] >= 2 * meta["segments"]  # heads fuse >= 2
    assert 2.0 <= meta["mean_batch"] <= meta["max_batch"]
    warm = rt._traces[prog].warm
    assert meta["entries"] == len(warm.entries)
    heads = sum(1 for e in warm.entries if e[0] in (W_CHEAD, W_BHEAD))
    assert heads == meta["segments"]
    # entry-kind counters tally the pre-segmentation entry stream
    assert (meta["comp_entries"] + meta["block_entries"]
            + meta["coll_entries"] + meta["p2p_entries"]
            + meta["ipost_entries"] + meta["imatch_entries"]
            ) == meta["entries"]


def test_bench_engine_verify_wiring():
    """The check.sh engine stage's in-process gates."""
    from benchmarks.bench_engine import (verify_compiled_path,
                                         verify_counter_rng)
    summary = verify_compiled_path(16)
    assert summary["configs"] == 4
    assert summary["compiled"]["segments"] > 0
    summary = verify_counter_rng(16)
    assert summary["draws"] > 0
