"""Batched cold-run fast path: bit-identity against the scalar engine.

The PR-4 cold path splits the first (recording/forced) execution into a
structural recording pass plus a batched interpreter that pre-draws kernel
samples (vectorized when the cost model's straggler branch is off, scalar
fallback when it is on) and charges fused computation runs in bulk.  These
tests pin it to the scalar reference — ``Runtime.run_scalar`` runs the same
recorded program through the scalar interceptions, one timer draw per
sampled event — requiring bit-identical:

- iteration reports (every ``IterationReport`` field),
- engine state after every iteration (statistics, mean mirrors, counts,
  path profiles), and
- the sampler RNG stream (bit-generator state after the run),

across all five policies, the three op-mix-distinct studies, straggler
branch on AND off, forced first runs, selective runs, and forced replays
(including ``update_stats=False`` reference runs).  Also pins the
optimized SLATE generator's op stream to a reference implementation and
the event-program identity of batched vs unbatched cold runs.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.critter import Critter
from repro.core.policies import POLICIES, policy
from repro.core.stats import KernelStats
from repro.linalg import candmc_qr, capital_cholesky, slate_cholesky
from repro.simmpi import Comp, Isend, Recv
from repro.simmpi.comm import World
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2
from repro.simmpi.runtime import Runtime

REPORT_FIELDS = ("predicted_time", "wall_time", "crit_comp", "crit_comm",
                 "measured_time", "max_measured_comp", "executed",
                 "skipped", "events")

STUDIES = {
    "slate": (16, lambda w: slate_cholesky.make_program(
        w, n=512, tile=64, lookahead=1, pr=4, pc=4)),
    "capital": (8, lambda w: capital_cholesky.make_program(
        w, n=256, block=32, strategy=1, grid_c=2)),
    "candmc": (16, lambda w: candmc_qr.make_program(
        w, m=1024, n=128, block=16, pr=4, pc=4)),
}


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


def _run_protocol(study, pol, straggler_p, scalar):
    """The tuner's per-configuration pattern: forced reference run, three
    selective trials, then a forced ``update_stats=False`` replay (the
    next configuration's reference measurement); through ``run_scalar``
    when ``scalar``, else through ``run``."""
    world_size, make = STUDIES[study]
    w = World(world_size)
    c = Critter(w, policy(pol, tolerance=0.25))
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                   straggler_p=straggler_p)
    rt = Runtime(w, c, cm.sample, seed=3)
    run = rt.run_scalar if scalar else rt.run
    prog = make(w)
    trace = []
    for i in range(4):
        res = run(prog, force_execute=(i == 0))
        trace.append(tuple(getattr(res, f) for f in REPORT_FIELDS))
        trace.append(_state_snapshot(c))
    res = run(prog, force_execute=True, update_stats=False)
    trace.append(tuple(getattr(res, f) for f in REPORT_FIELDS))
    trace.append(_state_snapshot(c))
    trace.append(rt._rng.bit_generator.state)
    return trace


@pytest.mark.parametrize("study", sorted(STUDIES))
@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("straggler_p", [0.002, 0.0],
                         ids=["straggler-on", "straggler-off"])
def test_cold_path_bit_identical(study, pol, straggler_p):
    scalar = _run_protocol(study, pol, straggler_p, scalar=True)
    batched = _run_protocol(study, pol, straggler_p, scalar=False)
    for i, (a, b) in enumerate(zip(scalar, batched)):
        assert a == b, (f"{study}/{pol}/straggler={straggler_p}: "
                        f"divergence at trace step {i}")


def test_rng_stream_batched_vs_scalar():
    """The RNG-order-compat contract in isolation: after a forced run the
    bit-generator state matches the scalar path exactly, for both the
    vectorized pre-draw (straggler off) and the scalar fallback."""
    for straggler_p in (0.0, 0.002):
        states = []
        for scalar in (True, False):
            w = World(16)
            c = Critter(w, policy("online", tolerance=0.25))
            cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                           straggler_p=straggler_p)
            rt = Runtime(w, c, cm.sample, seed=11)
            run = rt.run_scalar if scalar else rt.run
            run(STUDIES["slate"][1](w), force_execute=True)
            states.append(rt._rng.bit_generator.state)
        assert states[0] == states[1], f"straggler_p={straggler_p}"


def test_bench_engine_verify_cold_path():
    """The bench_engine assertion wired into check.sh: batched and
    unbatched cold runs record identical event programs, and the batched
    run matches ``run_scalar`` in reports and RNG stream."""
    from benchmarks.bench_engine import verify_cold_path
    summary = verify_cold_path(16)
    assert summary["report"]["skipped"] == 0   # forced run executes all


def test_custom_timer_falls_back_to_scalar_draws():
    """A plain callable timer (no batch_info) must still produce
    bit-identical forced runs — the cold interpreter draws through the
    timer per event, in event order."""
    calls = []

    def timer(sig, rng):
        calls.append(sig.kind)
        return 0.5 + 0.25 * rng.random()

    def run(scalar):
        calls.clear()
        w = World(8)
        c = Critter(w, policy("conditional", tolerance=0.25))
        rt = Runtime(w, c, timer, seed=5)
        run = rt.run_scalar if scalar else rt.run
        res = run(STUDIES["capital"][1](w), force_execute=True)
        return ([getattr(res, f) for f in REPORT_FIELDS], list(calls),
                rt._rng.bit_generator.state)

    assert run(True) == run(False)


def test_update_many_matches_sequential_updates():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(0.0, 0.4, 257).tolist()
    a, b = KernelStats(), KernelStats()
    for x in xs:
        a.update(x)
    b.update_many(xs[:100])
    b.update_many(xs[100:])
    assert (a.n, a.mean, a.m2, a.total, a.min_t, a.max_t) == \
        (b.n, b.mean, b.m2, b.total, b.min_t, b.max_t)


def test_batch_info_contract():
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0, straggler_p=0.0)
    w = World(4)
    prog = STUDIES["slate"][1]
    # straggler on -> no batching
    assert CostModel(KNL_STAMPEDE2, straggler_p=0.002).batch_info(
        [None]) is None
    assert cm.batch_info([]) is None
    from repro.core.signatures import comp_sig, p2p_sig
    sigs = [comp_sig("gemm", 64, 64, 64), p2p_sig("send", 4096),
            comp_sig("gemm", 64, 64, 64)]
    det, sigma = cm.batch_info(sigs)
    assert det.shape == sigma.shape == (3,)
    assert sigma[0] == cm.noise and sigma[1] == cm.comm_noise
    assert det[0] == det[2]
    # the batched draw reproduces scalar sample() exactly
    r1 = np.random.default_rng(7)
    r2 = np.random.default_rng(7)
    batched = det * np.exp(sigma * r1.standard_normal(3))
    scalar = [cm.sample(s, r2) for s in sigs]
    assert batched.tolist() == scalar
    assert r1.bit_generator.state == r2.bit_generator.state


# ---------------------------------------------------- SLATE stream pinning

def _reference_slate(world, *, n, tile, lookahead, pr, pc):
    """The pre-PR-4 scan-and-filter SLATE generator (owner() over every
    tile), kept verbatim as the reference the optimized
    arithmetic-progression form is pinned against."""
    assert pr * pc == world.size
    nt = n // tile
    tb = 8 * tile * tile

    def owner(i, j):
        return (i % pr) + pr * (j % pc)

    def program(rank, world):
        TAG_LKK, TAG_ROW, TAG_COL = 0, 1, 2

        def panel(k):
            if owner(k, k) == rank:
                yield Comp("potrf", (tile,))
                sent = set()
                for i in range(k + 1, nt):
                    o = owner(i, k)
                    if o != rank and o not in sent:
                        sent.add(o)
                        yield Isend(o, tb, (TAG_LKK, k))
            my_tiles = [i for i in range(k + 1, nt)
                        if owner(i, k) == rank]
            if my_tiles and owner(k, k) != rank:
                yield Recv(owner(k, k), tb, (TAG_LKK, k))
            for i in my_tiles:
                yield Comp("trsm", (tile, tile))
                sent = set()
                for j in range(k + 1, i + 1):
                    o = owner(i, j)
                    if o != rank and o not in sent:
                        sent.add(o)
                        yield Isend(o, tb, (TAG_ROW, k, i))
                sent = set()
                for i2 in range(i, nt):
                    o = owner(i2, i)
                    if o != rank and o not in sent:
                        sent.add(o)
                        yield Isend(o, tb, (TAG_COL, k, i))

        def recv_for_update(k, i, j, got):
            src_row = owner(i, k)
            if ("r", i) not in got:
                got.add(("r", i))
                if src_row != rank:
                    yield Recv(src_row, tb, (TAG_ROW, k, i))
            src_col = owner(j, k)
            if ("c", j) not in got:
                got.add(("c", j))
                if src_col != rank:
                    yield Recv(src_col, tb, (TAG_COL, k, j))

        def updates(k, js, got):
            for j in js:
                for i in range(j, nt):
                    if owner(i, j) != rank:
                        continue
                    yield from recv_for_update(k, i, j, got)
                    if i == j:
                        yield Comp("syrk", (tile, tile))
                    else:
                        yield Comp("gemm", (tile, tile, tile))

        deferred = []
        for k in range(nt):
            while deferred and deferred[0][0] < k - lookahead:
                dk, djs, dgot = deferred.pop(0)
                yield from updates(dk, djs, dgot)
            yield from panel(k)
            got = set()
            if lookahead > 0:
                near = [j for j in
                        range(k + 1, min(k + 1 + lookahead, nt))]
                far = [j for j in range(k + 1 + lookahead, nt)]
                yield from updates(k, near, got)
                if far:
                    deferred.append((k, far, got))
            else:
                yield from updates(k, list(range(k + 1, nt)), got)
        for dk, djs, dgot in deferred:
            yield from updates(dk, djs, dgot)

    return program


def _op_key(op):
    c = op.__class__.__name__
    if c == "Comp":
        return (c, op.name, op.params)
    if c in ("Isend", "Send"):
        return (c, op.dst, op.nbytes, op.tag)
    if c == "Recv":
        return (c, op.src, op.nbytes, op.tag)
    return (c,)


def _drain(progf, rank, w):
    g = progf(rank, w)
    out = []
    v = None
    try:
        while True:
            op = g.send(v)
            v = 1 if isinstance(op, Isend) else None
            out.append(_op_key(op))
    except StopIteration:
        return out


@pytest.mark.parametrize("geom", [
    (512, 64, 1, 4, 4), (512, 128, 0, 4, 4), (1024, 64, 2, 2, 8),
    (768, 128, 3, 8, 2), (512, 256, 1, 1, 16), (512, 256, 0, 16, 1),
])
def test_slate_program_stream_unchanged(geom):
    n, tile, la, pr, pc = geom
    w = World(pr * pc)
    fast = slate_cholesky.make_program(w, n=n, tile=tile, lookahead=la,
                                      pr=pr, pc=pc)
    ref = _reference_slate(w, n=n, tile=tile, lookahead=la, pr=pr, pc=pc)
    for r in range(pr * pc):
        assert _drain(fast, r, w) == _drain(ref, r, w), f"rank {r}"
