"""Cross-task event-program cache: bit-identity and durability.

PR 10 makes the recorded event program a serializable, content-addressed
artifact (``repro.simmpi.program``): a structural fingerprint over
(study key, world size, geometry params) keys an in-process LRU plus an
optional crash-atomic on-disk store, and a ``Runtime`` whose program
factory carries that fingerprint skips the structural recording pass on a
hit.  The gate is bit-identity: a cache-hit run must produce byte-equal
iteration reports, engine state, and sampler RNG stream to a cache-miss
run — across all five policies, the three op-mix-distinct studies, and
the straggler branch on AND off.  Durability: a corrupted or
version-stale artifact triggers a LOUD re-record (never a silent replay),
and concurrent workers sharing one cache directory never observe torn
writes.
"""

from __future__ import annotations

import json
import multiprocessing
import os

import pytest

from repro.core.critter import Critter
from repro.core.policies import POLICIES, policy
from repro.linalg import candmc_qr, capital_cholesky, slate_cholesky
from repro.simmpi.comm import World
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2
from repro.simmpi.program import (PROGRAM_VERSION, ProgramCache,
                                  compile_events, program_from_payload,
                                  program_to_payload, structural_fingerprint)
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

FP = {name: structural_fingerprint(name, "p0", {"geom": name}, ws)
      for name, (ws, _) in STUDIES.items()}


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


def _run_protocol(study, pol, straggler_p, cache):
    """The tuner's per-configuration pattern (forced reference, selective
    trials, forced ``update_stats=False`` replay) under a fingerprint-
    stamped factory; ``cache=None`` is the uncached reference."""
    world_size, make = STUDIES[study]
    w = World(world_size)
    c = Critter(w, policy(pol, tolerance=0.25))
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0,
                   straggler_p=straggler_p)
    rt = Runtime(w, c, cm.sample, seed=3, program_cache=cache)
    prog = make(w)
    if cache is not None:
        prog.program_key = FP[study]
    trace = []
    for i in range(4):
        res = rt.run(prog, force_execute=(i == 0))
        trace.append(tuple(getattr(res, f) for f in REPORT_FIELDS))
        trace.append(_state_snapshot(c))
    res = rt.run(prog, force_execute=True, update_stats=False)
    trace.append(tuple(getattr(res, f) for f in REPORT_FIELDS))
    trace.append(_state_snapshot(c))
    trace.append(rt._rng.bit_generator.state)
    return trace, rt


@pytest.mark.parametrize("study", sorted(STUDIES))
@pytest.mark.parametrize("pol", POLICIES)
@pytest.mark.parametrize("straggler_p", [0.002, 0.0],
                         ids=["straggler-on", "straggler-off"])
def test_cache_hit_bit_identical(study, pol, straggler_p):
    """Miss (records + stores), hit (replays the artifact into a fresh
    World), and the uncached engine all produce byte-equal traces."""
    cache = ProgramCache()
    uncached, _ = _run_protocol(study, pol, straggler_p, None)
    miss, rt_miss = _run_protocol(study, pol, straggler_p, cache)
    assert rt_miss.recordings == 1 and rt_miss.cache_misses == 1
    hit, rt_hit = _run_protocol(study, pol, straggler_p, cache)
    assert rt_hit.recordings == 0 and rt_hit.cache_hits == 1
    for i, (u, m, h) in enumerate(zip(uncached, miss, hit)):
        assert u == m, (f"{study}/{pol}/straggler={straggler_p}: "
                        f"cache-MISS diverged at trace step {i}")
        assert u == h, (f"{study}/{pol}/straggler={straggler_p}: "
                        f"cache-HIT diverged at trace step {i}")


def test_disk_round_trip_bit_identical(tmp_path):
    """A program stored by one cache instance and loaded by another (fresh
    process simulation: cold LRU, disk only) replays bit-identically."""
    path = str(tmp_path / "progs")
    ref, _ = _run_protocol("slate", "conditional", 0.0, None)
    writer = ProgramCache(path)
    _run_protocol("slate", "conditional", 0.0, writer)
    assert writer.stores == 1 and os.listdir(path)
    reader = ProgramCache(path)
    got, rt = _run_protocol("slate", "conditional", 0.0, reader)
    assert rt.recordings == 0
    assert reader.disk_hits == 1 and reader.hits == 1
    assert got == ref


def test_fingerprint_is_structural():
    fp = structural_fingerprint("s", "p", {"n": 512, "tile": 64}, 16)
    assert fp == structural_fingerprint("s", "p", {"tile": 64, "n": 512},
                                        16)          # key order irrelevant
    assert fp.startswith(f"prog{PROGRAM_VERSION}:")
    others = [structural_fingerprint("s", "p", {"n": 512, "tile": 32}, 16),
              structural_fingerprint("s", "p", {"n": 512, "tile": 64}, 64),
              structural_fingerprint("s", "q", {"n": 512, "tile": 64}, 16),
              structural_fingerprint("t", "p", {"n": 512, "tile": 64}, 16)]
    assert len({fp, *others}) == 5


def test_payload_round_trip_equivalence():
    """Serialize from one World, materialize into a fresh one: identical
    event structure, signature tables, and communicator tables."""
    from repro.simmpi.ops import EV_BLOCK, EV_COLL
    ws, make = STUDIES["capital"]
    w1 = World(ws)
    rt = Runtime(w1, Critter(w1, policy("eager", 0.25)),
                 CostModel(KNL_STAMPEDE2).sample)
    before = len(w1._comms)
    prog = compile_events(rt._record(make(w1)))
    comms = list(w1._comms)[before:]
    payload = program_to_payload(prog, w1.interner.sigs, comms)
    payload = json.loads(json.dumps(payload))        # full JSON round trip

    w2 = World(ws)
    loaded = program_from_payload(payload, w2)
    assert list(w1.interner.sigs) == list(w2.interner.sigs)
    assert list(w1._comms) == list(w2._comms)
    assert loaded.n_slots == prog.n_slots
    assert len(loaded.events) == len(prog.events)
    for a, b in zip(prog.events, loaded.events):
        assert a[0] == b[0]
        if a[0] == EV_BLOCK:
            assert a[1] == b[1] and a[2].sids == b[2].sids
        elif a[0] == EV_COLL:
            assert a[1] == b[1] and a[2].ranks == b[2].ranks
        else:
            assert a == b


def _corrupt(path, mutate):
    files = [f for f in os.listdir(path) if f.endswith(".json")]
    assert len(files) == 1
    f = os.path.join(path, files[0])
    with open(f) as fh:
        doc = json.load(fh)
    mutate(doc)
    with open(f, "w") as fh:
        json.dump(doc, fh)


@pytest.mark.parametrize("mutate, reason", [
    (lambda d: d["payload"]["events"].pop(), "checksum"),
    (lambda d: d.update(version=PROGRAM_VERSION + 1), "version"),
    (lambda d: d.update(fingerprint="prog1:deadbeef"), "fingerprint"),
    (lambda d: d.clear(), "not a program document"),
], ids=["corrupted-payload", "stale-version", "wrong-fingerprint",
        "emptied"])
def test_bad_artifact_rerecords_loudly(tmp_path, capsys, mutate, reason):
    """Every invalid on-disk artifact is refused with a stderr complaint
    and the engine re-records — results identical to an uncached run,
    never a silent replay of the bad artifact."""
    path = str(tmp_path / "progs")
    ref, _ = _run_protocol("capital", "local", 0.0, None)
    _run_protocol("capital", "local", 0.0, ProgramCache(path))
    _corrupt(path, mutate)
    capsys.readouterr()
    cache = ProgramCache(path)
    got, rt = _run_protocol("capital", "local", 0.0, cache)
    assert got == ref
    assert rt.recordings == 1, "bad artifact must force a re-record"
    assert cache.rejects == 1 and cache.misses == 1 and cache.hits == 0
    err = capsys.readouterr().err
    assert "falling back to re-recording" in err
    # the re-record republishes a valid artifact over the bad one
    assert ProgramCache(path).lookup(FP["capital"]) is not None


def test_unreadable_artifact_rerecords_loudly(tmp_path, capsys):
    path = str(tmp_path / "progs")
    os.makedirs(path)
    fname = FP["candmc"].replace(":", "_") + ".json"
    with open(os.path.join(path, fname), "w") as fh:
        fh.write('{"version": 1, "payload": ')       # torn mid-write
    ref, _ = _run_protocol("candmc", "apriori", 0.002, None)
    cache = ProgramCache(path)
    got, rt = _run_protocol("candmc", "apriori", 0.002, cache)
    assert got == ref and rt.recordings == 1 and cache.rejects == 1
    assert "falling back" in capsys.readouterr().err


def test_lru_eviction_and_adopt():
    cache = ProgramCache(capacity=2)
    ws, make = STUDIES["capital"]
    for i in range(3):
        w = World(ws)
        rt = Runtime(w, Critter(w, policy("conditional", 0.25)),
                     CostModel(KNL_STAMPEDE2).sample, program_cache=cache)
        prog = make(w)
        prog.program_key = f"prog1:{i:08x}"
        rt.run(prog, force_execute=True)
    assert len(cache) == 2                      # oldest evicted
    assert cache.lookup("prog1:00000000") is None
    assert cache.lookup("prog1:00000002") is not None
    # adopt_program: direct injection skips recording entirely
    w = World(ws)
    rt = Runtime(w, Critter(w, policy("conditional", 0.25)),
                 CostModel(KNL_STAMPEDE2).sample, program_cache=cache)
    adopted = cache.get("prog1:00000002", w)
    rt.adopt_program("prog1:deadbeef", adopted)
    prog = make(w)
    prog.program_key = "prog1:deadbeef"
    rt.run(prog, force_execute=True)
    assert rt.recordings == 0


# ------------------------------------------------- concurrent shared dir

def _hammer(args):
    """One simulated worker: alternately publish and load the same
    fingerprint against a shared cache directory.  Returns the number of
    validation rejects observed — any torn write would surface as one."""
    path, seed = args
    ws, make = STUDIES["capital"]
    w = World(ws)
    rt = Runtime(w, Critter(w, policy("conditional", 0.25)),
                 CostModel(KNL_STAMPEDE2).sample)
    before = len(w._comms)
    prog = compile_events(rt._record(make(w)))
    comms = list(w._comms)[before:]
    fp = FP["capital"]
    cache = ProgramCache(path)
    loads = 0
    for i in range(20):
        if (i + seed) % 2:
            cache.put(fp, prog, w, comms=comms)
        else:
            cache._mem.clear()                  # force the disk path
            if cache.lookup(fp) is not None:
                loads += 1
    return cache.rejects, loads


def test_concurrent_workers_share_dir_without_torn_writes(tmp_path):
    path = str(tmp_path / "shared")
    ctx = multiprocessing.get_context("fork")
    with ctx.Pool(4) as pool:
        out = pool.map(_hammer, [(path, s) for s in range(4)])
    assert sum(r for r, _ in out) == 0, f"validation rejects: {out}"
    assert sum(l for _, l in out) > 0          # readers did hit disk
    assert ProgramCache(path).lookup(FP["capital"]) is not None


# ----------------------------------------------------- session integration

def test_sweep_records_once_per_geometry():
    """The acceptance counter end-to-end: a policy x tolerance sweep over
    one cached backend journals exactly one structural recording per
    unique geometry (first task records, every later task replays), and
    the results are bit-identical to the uncached sweep."""
    from golden_runner import golden_space
    from repro.api import AutotuneSession
    from repro.api.backends import SimBackend

    space = golden_space(1)
    kw = dict(policies=["conditional", "eager"], tolerances=[0.25, 0.1])

    cached = AutotuneSession(space, backend=SimBackend(program_cache="mem"),
                             trials=2)
    res = cached.sweep(**kw)
    pcs = [r.extra["program_cache"] for r in res]
    assert sum(p["recordings"] for p in pcs) == len(space.points)
    assert all(p["recordings"] == 0 for p in pcs[1:])
    assert all(p["hits"] == len(space.points) for p in pcs[1:])
    assert pcs[0]["fingerprints"].keys() == {p.name for p in space.points}
    evs = [e for e in cached.last_sweep_events
           if e.get("event") == "program_cache"]
    assert len(evs) == len(res)
    assert sum(e["recordings"] for e in evs) == len(space.points)

    plain = AutotuneSession(space, backend=SimBackend(), trials=2)

    def strip(r):
        d = r.to_json()
        d.pop("wall_s", None)
        d.get("extra", {}).pop("program_cache", None)
        return d

    assert [strip(a) for a in res] == [strip(b) for b in plain.sweep(**kw)]


def test_payload_fingerprint_mismatch_is_loud():
    """run_payload refuses a task whose dispatcher-side fingerprints
    disagree with what this (space, backend) computes — geometry drift
    must fail the task, not silently measure the wrong program."""
    from golden_runner import golden_space
    from repro.api.backends import SimBackend
    from repro.api.session import AutotuneSession, run_payload

    space = golden_space(1)
    backend = SimBackend(program_cache="mem")
    sess = AutotuneSession(space, backend=backend, trials=2)
    payload = sess._task_payload(("conditional", 0.25, 0, 0), None,
                                 collect=False, shared=False)
    fps = payload["program_fingerprints"]
    assert fps == backend.point_fingerprints(space)
    ok = run_payload(space, backend, json.loads(json.dumps(payload)))
    assert ok["policy"] == "conditional"

    drifted = dict(payload)
    drifted["program_fingerprints"] = {
        name: "prog1:00000bad" for name in fps}
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        run_payload(space, backend, drifted)
