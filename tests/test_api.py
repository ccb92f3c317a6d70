"""Session-API contract tests.

- backend equivalence: ``AutotuneSession`` + ``SimBackend`` reproduces the
  seed engine's golden reports bit-for-bit (same pin as
  ``test_golden_reports``, but through the new front-end);
- parallel-sweep determinism: an N-worker fork-parallel sweep produces
  exactly the serial sweep's merged results;
- checkpoint/resume: partial studies and sweeps resume from JSON and land
  on results identical to an uninterrupted run;
- lossless JSON round-trips of ``ConfigRecord``/``StudyResult`` (tuples in
  params, infinities, NumPy scalars).
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import (AutotuneSession, ConfigPoint, ConfigRecord,
                       SearchSpace, SimBackend, StudyResult,
                       WallClockBackend)
from repro.core.policies import POLICIES
from repro.core.signatures import comp_sig
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2

from golden_runner import GOLDEN_PATH, _studies

GOLDEN_FIELDS = ("full_time", "predicted", "rel_error", "comp_error",
                 "selective_cost", "full_cost", "executed", "skipped",
                 "predictions")


def _golden_backend():
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0, bias_sigma=0.0)
    return SimBackend(timer=cm.sample)


def _strip_wall(result_json: dict) -> dict:
    d = dict(result_json)
    d.pop("wall_s", None)
    return d


# -- backend equivalence ------------------------------------------------------

def test_session_simbackend_reproduces_goldens():
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    for space in _studies():
        for pol in POLICIES:
            session = AutotuneSession(space, backend=_golden_backend(),
                                      policy=pol, tolerance=0.25, trials=2)
            result = session.run()
            assert result.study == space.name
            assert result.backend == "sim"
            g_recs = golden[space.name][pol]
            assert len(result.records) == len(g_recs)
            got = json.loads(json.dumps(
                [r.to_json() for r in result.records]))
            for g, n in zip(g_recs, got):
                assert n["name"] == g["name"]
                for field in GOLDEN_FIELDS:
                    assert n[field] == g[field], \
                        f"{space.name}/{pol}/{g['name']}/{field}: " \
                        f"{n[field]!r} != {g[field]!r}"


# -- parallel sweep determinism ----------------------------------------------

def _tiny_session():
    return AutotuneSession(_studies()[1],   # golden-capital: world 8
                           backend=_golden_backend(), trials=2)


def test_parallel_sweep_matches_serial():
    # worker count is PINNED to 2, never derived from os.cpu_count():
    # on a single-core CI box a cpu-derived count degenerates to 1 and the
    # fork path silently goes untested.  run_tasks forks regardless of
    # core count, so 2 workers exercise it everywhere fork exists.
    from repro.api.scheduler import fork_available
    assert fork_available(), \
        "no os.fork: the parallel sweep path cannot be exercised here"
    kw = dict(policies=["conditional", "eager"], tolerances=[1.0, 0.25])
    serial = _tiny_session().sweep(workers=1, **kw)
    forked = _tiny_session().sweep(workers=2, **kw)
    assert len(serial) == len(forked) == 4
    for s, p in zip(serial, forked):
        assert _strip_wall(s.to_json()) == _strip_wall(p.to_json())


# -- checkpoint / resume ------------------------------------------------------

def test_sweep_checkpoint_resume(tmp_path):
    ck = str(tmp_path / "sweep.json")
    kw = dict(policies=["conditional", "online"], tolerances=[0.25])
    # interrupted run: only the first sweep point completes
    first = _tiny_session().sweep(policies=["conditional"],
                                  tolerances=[0.25], checkpoint=ck)
    assert len(first) == 1
    # resumed run computes only the missing point and merges in grid order
    resumed = _tiny_session().sweep(checkpoint=ck, **kw)
    fresh = _tiny_session().sweep(**kw)
    assert len(resumed) == len(fresh) == 2
    # the checkpointed point is returned verbatim (wall_s included)
    assert resumed[0].to_json() == first[0].to_json()
    for a, b in zip(resumed, fresh):
        assert _strip_wall(a.to_json()) == _strip_wall(b.to_json())


class _FailingBackend(SimBackend):
    """Raises on the named configuration's reference run, once."""

    def __init__(self, fail_at: str, **kw):
        super().__init__(**kw)
        self.fail_at = fail_at
        self.tripped = False

    def open(self, *a, **kw):
        run = super().open(*a, **kw)
        orig = run.run_reference

        def ref(point):
            if not self.tripped and point.name == self.fail_at:
                self.tripped = True
                raise RuntimeError("interrupted")
            return orig(point)

        run.run_reference = ref
        return run


def test_study_checkpoint_resumes_partial_records(tmp_path):
    """Kill a study mid-run; the resumed study must be bit-identical to an
    uninterrupted one — including the sim RNG stream, which the journal
    carries across the interruption."""
    ck = str(tmp_path / "study.json")
    space = _studies()[0]           # golden-slate: resets between configs

    def session(backend):
        return AutotuneSession(space, backend=backend, policy="online",
                               tolerance=0.25, trials=2)

    reference = session(_golden_backend()).run()

    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0, bias_sigma=0.0)
    failing = _FailingBackend(space.points[1].name, timer=cm.sample)
    with pytest.raises(RuntimeError, match="interrupted"):
        session(failing).run(checkpoint=ck)
    # the journal holds config 0's record (+ RNG carry) — resume from it
    resumed = session(failing).run(checkpoint=ck)
    assert _strip_wall(resumed.to_json()) == \
        _strip_wall(reference.to_json())
    # a third run loads the completed result straight from the journal
    again = session(_golden_backend()).run(checkpoint=ck)
    assert again.to_json() == resumed.to_json()


# -- racing through the session front-end -------------------------------------

def test_racing_search_via_session():
    session = AutotuneSession(_studies()[1],
                              backend=_golden_backend(), policy="online",
                              tolerance=0.25, search="racing",
                              search_options={"max_rounds": 3})
    result = session.run()
    names = {p.name for p in session.space.points}
    assert result.search == "racing"
    assert result.extra["best"] in names
    assert set(result.extra["survivors"]) <= names
    assert result.extra["total_iterations"] <= 3 * len(names)
    assert all(r.predictions for r in result.records)
    # racing has no full-execution reference: the ratio metrics must be
    # NaN (not a crash, not a fake 0/inf) and the row must tabulate
    assert math.isnan(result.speedup)
    assert math.isnan(result.optimum_quality)
    row = result.row()
    assert row["selective_time"] == result.selective_tuning_time > 0


# -- wall-clock backend through the session -----------------------------------

def test_wallclock_backend_accounting():
    """Deterministic scripted clock: kernel A costs 1.0, kernel B 0.01;
    with a loose tolerance the timer must start skipping and the session's
    speedup/accounting must reflect the skipped executions."""
    sig_a, sig_b = comp_sig("ka", 1), comp_sig("kb", 2)
    now = [0.0]
    durations = {sig_a: 1.0, sig_b: 0.01}
    current = [None]

    def clock():
        return now[0]

    def make_thunk(sig):
        def thunk():
            now[0] += durations[sig]
        return thunk

    kernels = [(sig_a, make_thunk(sig_a), 1), (sig_b, make_thunk(sig_b), 1)]

    def kernels_of(point):
        return kernels

    space = SearchSpace(name="fake", points=[
        ConfigPoint(name="c0", params={"i": 0}),
        ConfigPoint(name="c1", params={"i": 1})])
    session = AutotuneSession(
        space, backend=WallClockBackend(kernels_of, clock=clock),
        policy="eager", tolerance=1.0, min_samples=2, trials=4)
    result = session.run()
    assert result.backend == "wallclock"
    assert len(result.records) == 2
    # eager keeps models across configs: by config c1 everything is skipped
    assert result.records[1].skipped > 0
    assert result.selective_tuning_time < result.full_tuning_time
    assert result.speedup > 1.0


def test_apriori_requires_sim_backend():
    def kernels_of(point):
        return []
    space = SearchSpace(name="fake", points=[ConfigPoint(name="c0")])
    session = AutotuneSession(space,
                              backend=WallClockBackend(kernels_of),
                              policy="apriori", tolerance=0.5)
    with pytest.raises(NotImplementedError):
        session.run()


# -- cross-process determinism -------------------------------------------------

_XPROC_SNIPPET = """
import json, sys
sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[2])
from repro.api import AutotuneSession, SimBackend
from golden_runner import _studies
res = AutotuneSession(_studies()[1], backend=SimBackend(),
                      policy="online", tolerance=0.25, trials=2).run()
d = res.to_json(); d.pop("wall_s")
print(json.dumps(d, sort_keys=True))
"""


def test_cross_process_determinism_with_default_bias():
    """The DEFAULT cost model (bias_sigma > 0) must reproduce across
    interpreters with different hash seeds — the property checkpoint
    resume and recorded sweep artifacts rely on (the allocation bias is
    crc32-keyed, not hash()-keyed)."""
    here = os.path.dirname(__file__)
    src = os.path.join(here, os.pardir, "src")

    def run(hashseed):
        env = dict(os.environ, PYTHONHASHSEED=hashseed)
        out = subprocess.run(
            [sys.executable, "-c", _XPROC_SNIPPET, src, here],
            capture_output=True, text=True, env=env, check=True)
        return out.stdout.strip()

    assert run("1") == run("2")


# -- lossless serialization ---------------------------------------------------

def test_config_record_json_roundtrip_lossless():
    rec = ConfigRecord(
        name="cfg", params={"grid": (4, 8), "tile": np.int64(64),
                            "alpha": np.float64(0.5), "tag": "x",
                            "nested": {"dims": (1, (2, 3))},
                            "flags": [True, None]},
        full_time=1.25, predicted=float("inf"), rel_error=0.5,
        comp_error=0.0, selective_cost=0.75, full_cost=3.75,
        executed=10, skipped=2,
        predictions=[0.1, float("-inf"), 0.3],
        extra={"pruned_at": None})
    back = ConfigRecord.from_json(json.loads(json.dumps(rec.to_json())))
    assert back.params == {"grid": (4, 8), "tile": 64, "alpha": 0.5,
                           "tag": "x", "nested": {"dims": (1, (2, 3))},
                           "flags": [True, None]}
    assert isinstance(back.params["grid"], tuple)
    assert back.predicted == math.inf
    assert back.predictions == [0.1, -math.inf, 0.3]
    assert back == ConfigRecord.from_json(rec.to_json())


def test_study_result_json_roundtrip_lossless():
    rec = ConfigRecord(name="c", params={"b": (2, 3)}, full_time=1.0,
                       predicted=0.9, rel_error=0.1, comp_error=0.05,
                       selective_cost=0.5, full_cost=3.0, executed=4,
                       skipped=6, predictions=[0.8, 0.9])
    res = StudyResult(study="s", policy="online", tolerance=0.25,
                      records=[rec], full_tuning_time=3.0,
                      selective_tuning_time=0.5, backend="sim",
                      search="exhaustive", seed=1, allocation=2,
                      wall_s=0.1, extra={"survivors": ["c"]})
    back = StudyResult.from_json(json.loads(json.dumps(res.to_json())))
    assert back == res
    assert back.records[0].params["b"] == (2, 3)


def test_serializer_rejects_unknown_types():
    from repro.api import to_jsonable
    with pytest.raises(TypeError):
        to_jsonable({"bad": object()})
