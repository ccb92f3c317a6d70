"""The program's own profiler spans (``jax.profiler.TraceAnnotation``):
the serving engine's ``engine.*`` and the tuner's ``tuner.*``, read back
from a CPU trace's host plane."""

import glob
import os
import time

import jax
import numpy as np
from jax.profiler import ProfileData, TraceAnnotation

from repro.api import AutotuneSession, ConfigPoint, SearchSpace
from repro.api import WallClockBackend
from repro.configs import get_config
from repro.core.signatures import comp_sig
from repro.models.model import Model, ModelKnobs
from repro.serve.engine import Engine, Request, ServeConfig


def _traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; the host spans as (name, start,
    end, thread line)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            line.name) for e in line.events)
    return out


def _named(spans, prefix):
    return [sp for sp in spans if sp[0].startswith(prefix)]


def _overlap(a, b):
    return a[3] == b[3] and a[1] < b[2] and b[1] < a[2]


def _leaves(spans):
    """No span of ``spans`` lies inside another."""
    return not any(a is not b and a[3] == b[3] and a[1] <= b[1]
                   and b[2] <= a[2] for a in spans for b in spans)


def test_engine_spans_once_per_step_and_per_admission(tmp_path):
    cfg = get_config("smollm-135m", reduced=True)
    model = Model(cfg, ModelKnobs(kv_chunk=16, ssm_chunk=8))
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 ServeConfig(batch_size=2, s_max=64, max_new_tokens=4,
                             prompt_buckets=(8, 16)))
    eng.submit(Request(99, np.arange(5, dtype=np.int32)))
    eng.run()                                   # compiles, untraced
    for uid in range(5):                        # more requests than slots
        eng.submit(Request(uid, np.arange(3 + uid, dtype=np.int32)
                           % cfg.vocab))
    steps = []

    def serve():
        while eng.queue or eng.active.any():
            eng.step()
            steps.append(1)
    spans = _named(_traced(tmp_path, serve), "engine.")
    count = {n: len(_named(spans, n)) for n in
             ("engine.prefill", "engine.splice", "engine.first_token",
              "engine.decode", "engine.sample")}
    assert count == {"engine.prefill": 5, "engine.splice": 5,
                     "engine.first_token": 5,
                     "engine.decode": len(steps),
                     "engine.sample": len(steps)}
    assert _leaves(spans)
    assert all(len(eng.results[u].tokens) == 4 for u in range(5))


def test_tuner_spans_leave_the_kernels_alone(tmp_path):
    """``tuner.decide`` once per trial kernel occurrence, ``tuner.update``
    once per executed trial kernel, and no tuner span over a kernel."""
    sigs = [comp_sig("ka", 1), comp_sig("kb", 2)]
    calls = {"n": 0}

    def thunk_of(sig):
        def thunk():
            calls["n"] += 1
            with TraceAnnotation(f"kernel {sig}"):
                time.sleep(2e-4)
        return thunk

    def kernels_of(point):
        return [(sig, thunk_of(sig), 2) for sig in sigs for _ in range(2)]

    points = [ConfigPoint(name=f"c{i}", params={"i": i}) for i in range(3)]
    space = SearchSpace(name="fake", points=points)
    trials = 3
    session = AutotuneSession(space, backend=WallClockBackend(kernels_of),
                              policy="eager", tolerance=1.0, min_samples=2,
                              trials=trials)
    result = []
    spans = _traced(tmp_path, lambda: result.append(session.run()))
    per_config = len(kernels_of(None))
    reference = per_config * len(points)
    trial_kernels = per_config * trials * len(points)
    decide = _named(spans, "tuner.decide")
    update = _named(spans, "tuner.update")
    assert len(decide) == trial_kernels
    assert len(update) == calls["n"] - reference
    assert 0 < len(update) < trial_kernels      # eager skipped some
    book = _named(spans, "tuner.bookkeeping")
    assert len(book) >= len(points) + 2         # records; before/after
    kernels = _named(spans, "kernel ")
    assert len(kernels) == calls["n"]
    tuner = _named(spans, "tuner.")
    assert _leaves(tuner)
    assert not any(_overlap(t, k) for t in tuner for k in kernels)
    assert len(result[0].records) == len(points)


def test_spans_cost_little_untraced():
    """Without a profiler session a span is one annotation object: well
    under 20 us on any host this suite runs on."""
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("tuner.decide"):
            pass
    assert (time.perf_counter() - t0) / n < 20e-6
