"""Always-on tuning daemon (repro.api.daemon + repro.serve.tuner) tests.

- a shape miss opens a study whose winner matches an offline
  ``LMStudy.session`` run under the same deterministic clock;
- a warm-started shape's study executes strictly fewer kernels than the
  cold one (fleet-store transfer);
- an injected kernel-cost shift trips the drift detector and the
  background re-tune lands a new winner while serving continues;
- daemon checkpoint kill/restore resumes with the fleet bank intact;
- a background re-tune through ``ForkExecutor`` is bit-identical to the
  in-process run;
- satellites: age-aware ``KernelStats`` discounting round-trips through
  JSON, the engine and the daemon share ONE bucketing function, and
  ``StatisticsBank.save`` is crash-atomic.
"""

import json
import os

import pytest

from repro.api import (AutotuneSession, ConfigPoint, DaemonConfig,
                       ForkExecutor, InProcessExecutor, RESET_POLICY,
                       SearchSpace, StatisticsBank, TuningDaemon,
                       WallClockBackend, fork_available)
from repro.api.daemon import (BackgroundTuner, DriftDetector, FleetStore,
                              TUNED, TUNING)
from repro.core.signatures import comp_sig, structural_key
from repro.core.stats import KernelStats
from repro.serve.engine import bucket_length
from repro.serve.tuner import VirtualClock, shape_key


def _stats_of(xs) -> KernelStats:
    ks = KernelStats()
    for x in xs:
        ks.update(x)
    return ks


# ------------------------------------------------- synthetic study provider

class VirtualClockBackend(WallClockBackend):
    """The wall-clock protocol over virtual-clock thunks: they touch no
    device, so unlike real wall-clock kernels they may run in a fork."""

    parallel_safe = True


class SyntheticProvider:
    """Two-config studies over fake kernels with dict-driven costs.

    Every shape's step runs a fleet-shared kernel plus a per-(config,
    shape) kernel; costs come from a mutable dict, so a mid-run cost
    shift is one assignment.  Thunks advance the clock their context
    reads — serving thunks the daemon's serve clock, each study a FRESH
    per-study clock — so every measured value is an exact (cost + dt)
    independent of absolute clock state; fork and in-process study runs
    are therefore bit-identical.
    """

    def __init__(self, serve_clock, costs, *, trials: int = 2):
        self.serve_clock = serve_clock
        self.costs = costs
        self.trials = trials
        self.executions = 0     # ground-truth count of thunk invocations

    def _kernels(self, shape, which, clock):
        out = []
        for name, freq in (("shared", 2), (f"{which}-{shape}", 4)):
            sig = comp_sig(name)
            costs = self.costs

            def thunk(name=name):
                self.executions += 1
                clock.now += costs[name]
            out.extend([(sig, thunk, freq)] * freq)
        return out

    def _space(self, shape):
        pts = [ConfigPoint(name="A", params={"w": "a"},
                           payload=("a", shape)),
               ConfigPoint(name="B", params={"w": "b"},
                           payload=("b", shape))]
        return SearchSpace(name=f"syn-{shape}", points=pts,
                           reset_between_configs=RESET_POLICY)

    def session_for(self, key, meta, prior):
        clock = VirtualClock()

        def kernels_of(point):
            which, shape = getattr(point, "payload", point)
            return self._kernels(shape, which, clock)

        return AutotuneSession(
            self._space(meta["shape"]),
            backend=VirtualClockBackend(kernels_of, clock=clock),
            policy="eager", tolerance=0.5, min_samples=2,
            trials=self.trials, prior=prior, prior_discount=1.0,
            collect_stats=True)

    def kernels_for(self, key, meta, winner_name):
        return self._kernels(meta["shape"], winner_name.lower(),
                             self.serve_clock)

    def kernel_keys(self, key, meta, winner_name):
        return sorted({structural_key(s, 1) for s, _, _ in
                       self.kernels_for(key, meta, winner_name)})


def _daemon(costs=None, *, checkpoint=None, executor_factory=None):
    clock = VirtualClock()
    costs = dict(costs or {"shared": 1e-3,
                           "a-s1": 1e-3, "b-s1": 3e-3,
                           "a-s2": 1e-3, "b-s2": 3e-3})
    cfg = DaemonConfig(shadow_every=3, drift_z=3.0, drift_min_samples=2,
                       serve_min_samples=2, synchronous=True)
    d = TuningDaemon(SyntheticProvider(clock, costs), clock=clock,
                     config=cfg, checkpoint=checkpoint,
                     executor_factory=executor_factory)
    return d, clock, costs


def _tune(d, key, shape):
    info = d.serve(key, {"shape": shape})
    d.pump()
    return info


def _events(d, kind):
    return [e for e in d.events if e["event"] == kind]


# ----------------------------------------------------------- router + serve

def test_shape_miss_opens_study_then_serves_tuned():
    d, _, _ = _daemon()
    info = d.serve("k1", {"shape": "s1"})
    assert info["state"] == "miss" and info["winner"] is None
    assert d.pump() == 1
    info = d.serve("k1", {"shape": "s1"})
    assert info["state"] == TUNED
    assert info["winner"] == "A"          # cheaper per-config kernel
    # second occurrence: every winner kernel is banked and confident, so
    # the selective timer runs zero kernels and charges stored means
    assert info["executed"] == 0 and info["cold_banked"] == 0
    assert info["skipped"] > 0 and info["charged"] > 0.0


def test_daemon_winner_matches_offline_lm_session():
    """The daemon's shape-miss study converges to the same winner as an
    offline ``LMStudy.session`` run under the same deterministic clock."""
    from repro.serve.tuner import LMShapeProvider, ServingTuner
    from repro.tune.lm_study import LMStudy

    offline = LMStudy("smollm-135m", batch=2, seq=16).session(
        policy="eager", trials=2, max_configs=2,
        clock=VirtualClock(), collect_stats=True).run()

    tuner = ServingTuner(
        "smollm-135m", seq_buckets=(16,), clock=VirtualClock(),
        provider=LMShapeProvider(trials=2, max_configs=2,
                                 clock=VirtualClock()),
        config=DaemonConfig(shadow_every=3, serve_min_samples=2,
                            synchronous=True))
    assert tuner.serve_step(2, 16)["state"] == "miss"
    tuner.daemon.pump()
    info = tuner.serve_step(2, 16)
    assert info["state"] == TUNED
    assert info["winner"] == offline.chosen.name
    assert info["executed"] == 0 and info["cold_banked"] == 0
    assert tuner.knobs_for(2, 16).name == offline.chosen.name


def test_warm_started_shape_executes_fewer_kernels():
    d, _, _ = _daemon()
    prov = d.provider
    _tune(d, "k1", "s1")
    cold_execs = prov.executions
    _tune(d, "k2", "s2")        # warm: 'shared' is already banked
    warm_execs = prov.executions - cold_execs
    assert d.counters["warm_starts"] == 1
    started = _events(d, "tune_started")
    assert started[0]["warm"] is False and started[1]["warm"] is True
    assert 0 < warm_execs < cold_execs


def test_drift_detected_and_retune_lands_new_winner():
    d, _, costs = _daemon()
    _tune(d, "k1", "s1")
    assert d.winners["k1"]["name"] == "A"
    costs["a-s1"] = 10e-3                 # the winner's kernel got slow
    for _ in range(12):
        info = d.serve("k1", {"shape": "s1"})
        assert info["winner"] is not None     # serving never stops
        d.pump()
        if d.counters["retunes"]:
            break
    assert d.counters["drifts"] >= 1
    assert d.counters["retunes"] >= 1
    assert d.winners["k1"]["name"] == "B"     # re-tune flipped the winner
    names = [e["event"] for e in d.events]
    assert "drift_detected" in names and "retune_complete" in names
    retune = _events(d, "retune_complete")[-1]
    assert retune["previous"] == "A" and retune["winner"] == "B"


def test_drift_requires_min_samples_and_respects_ci():
    store = FleetStore(StatisticsBank(
        {"k": _stats_of([1.0, 1.1, 0.9, 1.0])}))
    det = DriftDetector(store, z=3.0, min_samples=3)
    assert det.observe("k", 5.0) is False     # 1 sample < min_samples
    assert det.observe("k", 5.0) is False
    assert det.observe("k", 5.0) is True      # live mean far outside CI
    # live samples matching the stored mean never drift
    det2 = DriftDetector(store, z=3.0, min_samples=3)
    assert not any(det2.observe("k", 1.0) for _ in range(10))
    # nothing stored -> nothing to drift from
    assert DriftDetector(store).observe("unknown", 9.9) is False


# ------------------------------------------------------ checkpoint / restore

def test_checkpoint_kill_restore_keeps_fleet_bank(tmp_path):
    ck = str(tmp_path / "daemon.json")
    d, _, costs = _daemon(checkpoint=ck)
    _tune(d, "k1", "s1")
    _tune(d, "k2", "s2")
    d.save_checkpoint()
    fp = d.fleet.bank.fingerprint()

    d2, _, _ = _daemon(costs, checkpoint=ck)  # "restart"
    assert d2.fleet.bank.fingerprint() == fp
    assert d2.winners == d.winners
    assert d2.state == {"k1": TUNED, "k2": TUNED}
    assert [e["event"] for e in d2.events][:len(d.events)] == \
        [e["event"] for e in d.events]
    info = d2.serve("k1", {"shape": "s1"})
    assert info["state"] == TUNED and info["executed"] == 0


def test_checkpoint_restore_resubmits_inflight_studies(tmp_path):
    ck = str(tmp_path / "daemon.json")
    d, _, costs = _daemon(checkpoint=ck)
    _tune(d, "k1", "s1")
    # open a study for k2 but "kill" the daemon before pumping the result
    d.serve("k2", {"shape": "s2"})
    assert d.state["k2"] == TUNING
    d.save_checkpoint()

    d2, _, _ = _daemon(costs, checkpoint=ck)
    d2.pump()                              # resubmitted study lands
    assert d2.state.get("k2") == TUNED
    assert d2.winners["k2"]["name"] == "A"


# ------------------------------------------------- fork-executor parity

@pytest.mark.skipif(not fork_available(), reason="no os.fork")
def test_fork_background_retune_bit_identical_to_inprocess():
    """A study forked to a worker must land the exact state an in-process
    run lands: every study starts from a fresh virtual clock and every
    fleet stamp comes off the parent-side serve clock, so the full
    snapshot — stats moments, winners, predicted times, counters, event
    journal including timestamps — is bit-identical across executors."""
    def flow(factory):
        d, _, costs = _daemon(executor_factory=factory)
        _tune(d, "k1", "s1")
        _tune(d, "k2", "s2")
        costs["a-s1"] = 10e-3
        for _ in range(12):
            d.serve("k1", {"shape": "s1"})
            d.pump()
            if d.counters["retunes"]:
                break
        d.pump()
        assert d.winners["k1"]["name"] == "B"
        return json.loads(json.dumps(d.snapshot()))

    inproc = flow(InProcessExecutor)
    forked = flow(lambda: ForkExecutor(1))
    assert forked == inproc


@pytest.mark.skipif(not fork_available(), reason="no os.fork")
def test_background_tuner_refuses_fork_for_serial_backend():
    """A backend that is not parallel_safe (real wall-clock kernels, which
    hold the chip in this process) is never handed to a fork pool."""
    clock = VirtualClock()
    session = SyntheticProvider(clock, {}).session_for(
        "k", {"shape": "s1"}, None)
    session.backend = WallClockBackend(session.backend.kernels_of,
                                       clock=clock)
    tuner = BackgroundTuner(executor_factory=lambda: ForkExecutor(1),
                            synchronous=True)
    with pytest.raises(ValueError, match="parallel_safe"):
        tuner.submit("k", session)


# --------------------------------------------------- satellite: age discount

def test_last_updated_roundtrips_and_keeps_old_banks_stable():
    st = _stats_of([1.0, 2.0, 3.0])
    st.last_updated = 123.5
    back = KernelStats.from_json(st.to_json())
    assert back.last_updated == 123.5
    assert back.copy().last_updated == 123.5
    # unstamped records serialize exactly as before (no new JSON field),
    # so pre-daemon banks keep their fingerprints
    assert "last_updated" not in _stats_of([1.0, 2.0]).to_json()
    bank = StatisticsBank({"k": _stats_of([1.0, 2.0])})
    fp = bank.fingerprint()
    bank.stamp(50.0)
    assert bank.fingerprint() != fp
    assert StatisticsBank.from_json(bank.to_json()) \
        .entries["k"].last_updated == 50.0


def test_discount_by_age_halves_evidence_per_half_life():
    st = _stats_of([1.0, 1.1, 0.9, 1.0, 1.05, 0.95, 1.0, 1.0])
    st.last_updated = 0.0
    aged = st.discount_by_age(100.0, 100.0)    # exactly one half-life
    assert aged.n == st.n // 2
    assert aged.mean == pytest.approx(st.mean)
    assert aged.variance == pytest.approx(st.variance)
    assert aged.ci_halfwidth() > st.ci_halfwidth()
    # unstamped evidence does not age; future stamps do not rejuvenate
    assert _stats_of([1.0, 2.0]).discount_by_age(1e9, 1.0).n == 2
    assert st.discount_by_age(-5.0, 1.0).n == st.n


def test_bank_discount_by_age_ttl_and_merge_stamps():
    young = _stats_of([1.0] * 4)
    young.last_updated = 90.0
    old = _stats_of([2.0] * 4)
    old.last_updated = 0.0
    bank = StatisticsBank({"young": young, "old": old})
    view = bank.discount_by_age(100.0, half_life=10.0, ttl=50.0)
    assert "old" not in view.entries            # beyond the TTL
    assert view.entries["young"].n == 2         # one half-life of age
    assert bank.entries["old"].n == 4           # source untouched
    # merge keeps the freshest stamp
    a, b = _stats_of([1.0]), _stats_of([2.0])
    a.last_updated, b.last_updated = 10.0, 20.0
    a.merge(b)
    assert a.last_updated == 20.0


# ------------------------------------------- satellite: unified bucketing

def test_engine_and_daemon_share_one_bucketing_function():
    from repro.serve.engine import Engine

    class _FakeEngine:
        class sc:
            prompt_buckets = (16, 32, 64)

    for n in (1, 16, 17, 32, 50, 64, 100):
        assert Engine._bucket(_FakeEngine(), n) == \
            bucket_length(n, (16, 32, 64))
    assert bucket_length(7, ()) == 7            # no buckets: identity
    assert bucket_length(100, (16, 32)) == 32   # clamped to the last
    # the daemon's shape keys bucket through the same function
    assert shape_key("smollm-135m", 2, bucket_length(24, (16, 32))) == \
        shape_key("smollm-135m", 2, 32)


# --------------------------------------------- satellite: crash-safe save

def test_bank_save_is_atomic_and_leaves_no_droppings(tmp_path,
                                                     monkeypatch):
    path = str(tmp_path / "bank.json")
    st = _stats_of([1.0, 2.0])
    st.last_updated = 7.0
    bank = StatisticsBank({"k": st})
    bank.save(path)
    loaded = StatisticsBank.load(path)
    assert loaded.fingerprint() == bank.fingerprint()
    assert loaded.entries["k"].last_updated == 7.0
    # a crash mid-save must leave the previous bank intact and no temp
    bank2 = StatisticsBank({"k": _stats_of([9.0, 9.0])})

    def boom(src, dst):
        raise OSError("disk went away")
    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        bank2.save(path)
    monkeypatch.undo()
    assert StatisticsBank.load(path).fingerprint() == bank.fingerprint()
    assert os.listdir(tmp_path) == ["bank.json"]


def test_fleet_store_record_prior_and_evict():
    clock = VirtualClock()
    fs = FleetStore(clock=clock, half_life=1e9)
    fs.record("k", 2.0)
    fs.record("k", 2.0)
    assert fs.reference("k").n == 2
    assert fs.reference("k").last_updated is not None
    assert len(fs.prior()) == 1
    assert fs.evict(["k", "missing"]) == 1
    assert fs.reference("k") is None
