"""The idle split (bench/harness/idle_split.py): device idle time put down
to the innermost host span over it, the flows' bounds on the device
clock's offset, and the readers of the metrics it feeds."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny as B  # noqa: E402

from harness import idle_split as I  # noqa: E402
from harness import spec  # noqa: E402
from harness import trace as T  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "fixture.xplane.pb"
READERS = ("idle_engine.serve", "decode_ms.serve", "admit_ms.serve",
           "idle_stats.tune", "idle_session.tune")


@pytest.fixture(scope="module")
def red():
    return T.reduce_trace(str(FIXTURE))


@pytest.fixture(scope="module")
def split():
    return I.split_trace(str(FIXTURE))


def _busy(pairs):
    return T._Busy(np.asarray([a for a, _ in pairs], float),
                   np.asarray([b for _, b in pairs], float))


@pytest.mark.parametrize("busy, spans, want", [
    # nested: B inside A takes the idle it covers, A the rest of its own
    ([(10, 20)], [("A", 0, 60), ("B", 30, 50)],
     {"A": 30.0, "B": 20.0, "bench window": 40.0}),
    # crossing: from B's start on, B (the later start) is innermost
    ([], [("A", 0, 60), ("B", 40, 80)],
     {"A": 40.0, "B": 40.0, "bench window": 20.0}),
    # uncovered: idle time under no span goes to the window
    ([(0, 30)], [], {"bench window": 70.0}),
    # a span that starts with another: the greater name, as gap labels
    ([(50, 60)], [("A", 20, 80), ("B", 20, 40)],
     {"A": 30.0, "B": 20.0, "bench window": 40.0}),
    # busy all through a span: it keeps its name with no idle
    ([(5, 15)], [("A", 5, 15)], {"A": 0.0, "bench window": 90.0}),
], ids=["nested", "crossing", "uncovered", "same-start", "busy-span"])
def test_self_idle_on_synthetic_intervals(busy, spans, want):
    got = I.self_idle(0.0, 100.0, _busy(busy), spans)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(
        100.0 - sum(b - a for a, b in busy))


@pytest.mark.parametrize("at_bound", [False, True])
def test_self_idle_adds_up_to_window_less_busy(at_bound):
    split = I.split_trace(str(FIXTURE), at_bound=at_bound)
    idle = sum(split.self_idle_s.values())
    want = split.window_s - split.busy_s
    assert abs(idle - want) <= 1e-9 * want
    # the 2 ms host sleeps are most of it, as the gap labels say
    assert max(split.self_idle_s, key=split.self_idle_s.get) \
        == "skip bookkeeping"


def test_split_reads_what_the_reduction_reads(red, split):
    assert split.offsets_ns == red.offsets_ns
    assert split.window_s == red.window_s
    assert split.busy_s == red.busy_s
    assert split.spans == red.spans
    assert split.idle_gaps == red.idle_gaps
    assert split.n_devices == red.n_devices


def test_offset_bounds_from_the_flows(red, split):
    """Each program's flow id ties it to the host's ``DoEnqueueProgram``
    (no program starts before it) and ``CompleteCallbacks`` (none ends
    after it starts).  On this trace they leave 0.36 ms; the offset in use,
    the least that starts no program before its ``PJRT`` launch, lies
    0.098 ms below them: launch to enqueue takes 0.10-0.24 ms here."""
    (lo, hi), = split.offset_bounds_ns
    assert (lo, hi) == (1583348.0, 1939974.0)
    assert red.offsets_ns[0] == 1485609.0
    assert 0 < lo - red.offsets_ns[0] < 0.2e6
    assert I.split_trace(str(FIXTURE), at_bound=True).offsets_ns == [lo]


def test_offset_bounds_need_a_matched_flow():
    modules = [(100.0, 10.0, 7)]
    assert I.offset_bounds(modules, {7: 150.0}, {7: 200.0}) == (50.0, 90.0)
    assert I.offset_bounds(modules, {8: 150.0}, {7: 200.0}) is None


def _ctx(trace):
    return type("Ctx", (), {"trace": trace, "counters": {},
                            "kernel_costs": {}})()


def _readers():
    return {name: spec.load_module(B.ROOT / "bench" / "metrics"
                                   / f"{name}.py",
                                   "test_" + name.replace(".", "_"))
            for name in READERS}


@pytest.mark.parametrize("which", ["reduction", "split"])
def test_readers_read_nothing_without_their_spans(red, split, which):
    """The fixture holds no program span: neither the accepted reduction
    (no ``self_idle_s`` at all) nor the split gives these metrics."""
    ctx = _ctx(red if which == "reduction" else split)
    assert {n: r.read(ctx) for n, r in _readers().items()} == \
        dict.fromkeys(READERS)


def test_readers_over_program_spans():
    def rec(count, device_s, wall_s):
        return {"count": count, "device_s": device_s, "wall_s": wall_s}
    split = I.Split(
        window_s=2.0, busy_s=1.5, n_devices=1, offsets_ns=[0.0],
        offset_bounds_ns=[None], idle_gaps=[],
        spans={"engine.decode": rec(100, 1.0, 1.4),
               "engine.prefill": rec(4, 0.2, 0.08),
               "engine.splice": rec(4, 0.1, 0.02),
               "engine.first_token": rec(4, 0.0, 0.02),
               "tuner.decide": rec(10, 0.0, 0.01),
               "tuner.bookkeeping": rec(2, 0.0, 0.01)},
        self_idle_s={"engine.decode": 0.1, "engine.sample": 0.1,
                     "engine.step": 0.05, "tuner.decide": 0.02,
                     "tuner.update": 0.04, "tuner.kernels_of": 0.06,
                     "tuner.bookkeeping": 0.08, "bench window": 0.05})
    got = {n: r.read(_ctx(split)) for n, r in _readers().items()}
    assert got == pytest.approx({
        "idle_engine.serve": 12.5,       # (0.1 + 0.1 + 0.05) / 2
        "decode_ms.serve": 10.0,         # 1.0 s / 100 steps
        "admit_ms.serve": 30.0,          # (0.08 + 0.02 + 0.02) s / 4
        "idle_stats.tune": 3.0,          # (0.02 + 0.04) / 2
        "idle_session.tune": 7.0})       # (0.06 + 0.08) / 2
