"""The trace reduction (bench/harness/trace.py) on a small trace recorded
on a v5e by bench/tools/record_trace_fixture.py: three rounds of a
matmul kernel span, a 2 ms host-only ``skip bookkeeping`` span and an
elementwise kernel span, inside ``bench window``."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny  # noqa: E402,F401

from harness import trace as T  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return T.reduce_trace(str(FIXTURE))


def test_window_and_busy(red):
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.01305727, rel=1e-6)
    # the device ran 3 x (116 us matmul program + 24 us reduction)
    assert red.busy_s == pytest.approx(4.20201e-4, rel=1e-4)
    assert 0.9 < red.idle_share < 1.0


def test_device_clock_offset(red):
    # the v5e's device clock trails the host's by about 1.5 ms here
    assert 1.0e6 < red.offsets_ns[0] < 2.0e6


def test_device_time_lands_in_its_kernel_spans(red):
    mm, ew = red.spans["kernel mm(2048)"], red.spans["kernel ew(2048)"]
    sk = red.spans["skip bookkeeping"]
    assert mm["count"] == ew["count"] == sk["count"] == 3
    assert sk["device_s"] == 0.0
    assert mm["device_s"] + ew["device_s"] == pytest.approx(red.busy_s)
    assert mm["device_s"] == pytest.approx(3.46949e-4, rel=1e-4)
    assert mm["wall_s"] > mm["device_s"]


def test_longest_gaps_are_the_host_sleeps(red):
    names = [n for n, _ in red.idle_gaps[:3]]
    assert names == ["skip bookkeeping"] * 3
    secs = [s for _, s in red.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert len(red.idle_gaps) <= 10


def test_top_ops_by_self_time(red):
    top = dict(red.top_ops)
    assert list(top)[0] == "convolution_tanh_fusion"
    # the programs' ops run one after another: self times add to busy
    assert sum(top.values()) == pytest.approx(red.busy_s, rel=1e-6)
    assert all(v >= 0 for v in top.values())


def test_union_and_self_time_helpers():
    us, ue = T._union([0, 5, 1, 20], [2, 6, 4, 30])
    assert list(us) == [0, 5, 20] and list(ue) == [4, 6, 30]
    b = T._Busy(us, ue)
    assert float(b.between(np.asarray(3.0), np.asarray(25.0))) == 7.0
    ops = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 5.0, 2.0),
           ("c", 12.0, 1.0)]
    assert T._self_times(ops) == [5.0, 3.0, 2.0, 1.0]
    assert T.short_op_name("%fusion.12 = f32[8] fusion(...)") == "fusion"
