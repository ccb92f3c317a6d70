"""Golden-output regression: the optimized engine must reproduce the seed
engine's protocol output bit-for-bit.

``tests/golden_reports.json`` was generated by the PRE-refactor (seed)
engine via ``python -m tests.golden_runner`` with a fully deterministic
cost model; every ConfigRecord field (predicted, full/wall times, executed,
skipped, per-trial predictions) must match EXACTLY — float equality, not
allclose — across all five policies and three op-mix-distinct studies
(nonblocking p2p, sub-communicator collectives, blocking p2p).
"""

import json

import numpy as np
import pytest

from repro.core.signatures import (INTERNER, Signature, comm_sig, comp_sig,
                                   intern_sig, p2p_sig, sig_of)

from golden_runner import GOLDEN_PATH, compute_goldens

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def test_reports_bit_identical_to_seed_engine():
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    # round-trip through JSON so float repr/parse treatment is identical
    got = json.loads(json.dumps(compute_goldens()))
    assert set(got) == set(golden)
    for study in golden:
        assert set(got[study]) == set(golden[study]), study
        for pol in golden[study]:
            g_recs, n_recs = golden[study][pol], got[study][pol]
            assert len(g_recs) == len(n_recs), (study, pol)
            for g, n in zip(g_recs, n_recs):
                for field in g:
                    assert n[field] == g[field], \
                        f"{study}/{pol}/{g['name']}/{field}: " \
                        f"{n[field]!r} != {g[field]!r}"


def test_trace_replay_is_deterministic():
    """The same configuration run through the fast paths (the batched cold
    interpreter, then the compiled warm replay) must behave identically
    to the scalar reference ``run_scalar`` on every iteration."""
    from repro.core.critter import Critter
    from repro.core.policies import policy
    from repro.linalg import slate_cholesky
    from repro.simmpi.comm import World
    from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2
    from repro.simmpi.runtime import Runtime

    def run_seq(scalar):
        w = World(16)
        c = Critter(w, policy("online", tolerance=0.25))
        cm = CostModel(KNL_STAMPEDE2, seed=3, bias_sigma=0.0)
        rt = Runtime(w, c, cm.sample, seed=3)
        run = rt.run_scalar if scalar else rt.run
        prog = slate_cholesky.make_program(w, n=512, tile=64, lookahead=1,
                                           pr=4, pc=4)
        out = []
        for i in range(4):
            res = run(prog, force_execute=(i == 0))
            out.append((res.predicted_time, res.wall_time, res.crit_comp,
                        res.crit_comm, res.measured_time, res.executed,
                        res.skipped))
        return out

    assert run_seq(False) == run_seq(True)


# -- interned signature ids round-trip to equal Signatures -------------------

def _assert_roundtrip(sig: Signature):
    sid = intern_sig(sig)
    assert isinstance(sid, int)
    back = sig_of(sid)
    assert back == sig
    assert hash(back) == hash(sig)
    # interning an equal signature yields the same dense id
    clone = Signature(sig.kind, sig.name, tuple(sig.params))
    assert clone is not sig
    assert intern_sig(clone) == sid
    assert sid < len(INTERNER)


def test_intern_roundtrip_examples():
    _assert_roundtrip(comp_sig("gemm", 64, 64, 64))
    _assert_roundtrip(comm_sig("allreduce", 4096, 8, 2))
    _assert_roundtrip(p2p_sig("send", 12345))
    # distinct signatures get distinct ids
    a = intern_sig(comp_sig("gemm", 64, 64, 64))
    b = intern_sig(comp_sig("gemm", 64, 64, 65))
    assert a != b


if HAVE_HYPOTHESIS:
    @given(st.sampled_from(["gemm", "trsm", "potrf", "bcast", "send"]),
           st.lists(st.integers(min_value=0, max_value=1 << 20),
                    min_size=1, max_size=4),
           st.sampled_from(["comp", "comm"]))
    @settings(max_examples=100, deadline=None)
    def test_intern_roundtrip_property(name, params, kind):
        _assert_roundtrip(Signature(kind, name, tuple(params)))
else:
    def test_intern_roundtrip_property():
        rng = np.random.default_rng(0)
        names = ["gemm", "trsm", "potrf", "bcast", "send"]
        for _ in range(100):
            kind = "comp" if rng.random() < 0.5 else "comm"
            name = names[int(rng.integers(len(names)))]
            params = tuple(int(x) for x in
                           rng.integers(0, 1 << 20,
                                        size=int(rng.integers(1, 5))))
            _assert_roundtrip(Signature(kind, name, params))
