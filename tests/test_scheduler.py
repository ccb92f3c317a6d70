"""repro.api.scheduler contract tests.

- executor equivalence: serial (in-process), fork-pool, and localhost
  remote-worker sweeps produce identical results (same study, same
  winner, bit-identical records);
- deterministic sharing: a ``share_stats=True, deterministic=True`` fork
  sweep is bit-identical to the serial PR-2 golden sweep (and to the
  golden reports themselves);
- mid-sweep sharing: later-dispatched sweep points warm-start from
  earlier completions' banks (strictly fewer executed kernels, same
  winner) and the shared prior survives kill-and-resume through the
  checkpoint;
- the scheduler drives racing sweeps end-to-end;
- task lifecycle: explicit pending/running/done/failed states, failure
  propagation as ``SchedulerError``;
- fault tolerance: transient failures retried with attempt history and
  exponential backoff, ``on_failure="skip"`` partial sweeps, heartbeat
  and task-deadline liveness against protocol-stub workers, elastic
  mid-sweep worker join, worker survival across abrupt disconnects.
"""

import json
import os
import re
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from repro.api import (AutotuneSession, RemoteExecutor, Scheduler,
                       SchedulerError, SimBackend, StatisticsBank)
from repro.api.scheduler import (DONE, FAILED, ForkExecutor,
                                 InProcessExecutor, fork_available)
from repro.core.policies import POLICIES
from repro.simmpi.costmodel import CostModel, KNL_STAMPEDE2

from golden_runner import GOLDEN_PATH, _studies, golden_space

GOLDEN_FIELDS = ("full_time", "predicted", "rel_error", "comp_error",
                 "selective_cost", "full_cost", "executed", "skipped",
                 "predictions")


def _golden_backend():
    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0, bias_sigma=0.0)
    return SimBackend(timer=cm.sample)


def _capital_session(backend=None, **kw):
    return AutotuneSession(_studies()[1],
                           backend=backend or _golden_backend(),
                           trials=2, **kw)


def _strip(result) -> dict:
    d = result.to_json()
    d.pop("wall_s", None)
    # recovery provenance is infrastructure history, not measurement
    d.get("extra", {}).pop("recovery", None)
    # program-cache counters are worker-configuration provenance (a remote
    # worker defaults to a warm cache, the serial driver runs without one);
    # replay is bit-identical, so measurements must still compare equal
    d.get("extra", {}).pop("program_cache", None)
    return d


# -- scheduler core ------------------------------------------------------------

def test_task_lifecycle_and_order():
    seen = []
    done_order = []

    def runner(payload):
        seen.append(payload)
        return {"value": payload * 10}

    tasks = Scheduler(InProcessExecutor(), runner).run(
        [3, 1, 2], on_done=lambda t: done_order.append(t.index))
    assert [t.state for t in tasks] == [DONE] * 3
    assert [t.result for t in tasks] == [{"value": 30}, {"value": 10},
                                         {"value": 20}]
    assert seen == [3, 1, 2]            # submission order == spec order
    assert done_order == [0, 1, 2]      # serial: completion == submission


def test_prepare_hook_late_binds_payloads():
    """Payloads are built at dispatch time, after earlier completions —
    the property mid-sweep statistics sharing rests on."""
    finished = []

    def prepare(task):
        return {"spec": task.spec, "seen": list(finished)}

    def runner(payload):
        finished.append(payload["spec"])
        return payload

    tasks = Scheduler(InProcessExecutor(), runner).run(
        ["a", "b", "c"], prepare=prepare)
    assert tasks[0].result["seen"] == []
    assert tasks[1].result["seen"] == ["a"]
    assert tasks[2].result["seen"] == ["a", "b"]


def test_failed_task_raises_with_state():
    def runner(payload):
        if payload == 1:
            raise ValueError("boom")
        return {"ok": payload}

    sched = Scheduler(InProcessExecutor(), runner)
    with pytest.raises(SchedulerError, match="boom") as ei:
        sched.run([0, 1, 2])
    assert ei.value.task.state == FAILED
    assert ei.value.task.index == 1
    assert "ValueError" in ei.value.task.error


@pytest.mark.skipif(not fork_available(), reason="no os.fork")
def test_fork_executor_matches_in_process():
    def runner(payload):
        return {"square": payload * payload}

    serial = Scheduler(InProcessExecutor(), runner).run(list(range(5)))
    forked = Scheduler(ForkExecutor(2), runner).run(list(range(5)))
    assert [t.result for t in serial] == [t.result for t in forked]
    assert all(t.state == DONE for t in forked)


def test_scheduler_raises_when_capacity_exhausted():
    """Losing every worker mid-sweep (RemoteExecutor shrinks capacity as
    workers drop) must raise, not return with tasks silently pending."""
    from repro.api.scheduler import Executor

    class _DyingExecutor(Executor):
        capacity = 1

        def start(self, runner):
            self._runner = runner

        def submit(self, index, payload):
            self._pending = (index, {"ok": self._runner(payload)})

        def poll(self):
            out = [self._pending]
            self.capacity = 0            # the only worker died while idle
            return out

    with pytest.raises(SchedulerError, match="no capacity"):
        Scheduler(_DyingExecutor(), lambda p: {"v": p}).run([1, 2, 3])


# -- retries and failure policy ------------------------------------------------

def test_retry_recovers_transient_failure():
    calls = {"n": 0}

    def runner(payload):
        if payload == 1:
            calls["n"] += 1
            if calls["n"] < 3:
                raise ValueError(f"flaky {calls['n']}")
        return {"v": payload}

    events = []
    tasks = Scheduler(InProcessExecutor(), runner, max_retries=2,
                      on_event=events.append).run([0, 1, 2])
    assert [t.state for t in tasks] == [DONE] * 3
    t = tasks[1]
    assert t.result == {"v": 1}
    assert t.meta["retries"] == 2
    assert [a["attempt"] for a in t.attempts] == [1, 2]
    assert "flaky 1" in t.attempts[0]["error"]
    assert t.attempts[0]["worker"] == "in-process"
    retries = [e for e in events if e["event"] == "task_retry"]
    assert [e["task"] for e in retries] == [1, 1]
    # tasks that never failed carry no history
    assert tasks[0].attempts == [] and "retries" not in tasks[0].meta


def test_retries_exhausted_raises_with_history():
    def runner(payload):
        raise ValueError("always boom")

    with pytest.raises(SchedulerError,
                       match=r"failed after 3 attempt") as ei:
        Scheduler(InProcessExecutor(), runner, max_retries=2).run([7])
    t = ei.value.task
    assert t.state == FAILED
    assert len(t.attempts) == 3
    msg = str(ei.value)
    assert "attempt 2 on in-process" in msg
    assert "always boom" in msg          # the last traceback rides along


def test_on_failure_skip_completes_rest_of_grid():
    def runner(payload):
        if payload == "bad":
            raise RuntimeError("persistent")
        return {"v": payload}

    events = []
    tasks = Scheduler(InProcessExecutor(), runner, max_retries=1,
                      on_failure="skip",
                      on_event=events.append).run(["a", "bad", "b"])
    assert [t.state for t in tasks] == [DONE, FAILED, DONE]
    assert tasks[1].result is None
    assert len(tasks[1].attempts) == 2
    assert tasks[0].result == {"v": "a"} and tasks[2].result == {"v": "b"}
    assert any(e["event"] == "task_failed" for e in events)


def test_interrupts_are_not_retried():
    """Ctrl-C must stop the sweep, not masquerade as a flaky task."""
    def runner(payload):
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        Scheduler(InProcessExecutor(), runner, max_retries=5).run([1])


def test_retry_backoff_is_exponential():
    def runner(payload):
        raise ValueError("nope")

    events = []
    with pytest.raises(SchedulerError):
        Scheduler(InProcessExecutor(), runner, max_retries=2,
                  retry_backoff=0.05, on_event=events.append).run([0])
    delays = [e["delay_s"] for e in events if e["event"] == "task_retry"]
    assert delays == [0.05, 0.1]


def test_invalid_on_failure_rejected():
    with pytest.raises(ValueError, match="on_failure"):
        Scheduler(InProcessExecutor(), on_failure="explode")


# -- executor equivalence on real sweeps ---------------------------------------

def test_serial_vs_fork_vs_remote_same_results(tmp_path):
    """The acceptance smoke: the same sweep through all three executors
    lands on identical results (the sim backend is seeded-deterministic
    across processes and machines)."""
    assert fork_available(), "fork executor cannot be exercised here"
    space = golden_space(1)

    def sess():
        # default SimBackend: the remote worker builds the same one
        return AutotuneSession(space, backend=SimBackend(), trials=2)

    kw = dict(policies=["conditional", "eager"], tolerances=[0.25])
    serial = [_strip(r) for r in sess().sweep(workers=1, **kw)]
    forked = [_strip(r) for r in sess().sweep(workers=2, **kw)]
    assert forked == serial

    with _worker(1) as addr:
        ex = RemoteExecutor([addr], expect={"space": space.name,
                                            "n_points": len(space)})
        remote = [_strip(r) for r in sess().sweep(executor=ex, **kw)]
    assert remote == serial
    winners = {json.dumps(r["records"], sort_keys=True) for r in serial}
    assert len(winners) <= len(serial)   # sanity: records present
    for r in serial:
        assert len(r["records"]) == len(space)


class _worker:
    """Launch ``python -m repro.api.worker`` serving the tiny golden
    Capital space — listening on an ephemeral localhost port, or dialing
    a listening executor (``connect=``, elastic-join mode)."""

    def __init__(self, index: int, once: bool = True,
                 connect: str = None):
        self.index = index
        self.once = once
        self.connect = connect
        self.proc = None

    def __enter__(self) -> str:
        here = os.path.dirname(__file__)
        src = os.path.abspath(os.path.join(here, os.pardir, "src"))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [src, here] + env.get("PYTHONPATH", "").split(os.pathsep))
        cmd = [sys.executable, "-m", "repro.api.worker",
               "--spec", "golden_runner:golden_space",
               "--spec-args", json.dumps({"index": self.index})]
        if self.connect:
            cmd += ["--connect", self.connect]
        else:
            cmd += ["--port", "0"]
            if self.once:
                cmd += ["--once"]
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)
        line = self.proc.stdout.readline()
        m = re.match(r"WORKER_READY (\S+) (\S+)", line)
        assert m, (f"worker failed to start: {line!r}\n"
                   f"{self.proc.stderr.read()}")
        if m.group(1) == "connect":
            return m.group(2)
        return f"{m.group(1)}:{m.group(2)}"

    def __exit__(self, *exc):
        self.proc.terminate()
        self.proc.wait(timeout=30)


def test_remote_worker_rejects_wrong_spec():
    with _worker(0) as addr:                    # serves golden-slate
        ex = RemoteExecutor([addr], expect={"space": "golden-capital"})
        with pytest.raises(SchedulerError, match="golden-slate"):
            ex.start(None)


def test_worker_answers_ping():
    """The ``{"op": "ping"}`` liveness heartbeat of the worker protocol."""
    with _worker(1) as addr:
        host, port = addr.rsplit(":", 1)
        with socket.create_connection((host, int(port)), timeout=10) as s:
            s.sendall(b'{"op": "ping"}\n{"op": "shutdown"}\n')
            f = s.makefile("rb")
            assert json.loads(f.readline()) == {"ok": "pong"}
            assert json.loads(f.readline()) == {"ok": "bye"}


def test_worker_survives_abrupt_disconnect():
    """A scheduler that vanishes mid-session (RST, not FIN) costs one
    connection, not the worker — the next scheduler connects fine."""
    with _worker(1, once=False) as addr:
        host, port = addr.rsplit(":", 1)
        s1 = socket.create_connection((host, int(port)), timeout=10)
        s1.sendall(b'{"op": "hello"}\n')
        assert b'"ok"' in s1.makefile("rb").readline()
        s1.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                      struct.pack("ii", 1, 0))
        s1.close()                                   # hard reset
        with socket.create_connection((host, int(port)), timeout=10) as s2:
            s2.sendall(b'{"op": "ping"}\n{"op": "shutdown"}\n')
            f = s2.makefile("rb")
            assert json.loads(f.readline()) == {"ok": "pong"}
            assert json.loads(f.readline()) == {"ok": "bye"}


def test_elastic_worker_joins_listening_executor():
    """``RemoteExecutor(listen=...)`` starts with zero workers; a
    ``--connect`` worker dials in mid-sweep and supplies the capacity."""
    space = golden_space(1)
    ex = RemoteExecutor(listen=0, join_timeout=30,
                        expect={"space": space.name})
    sess = AutotuneSession(space, backend=SimBackend(), trials=2)
    kw = dict(policies=["eager"], tolerances=[0.25])
    with _worker(1, connect=ex.listen_address):
        got = [_strip(r) for r in sess.sweep(executor=ex, **kw)]
    serial = [_strip(r) for r in AutotuneSession(
        space, backend=SimBackend(), trials=2).sweep(workers=1, **kw)]
    assert got == serial
    assert any(e["event"] == "worker_joined"
               for e in sess.last_sweep_events)


# -- liveness against protocol stubs -------------------------------------------

class _stub_worker:
    """Protocol-level stub for liveness tests: answers ``hello``, never
    answers ``ping``; ``run`` requests are echoed (``echo``) or silently
    swallowed (``wedge`` — alive but stuck)."""

    def __init__(self, mode: str = "echo"):
        self.mode = mode
        self.srv = socket.create_server(("127.0.0.1", 0))
        h, p = self.srv.getsockname()[:2]
        self.addr = f"{h}:{p}"
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        try:
            conn, _ = self.srv.accept()
        except OSError:
            return
        buf = bytearray()
        with conn:
            while True:
                try:
                    chunk = conn.recv(1 << 16)
                except OSError:
                    return
                if not chunk:
                    return
                buf += chunk
                while b"\n" in buf:
                    line, _, rest = bytes(buf).partition(b"\n")
                    buf[:] = rest
                    msg = json.loads(line)
                    op = msg.get("op")
                    if op == "hello":
                        conn.sendall(json.dumps(
                            {"ok": {"space": "stub", "n_points": 1,
                                    "backend": {}}}).encode() + b"\n")
                    elif op == "run" and self.mode == "echo":
                        conn.sendall(json.dumps(
                            {"id": msg["id"],
                             "ok": {"v": msg["task"]}}).encode() + b"\n")
                    # pings and wedged runs: no reply, ever

    def close(self):
        self.srv.close()


def test_heartbeat_drops_silent_idle_worker():
    """An idle worker that stops answering pings is dropped before a
    task is wasted on it."""
    w = _stub_worker()
    ex = RemoteExecutor([w.addr], heartbeat_interval=0.1)
    try:
        ex.start(None)
        assert ex.capacity == 1
        t0 = time.monotonic()
        ex._check_heartbeats(t0 + 0.2)      # idle past interval: ping out
        st, = ex._workers.values()
        assert st["ping_sent"] is not None
        ex._check_heartbeats(t0 + 0.4)      # unanswered a full interval
        assert ex.capacity == 0
        assert any(e["event"] == "heartbeat_timeout"
                   for e in ex.drain_events())
    finally:
        ex.close()
        w.close()


def test_task_deadline_reassigns_wedged_worker_task():
    """A wedged worker (socket open, no reply) trips the per-task
    deadline; its task is reassigned and the sweep completes — without
    the deadline, ``poll`` would block forever."""
    wedge, good = _stub_worker("wedge"), _stub_worker("echo")
    events = []
    ex = RemoteExecutor([wedge.addr, good.addr], task_timeout=0.5)
    try:
        tasks = Scheduler(ex, None, max_retries=1,
                          on_event=events.append).run([10, 20])
        assert [t.state for t in tasks] == [DONE, DONE]
        assert [t.result for t in tasks] == [{"v": 10}, {"v": 20}]
        retried, = [t for t in tasks if t.attempts]
        assert retried.meta["retries"] == 1
        assert "task deadline" in retried.attempts[0]["error"]
        names = {e["event"] for e in events}
        assert "task_deadline" in names and "task_retry" in names
    finally:
        wedge.close()
        good.close()


def test_remote_worker_task_error_propagates():
    space = golden_space(1)
    with _worker(1) as addr:
        session = AutotuneSession(space, backend=SimBackend(),
                                  search="racing", trials=1,
                                  search_options={"max_rounds": 0,
                                                  "bogus_option": True})
        with pytest.raises(SchedulerError, match="bogus_option"):
            session.sweep(executor=RemoteExecutor([addr]),
                          policies=["online"], tolerances=[0.25])


# -- deterministic sharing: golden parity --------------------------------------

@pytest.mark.skipif(not fork_available(), reason="no os.fork")
def test_deterministic_fork_share_sweep_matches_golden():
    """share_stats=True, deterministic=True with no checkpoint bank defers
    all sharing: a 2-worker fork sweep must be bit-identical to the serial
    driver AND to the PR-2 golden records."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)
    study = _studies()[1]
    kw = dict(policies=list(POLICIES), tolerances=[0.25])
    serial = _capital_session().sweep(workers=1, **kw)
    det = _capital_session().sweep(workers=2, share_stats=True,
                                   deterministic=True, **kw)
    assert [_strip(r) for r in det] == [_strip(r) for r in serial]
    for res in det:
        g_recs = golden[study.name][res.policy]
        got = json.loads(json.dumps([r.to_json() for r in res.records]))
        assert len(got) == len(g_recs)
        for g, n in zip(g_recs, got):
            assert n["name"] == g["name"]
            for field in GOLDEN_FIELDS:
                assert n[field] == g[field], \
                    f"{res.policy}/{g['name']}/{field}"


# -- mid-sweep statistics sharing ----------------------------------------------

def test_live_sharing_warm_starts_later_points():
    kw = dict(policies=["eager"], tolerances=[1.0, 0.25, 0.0625])
    cold = _capital_session().sweep(workers=1, **kw)
    live = _capital_session().sweep(workers=1, share_stats=True, **kw)
    cold_exec = [sum(r.executed for r in res.records) for res in cold]
    live_exec = [sum(r.executed for r in res.records) for res in live]
    # the first point dispatches with no completions: identical to cold
    assert _strip(live[0]) == _strip(cold[0])
    # later points ride the shared prior: strictly fewer executions,
    # same winners
    assert sum(live_exec[1:]) < sum(cold_exec[1:])
    for c, l in zip(cold, live):
        assert l.chosen.name == c.chosen.name
    # sharing is scheduling-state, not result payload: no bank attached
    assert all("kernel_stats" not in res.extra for res in live)


def test_shared_results_never_replay_as_cold(tmp_path):
    ck = str(tmp_path / "ck.json")
    kw = dict(policies=["eager"], tolerances=[1.0, 0.25])
    shared = _capital_session().sweep(workers=1, share_stats=True,
                                      checkpoint=ck, **kw)
    # a cold sweep over the same grid must NOT reuse the shared journal
    cold = _capital_session().sweep(workers=1, checkpoint=ck, **kw)
    fresh = _capital_session().sweep(workers=1, **kw)
    for c, f in zip(cold, fresh):
        assert _strip(c) == _strip(f)
    # while a repeated shared sweep DOES reuse it (wall_s included)
    again = _capital_session().sweep(workers=1, share_stats=True,
                                     checkpoint=ck, **kw)
    assert [r.to_json() for r in again] == [r.to_json() for r in shared]


class _FailNthOpen(SimBackend):
    """Fails the N-th ``open`` (0-indexed) once — kills sweep task N."""

    def __init__(self, fail_at: int, **kw):
        super().__init__(**kw)
        self.fail_at = fail_at
        self.opens = 0

    def open(self, *a, **kw):
        n = self.opens
        self.opens += 1
        if n == self.fail_at:
            raise RuntimeError("killed mid-sweep")
        return super().open(*a, **kw)


def test_kill_and_resume_restores_shared_prior(tmp_path):
    """A share_stats sweep killed mid-run resumes with the shared prior
    rebuilt from the checkpoint: the resumed run is bit-identical to an
    uninterrupted one (serial dispatch order makes the shared priors
    deterministic)."""
    from repro.api.session import _Checkpoint
    ck = str(tmp_path / "shared.json")
    kw = dict(policies=["eager"], tolerances=[1.0, 0.25, 0.0625])

    uninterrupted = _capital_session().sweep(workers=1, share_stats=True,
                                             **kw)

    cm = CostModel(KNL_STAMPEDE2, allocation=0, seed=0, bias_sigma=0.0)
    failing = _FailNthOpen(2, timer=cm.sample)
    with pytest.raises(SchedulerError, match="killed mid-sweep"):
        _capital_session(backend=failing).sweep(
            workers=1, share_stats=True, checkpoint=ck, **kw)

    # the checkpoint holds the first two points' results AND their
    # accumulated shared bank
    journal = _Checkpoint(ck)
    bank = journal.shared_bank()
    assert bank is not None and len(bank) > 0
    assert len(journal._data["results"]) == 2

    resumed = _capital_session().sweep(workers=1, share_stats=True,
                                       checkpoint=ck, **kw)
    assert [_strip(r) for r in resumed] == \
        [_strip(r) for r in uninterrupted]
    # the resumed third point really ran warm (not cold)
    cold = _capital_session().sweep(workers=1, policies=["eager"],
                                    tolerances=[0.0625])
    assert sum(r.executed for r in resumed[2].records) < \
        sum(r.executed for r in cold[0].records)


# -- racing through the scheduler ----------------------------------------------

def test_scheduler_drives_racing_sweeps():
    session = AutotuneSession(_studies()[1],
                              backend=_golden_backend(), search="racing",
                              trials=1, search_options={"max_rounds": 3})
    kw = dict(policies=["online", "conditional"], tolerances=[0.25])
    serial = session.sweep(workers=1, **kw)
    names = {p.name for p in session.space.points}
    assert len(serial) == 2
    for r in serial:
        assert r.search == "racing"
        assert r.extra["best"] in names
    if fork_available():
        forked = AutotuneSession(
            _studies()[1], backend=_golden_backend(),
            search="racing", trials=1,
            search_options={"max_rounds": 3}).sweep(workers=2, **kw)
        assert [_strip(r) for r in forked] == [_strip(r) for r in serial]


# -- run_tasks: the plain fork pool ------------------------------------------

def test_run_tasks_shim_preserves_contract():
    from repro.api.scheduler import run_tasks
    landed = []
    out = run_tasks([1, 2, 3], lambda t: {"t": t}, workers=2,
                    on_result=lambda i, r: landed.append((i, r)))
    assert out == [{"t": 1}, {"t": 2}, {"t": 3}]
    assert sorted(landed) == [(0, {"t": 1}), (1, {"t": 2}), (2, {"t": 3})]
