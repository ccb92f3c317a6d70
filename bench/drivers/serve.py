"""Open-loop serving through the slot engine (``serve/engine.py``).

Traffic keys: ``slots``, ``s_max``, ``prompt_buckets``, ``temperature``,
``rate_per_s``, ``prompt_len`` and ``output_len`` (lognormal: ``median``,
``sigma``, ``min``, ``max``), ``pool_seed``, ``drain_s``,
``check_tokens``, ``trace_seconds``.

Requests arrive as a Poisson process at the fixed rate.  The sizes and
the gaps between arrivals are one pool drawn from ``pool_seed``, the same
for every run; the run's seed orders them and draws the prompts' tokens,
so every seed offers the same work.  The window admits the requests due
in ``--seconds`` and then drains them, ``drain_s`` at most; a request not
finished by then has failed.  Each request is timed from when it was due:
TTFT to the step that produced its first token, TPOT over the rest.

Correctness: a sample of the requests, drawn from the seed before the
window with the longest request in it, is watched: the harness keeps the
engine's own logits for each of their served tokens (the prefill's at the
prompt's last position, then each decode step's), copied from the rows
the engine pulls to the host to sample from.  After the window the plain
reference runs each finished request's prompt and served tokens in one
forward pass.  The numbers: the worst relative error of the engine's
logits at one position, ||engine - reference|| / ||reference||, and the
count of served tokens that are not the greedy choice of the engine's
own logits.
"""

from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from harness import data as D

class State:
    pass


def _lognormal(rng, spec, n):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic, seed, seconds):
    """[(due offset s, prompt length, output length)], due in the window:
    the pool from ``pool_seed``, ordered by the run's seed."""
    rate = traffic["rate_per_s"]
    n = int(np.ceil(rate * seconds * 1.5)) + 8
    pool = np.random.default_rng(traffic["pool_seed"])
    gaps = pool.exponential(1.0 / rate, n)
    plen = _lognormal(pool, traffic["prompt_len"], n)
    olen = _lognormal(pool, traffic["output_len"], n)
    order = np.random.default_rng(D.seed32(seed, 40))
    due = np.cumsum(order.permutation(gaps))
    perm = order.permutation(n)
    reqs = [(float(d), int(plen[i]), int(olen[i]))
            for d, i in zip(due, perm) if d < seconds]
    return reqs


def watched(reqs, seed, tokens):
    """Indices of the requests whose logits are kept: the longest, then
    others drawn from the seed, until ``tokens`` served tokens."""
    if not reqs:
        return set()
    longest = max(range(len(reqs)), key=lambda i: reqs[i][1] + reqs[i][2])
    out, served = {longest}, reqs[longest][2]
    for i in np.random.default_rng(D.seed32(seed, 43)).permutation(len(reqs)):
        if served >= tokens:
            break
        if int(i) not in out:
            out.add(int(i))
            served += reqs[i][2]
    return out


def build(cell, seed):
    from harness.spec import arch_config
    from repro.models.model import Model
    from repro.serve.engine import Engine, ServeConfig

    t = cell.traffic
    cfg = arch_config(cell.config)
    model = Model(cfg)
    params = D.lm_params(model, seed)
    sc = ServeConfig(batch_size=t["slots"], s_max=t["s_max"],
                     temperature=t["temperature"],
                     prompt_buckets=tuple(t["prompt_buckets"]),
                     seed=D.seed32(seed, 41))
    return model, Engine(model, params, sc)


def setup(cell, rec, seed):
    from repro.serve.engine import Request

    st = State()
    st.cell, st.rec, st.seed = cell, rec, seed
    st.model, st.engine = build(cell, seed)
    st.rng = np.random.default_rng(D.seed32(seed, 42))
    st.vocab = st.model.cfg.vocab
    st.uid = 0
    # every prompt bucket and the decode step, once each
    for b in cell.traffic["prompt_buckets"]:
        st.engine.submit(Request(uid=_next(st), tokens=_prompt(st, b),
                                 max_new_tokens=2))
    st.engine.run()
    st.served, st.rows, st.admitting = {}, {}, deque()
    _watch(st)
    return st


def _watch(st):
    """Keep the engine's logits for the watched requests: each prefill's
    (the k-th prefill of a step admits the k-th request of the queue) and
    each decode step's, by slot.  The engine pulls these logits to the
    host right after the call, so a call that has a watched row hands it
    the host copy it took, and the rows cross once."""
    eng = st.engine
    decode = eng._decode

    def watched_decode(*args):
        logits, cache = decode(*args)
        slots = [s for s in np.nonzero(eng.active)[0]
                 if int(eng.slot_uid[s]) in st.rows]
        if slots:
            logits = np.asarray(logits)
            for s in slots:
                st.rows[int(eng.slot_uid[s])].append(
                    logits[s].reshape(-1).copy())
        return logits, cache
    eng._decode = watched_decode

    def watched_prefill(fn):
        def prefill(*args):
            logits, *rest = fn(*args)
            uid = st.admitting.popleft()
            if uid in st.rows:
                logits = np.asarray(logits)
                st.rows[uid].append(logits[0].reshape(-1).copy())
            return (logits, *rest)
        return prefill
    for n, fn in list(eng._prefill_cache.items()):
        eng._prefill_cache[n] = watched_prefill(fn)


def _next(st):
    st.uid += 1
    return st.uid - 1


def _prompt(st, n):
    return st.rng.integers(0, st.vocab, n).astype(np.int32)


def window(st, seconds):
    from repro.serve.engine import Request

    t = st.cell.traffic
    eng, rec = st.engine, st.rec
    reqs = schedule(t, st.seed, seconds)
    watch = watched(reqs, st.seed, t["check_tokens"])
    pending = deque(enumerate(reqs))
    n_due = len(pending)
    live = {}          # uid -> [due, prompt, target, first, last]
    done = []
    steps, step_s = 0, 0.0
    lateness = 0.0
    t0 = time.perf_counter()
    stop = t0 + seconds + t["drain_s"]
    while True:
        now = time.perf_counter()
        while pending and t0 + pending[0][1][0] <= now:
            i, (due, plen, olen) = pending.popleft()
            uid = _next(st)
            prompt = _prompt(st, plen)
            lateness = max(lateness, now - (t0 + due))
            eng.submit(Request(uid=uid, tokens=prompt, max_new_tokens=olen))
            live[uid] = [t0 + due, prompt, olen, None, None]
            if i in watch:
                st.rows[uid] = []
        if eng.queue or eng.active.any():
            st.admitting.extend(r.uid for r in eng.queue)
            with rec.span("engine.step"):
                eng.step()
            steps += 1
            step_s += time.perf_counter() - now
            st.admitting.clear()
            now = time.perf_counter()
            for uid in list(live):
                r = live[uid]
                n = len(eng.results[uid].tokens)
                if n and r[3] is None:
                    r[3] = now
                if n >= r[2]:
                    r[4] = now
                    done.append((uid, r))
                    del live[uid]
        elif pending:
            time.sleep(max(0.0, min(t0 + pending[0][1][0], stop) - now))
        if (not pending and not live) or now >= stop:
            break
    elapsed = time.perf_counter() - t0
    ttft = [r[3] - r[0] for _, r in done]
    tpot = [(r[4] - r[3]) / max(r[2] - 1, 1) for _, r in done]
    missing = n_due - len(done)
    # a request not finished by the drain's end counts with all it waited
    waited = [stop - r[0] for r in live.values()] + \
        [stop - t0 - p[0] for _, p in pending]
    st.served = {uid: (r[1], list(eng.results[uid].tokens[:r[2]]))
                 for uid, r in done}
    st.timings = [(r[0] - t0, r[3] - r[0], r[4] - r[3], r[2])
                  for _, r in done]
    rec.counters["engine_steps"] += steps
    rec.counters["engine_step_s"] += step_s
    rec.counters["requests"] += len(done)
    return {"metrics": {"ttft_p95_ms": 1e3 * _p95(ttft + waited),
                        "tpot_p95_ms": 1e3 * _p95(tpot + waited)},
            "attempted": n_due, "failed": missing, "elapsed_s": elapsed,
            "info": {"requests_done": len(done),
                     "ttft_p50_ms": 1e3 * float(np.median(ttft))
                     if ttft else None,
                     "generator_late_ms": 1e3 * lateness,
                     "engine_steps": steps}}


def _p95(values):
    """95th percentile, the nearest rank."""
    vals = sorted(values)
    k = int(np.ceil(0.95 * len(vals))) - 1
    return float(vals[max(k, 0)])


def release(st):
    st.engine = None
    gc.collect()


def reference_logits(st, prompt, served, dtype):
    import jax.numpy as jnp
    ref = st.cell.reference()
    if not hasattr(st, "ref_params"):
        st.ref_params = D.lm_params(st.model, st.seed)
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    lg = ref.lm_logits(st.ref_params, jnp.asarray(seq)[None], st.cell.config,
                       dtype=dtype)[0]
    return lg[len(prompt) - 1:]          # the positions that chose `served`


def check(st, produce=None):
    """The worst relative error of the engine's logits at one position,
    and the served tokens that are not their greedy choice, over the
    watched requests that finished.  ``produce(prompt, served)`` stands in
    for the engine's logits (the control, a planted fault)."""
    import jax.numpy as jnp
    worst, not_greedy = 0.0, 0
    for uid in sorted(set(st.rows) & set(st.served)):
        prompt, served = st.served[uid]
        ref = np.asarray(reference_logits(st, prompt, served, jnp.float32))
        got = np.asarray(produce(prompt, served)) if produce else \
            np.stack(st.rows[uid]) if st.rows[uid] else np.zeros((0,))
        if got.shape != ref.shape:
            worst = np.inf
            continue
        err = np.linalg.norm(got - ref, axis=-1) / \
            np.linalg.norm(ref, axis=-1)
        worst = max(worst, float(err.max()))
        not_greedy += int(np.sum(got.argmax(axis=-1) != np.asarray(served)))
    return {"logit_rel_err": worst, "tokens_not_greedy": float(not_greedy)}


def control(st):
    """The reference with fp8 products (the precision below the
    configuration's), at each position of the same prompts and served
    tokens."""
    fp8 = st.cell.reference().FP8

    def produce(prompt, served):
        return reference_logits(st, prompt, served, fp8)
    return produce
