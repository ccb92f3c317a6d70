"""Tuning campaigns back to back: the paper's protocol on the chip.

Traffic keys: ``batch``, ``seq`` (the step the campaign tunes), ``policy``,
``tolerance``, ``trials``, ``trace_seconds``.  A campaign is a fresh
``AutotuneSession`` (fresh statistics) over ``LMStudy.search_space()``
with ``WallClockBackend`` bound to ``LMStudy.kernels_of``; the harness
wraps that provider to count kernel executions and to name each kernel's
host span.  The window closes at the first configuration boundary after
``--seconds``: every configuration counted is whole, and the window's time
is all of their time.

Correctness, after the window: the tuner's answers, that is each
configuration's predicted time against the full-execution reference time
the same campaign measured, and each campaign's winner (the least
predicted time, as ``StudyResult.chosen`` picks it) against its fastest
configuration by that reference; the timed kernels (the compiled programs
the window drove, at its shapes), run again on inputs the benchmark makes
from the seed and compared with the plain reference, one kernel of each
kind drawn from the seed; and every configuration's reference pass must
have executed every kernel of its step.  A cell compares the answers'
numbers its limits name; the others are printed.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from harness import data as D
from harness import flops as F

KINDS = ("attn_fb", "dense_fb", "embed_loss_fb", "adamw")
ANSWERS = ("pred_err_max", "pred_err_median", "winner_loss_max",
           "winner_loss_median")
#: the AdamW kernel's step count in the check, past every mix's warm-up,
#: so the update moves at the full rate
ADAMW_STEP = 100


class WindowClosed(Exception):
    pass


def kernel_cost(sig, cfg):
    """(FLOPs, bytes) of one tuner kernel from its signature."""
    name, p = sig.name, sig.params
    if name == "attn_fb":
        mb, s = p[0], p[1]
        return F.attn_fb(mb, s, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim)
    if name == "dense_fb":
        return F.dense_fb(p[0], p[1], cfg.d_model, cfg.d_ff)
    if name == "embed_loss_fb":
        return F.embed_loss_fb(p[0], p[1], cfg.d_model, cfg.vocab,
                               cfg.tie_embeddings)
    if name == "adamw":
        return F.adamw(p[0])
    return None


class State:
    pass


def setup(cell, rec, seed):
    from harness.spec import arch_config
    from repro.tune.lm_study import LMStudy

    t = cell.traffic
    st = State()
    st.cell, st.rec, st.seed = cell, rec, seed
    st.cfg = arch_config(cell.config)
    st.study = LMStudy(st.cfg.name, reduced=False, batch=t["batch"],
                       seq=t["seq"], seed=D.seed32(seed, 1))
    st.space = st.study.search_space()
    for point in st.space.points:          # compiles every signature
        st.study.kernels_of(point)
    for sig in st.study._fns:
        cost = kernel_cost(sig, st.cfg)
        if cost is not None:
            rec.kernel_costs[f"kernel {sig}"] = cost
    st.ref_missing = 0
    st.answers = []
    return st


def _provider(st):
    """``kernels_of`` as the harness sees it: count, span, close."""
    rec, study, trials = st.rec, st.study, st.cell.traffic["trials"]
    calls = {}
    last = {"n": 0, "exec": 0, "ref": False}

    def finish():
        if last["ref"]:
            st.ref_missing += last["n"] - last["exec"]
        last.update(n=0, exec=0, ref=False)

    def provider(point):
        k = calls.get(point.name, 0)
        starts_config = k % (1 + trials) == 0
        finish()
        if starts_config:
            if calls:                     # the configuration before is whole
                st.done += 1
            if time.perf_counter() >= st.deadline:
                raise WindowClosed
        calls[point.name] = k + 1
        rec.switch("reference" if starts_config else "skip bookkeeping")
        items = study.kernels_of(point)
        last.update(n=len(items), exec=0, ref=starts_config)

        def wrap(sig, thunk):
            name = f"kernel {sig}"

            def run():
                last["exec"] += 1
                rec.counters["kernel_exec"] += 1
                with rec.span(name):
                    thunk()
            return run
        return [(sig, wrap(sig, th), freq) for sig, th, freq in items]
    provider.finish = finish
    return provider


def _watched(backend, st):
    """The backend as the harness sees it: each configuration's reference
    time and its last trial's prediction, by campaign, in ``st.answers``."""
    open_run = backend.open

    def open_(*args, **kw):
        run = open_run(*args, **kw)
        rows = []
        st.answers.append(rows)
        reference, trial = run.run_reference, run.run_trial

        def run_reference(point):
            m = reference(point)
            rows.append([m.time, math.nan])
            return m

        def run_trial(point):
            m = trial(point)
            rows[-1][1] = m.predicted
            return m
        run.run_reference, run.run_trial = run_reference, run_trial
        return run
    backend.open = open_
    return backend


def window(st, seconds):
    from repro.api import AutotuneSession, WallClockBackend

    t = st.cell.traffic
    st.done, st.whole, st.answers = 0, 0, []
    t0 = time.perf_counter()
    st.deadline = t0 + seconds
    while True:
        provider = _provider(st)
        session = AutotuneSession(
            st.space, backend=_watched(WallClockBackend(provider), st),
            policy=t["policy"], tolerance=t["tolerance"], trials=t["trials"])
        try:
            with st.rec.span("campaign"):
                session.run()
        except WindowClosed:
            st.rec.switch(None)
            break
        provider.finish()
        st.rec.switch(None)
        st.done += 1
        st.whole += 1
        if time.perf_counter() >= st.deadline:
            break
    elapsed = time.perf_counter() - t0
    st.rec.counters["configs"] += st.done
    st.answers = [rows for rows in st.answers if rows]
    bad = sum(1 for rows in st.answers for _, p in rows
              if not (np.isfinite(p) and p > 0))
    return {"metrics": {"tune_s_per_config": elapsed / max(st.done, 1)},
            "attempted": st.done, "failed": bad, "elapsed_s": elapsed,
            "info": {"campaigns_whole": st.whole}}


def answer_numbers(answers):
    """The tuner's answers against the reference the same campaign timed:
    the worst and the median configuration's |predicted - reference| /
    reference, and the worst and the median campaign's winner loss,
    1 - (fastest reference time) / (the winner's reference time).  A
    campaign the window cut is judged over the configurations it
    completed."""
    err = [abs(p - f) / f for rows in answers for f, p in rows]
    loss = []
    for rows in answers:
        full = [f for f, _ in rows]
        chosen = min(range(len(rows)), key=lambda i: rows[i][1])
        loss.append(1.0 - min(full) / full[chosen])
    if not err:
        return {}
    return {"pred_err_max": max(err),
            "pred_err_median": float(np.median(err)),
            "winner_loss_max": max(loss),
            "winner_loss_median": float(np.median(loss))}


def release(st):
    """Free what the window held: the kernels' bound arguments, the
    study's weights and data, the campaigns' results."""
    st.shapes = {sig: _shapes(args) for sig, args in st.study._args.items()}
    st.study._args.clear()
    st.study.params = st.study.batch_data = None
    gc.collect()


def _shapes(args):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        args)


def sample_kernels(st):
    """One signature of each kind, drawn from the seed."""
    rng = np.random.default_rng(D.seed32(st.seed, 2))
    by_kind = {}
    for sig in sorted(st.shapes, key=str):
        by_kind.setdefault(sig.name, []).append(sig)
    return [by_kind[k][rng.integers(len(by_kind[k]))]
            for k in KINDS if k in by_kind]


def make_inputs(st, sig):
    """Inputs for one kernel, shaped as the window's, from the seed."""
    import jax
    import jax.numpy as jnp

    shapes = st.shapes[sig]
    salt = 10 + KINDS.index(sig.name)
    if sig.name in ("attn_fb", "dense_fb"):
        params = D.random_like(shapes[0], st.seed, salt)
        x = D.random_like(shapes[1], st.seed, salt + 100,
                          scale_of=lambda p, s: 1.0)
        return (params, x)
    if sig.name == "embed_loss_fb":
        params = D.random_like(
            shapes[0], st.seed, salt,
            scale_of=lambda p, s: 0.1 if len(s) == 1 else
            (0.02 if "embed" in p else 1.0 / np.sqrt(s[0])))
        return (params,)
    params, grads, state = shapes
    p = D.random_like(params, st.seed, salt, scale_of=lambda q, s: 0.05)
    g = D.random_like(params, st.seed, salt + 1, scale_of=lambda q, s: 1e-3)
    m = D.random_like(params, st.seed, salt + 2, scale_of=lambda q, s: 1e-3)
    v = jax.tree.map(jnp.square,
                     D.random_like(params, st.seed, salt + 3,
                                   scale_of=lambda q, s: 1e-3))
    step = jnp.asarray(ADAMW_STEP, jnp.int32)
    return (p, g, {"m": m, "v": v, "step": step})


def study_tokens(st, mb):
    """The token ids ``LMStudy`` draws from its seed for its embed/loss
    kernel (its own data, regenerated here by the same draw)."""
    import jax
    key = jax.random.PRNGKey(D.seed32(st.seed, 1))
    k1, _, _ = jax.random.split(key, 3)
    t = st.cell.traffic
    toks = jax.random.randint(k1, (t["batch"], t["seq"]), 0, st.cfg.vocab)
    return toks[:mb]


def reference_out(st, sig, inputs, ref, dtype):
    """What the plain reference gives for one kernel's inputs."""
    import jax.numpy as jnp
    opt = st.cell.config["optimizer"]
    if sig.name == "adamw":
        p, g, state = inputs
        new_p, m, v = ref.adamw(opt, p, g, state, dtype=dtype)
        return {"update": _sub(new_p, p), "m": m, "v": v}
    if sig.name == "embed_loss_fb":
        toks = study_tokens(st, sig.params[0])
        return ref.kernel_grad(sig.name, inputs[0], toks, st.cell.config,
                               dtype=dtype, rows=1)
    return ref.kernel_grad(sig.name, inputs[0], inputs[1], st.cell.config,
                           dtype=dtype)


def program_out(st, sig, inputs):
    out = st.study._fns[sig](*inputs)
    if sig.name == "adamw":
        new_p, state = out[0], out[1]
        return {"update": _sub(new_p, inputs[0]), "m": state["m"],
                "v": state["v"]}
    return out


def _sub(a, b):
    import jax
    return jax.tree.map(lambda x, y: x.astype("float32") - y, a, b)


def leaf_error(out, ref):
    """Worst leaf of |out - ref| / max(|ref|, median leaf |ref|), in the
    L2 norm over each leaf."""
    import jax
    import jax.numpy as jnp
    o = jax.tree.leaves(out)
    r = jax.tree.leaves(ref)
    rn = np.asarray([float(jnp.linalg.norm(x.astype(jnp.float32)))
                     for x in r])
    dn = np.asarray([float(jnp.linalg.norm(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))
                     for a, b in zip(o, r)])
    floor = float(np.median(rn))
    return float(np.max(dn / np.maximum(rn, floor)))


def check(st, produce=None):
    """Numbers compared, by name.  ``produce(sig, inputs)`` stands in for
    the program's kernels, and ``produce.answers(answers)``, where it is
    there, for the tuner's answers (the control, a planted fault)."""
    import jax.numpy as jnp
    ref = st.cell.reference()
    answers = getattr(produce, "answers", lambda a: a)(st.answers)
    numbers = answer_numbers(answers)
    out = {k: numbers.get(k, math.nan) for k in ANSWERS
           if k in st.cell.limits}
    st.check_info = {k: v for k, v in numbers.items() if k not in out}
    out["ref_exec_missing"] = float(st.ref_missing)
    produce = produce if callable(produce) else None
    for sig in sample_kernels(st):
        inputs = make_inputs(st, sig)
        got = (produce or (lambda s, i: program_out(st, s, i)))(sig, inputs)
        want = reference_out(st, sig, inputs, ref, jnp.float32)
        out[f"{sig.name}_err"] = leaf_error(got, want)
        del got, want, inputs
        gc.collect()
    return out


def control(st):
    """The reference with fp8 products, put in the program's place."""
    ref = st.cell.reference()
    return lambda sig, inputs: reference_out(st, sig, inputs, ref, ref.FP8)


def half_batch(st):
    """A planted fault: half of each kernel's tokens (the second half of
    every sequence) left out, the rest scaled as the mean over them (the
    reference, in float32, in the program's place).  AdamW has no batch
    and runs whole."""
    import jax.numpy as jnp
    ref = st.cell.reference()

    def produce(sig, inputs):
        if sig.name == "adamw":
            return reference_out(st, sig, inputs, ref, jnp.float32)
        s = st.cell.traffic["seq"] // 2
        if sig.name == "embed_loss_fb":
            toks = study_tokens(st, sig.params[0])[:, :s]
            return ref.kernel_grad(sig.name, inputs[0], toks,
                                   st.cell.config, dtype=jnp.float32)
        g = ref.kernel_grad(sig.name, inputs[0], inputs[1][:, :s],
                            st.cell.config, dtype=jnp.float32)
        return {k: 2.0 * v for k, v in g.items()}
    return produce


class AnswersFault:
    """A fault in the tuner's answers; the kernels stay the program's."""

    def __init__(self, answers):
        self.answers = answers


def skip_all(st):
    """A planted fault: trials that execute no kernel.  With statistics
    fresh each campaign, every skipped kernel charges its mean of 0, so
    every prediction is 0."""
    return AnswersFault(
        lambda answers: [[[f, 0.0] for f, _ in rows] for rows in answers])


def worst_winner(st):
    """A planted fault: each campaign picks its slowest configuration by
    the reference (its prediction set just below every other)."""
    def answers_of(answers):
        out = []
        for rows in answers:
            worst = max(f for f, _ in rows)
            least = np.nextafter(min(p for _, p in rows), -np.inf)
            out.append([[f, least if f == worst else p] for f, p in rows])
        return out
    return AnswersFault(answers_of)


FAULTS = {"half_batch": half_batch, "skip_all": skip_all,
          "worst_winner": worst_winner}
