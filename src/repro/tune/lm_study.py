"""Measured-mode LM autotuning study (the paper's technique on our own
framework, real wall-clock; reduced architectures, or published widths on
the chip).

A *configuration* is a ``StepKnobs`` point (grad accumulation x remat x
attention/ssm chunking x MoE dispatch).  A configuration's step is
decomposed into recurring kernels:

    embed+loss closure        once per microbatch
    <mixer kind> fwd+bwd      n_periods x period-positions x microbatches
    <ffn kind>  fwd+bwd       likewise
    optimizer update          once per step

Each kernel is a jitted closure keyed by a ``Signature`` carrying the knob
subset that affects it — so configurations SHARE kernels exactly when the
paper's theory says they should (e.g. changing MoE dispatch leaves every
attention kernel's signature intact).  ``SelectiveTimer`` then applies the
confidence-interval skipping; per-step occurrence counts feed the sqrt(k)
CI shrink.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core.signatures import Signature, comp_sig
from repro.models import layers as ML
from repro.models import moe as MM
from repro.models import ssm as MS
from repro.models.model import Model, ModelKnobs, init_params
from .selective import SelectiveTimer


@dataclass(frozen=True)
class StepKnobs:
    name: str
    grad_accum: int = 1
    remat: str = "none"          # 'none' | 'full'
    kv_chunk: int = 32
    ssm_chunk: int = 16
    moe_dispatch: str = "sort"   # 'sort' | 'dense'


def lm_config_space(cfg: ArchConfig) -> List[StepKnobs]:
    accums = (1, 2, 4)
    remats = ("none", "full")
    kvs = (16, 64)
    moes = ("sort", "dense") if cfg.moe else ("sort",)
    ssms = (8, 32) if any(k in ("mamba", "mlstm", "slstm")
                          for k in cfg.pattern) else (16,)
    out = []
    for ga, rm, kv, md, sc in itertools.product(accums, remats, kvs, moes,
                                                ssms):
        out.append(StepKnobs(
            name=f"ga{ga}-{rm}-kv{kv}-{md}-ssm{sc}",
            grad_accum=ga, remat=rm, kv_chunk=kv, ssm_chunk=sc,
            moe_dispatch=md))
    return out


def _block_params(model: Model, params, pos: int, period: int):
    """Slice one period's params for one position (concrete arrays)."""
    per = params[f"pos{pos}"]
    return jax.tree.map(lambda a: a[period], per)


class LMStudy:
    """Benchmarks StepKnobs configurations for one arch: its reduced
    config by default, its published widths with ``reduced=False``."""

    def __init__(self, arch: str, *, reduced: bool = True, batch: int = 2,
                 seq: int = 32, seed: int = 0):
        self.cfg = get_config(arch, reduced=reduced)
        self.batch, self.seq = batch, seq
        key = jax.random.PRNGKey(seed)
        self.params = init_params(self.cfg, key)
        tshape = ((batch, seq, self.cfg.n_codebooks) if self.cfg.n_codebooks
                  else (batch, seq))
        k1, k2, k3 = jax.random.split(key, 3)
        self.batch_data = {
            "tokens": jax.random.randint(k1, tshape, 0, self.cfg.vocab),
            "labels": jax.random.randint(k2, tshape, 0, self.cfg.vocab),
        }
        if self.cfg.n_patches:
            self.batch_data["patches"] = jax.random.normal(
                k3, (batch, self.cfg.n_patches, self.cfg.d_model))
        self._fns: Dict[Signature, callable] = {}
        self._args: Dict[Signature, tuple] = {}

    # -- kernel construction ---------------------------------------------------

    def _kernel(self, sig: Signature, build):
        """Get-or-build the jitted closure + concrete args for a signature;
        compile (first call) happens outside the timed region."""
        if sig not in self._fns:
            fn, args = build()
            jax.block_until_ready(fn(*args))   # compile outside timed region
            self._fns[sig] = fn
            self._args[sig] = args
        return self._fns[sig], self._args[sig]

    def _mixer_kernel(self, kind: str, pos: int, knobs: StepKnobs, mb: int):
        cfg = self.cfg
        S = self.seq
        sig = comp_sig(f"{kind}_fb", mb, S, cfg.d_model, knobs.kv_chunk
                       if kind in ("attn", "mla") else knobs.ssm_chunk,
                       knobs.remat)

        def build():
            p = _block_params(Model(cfg), self.params, pos, 0)
            mix = {k[len("mix_"):]: v for k, v in p.items()
                   if k.startswith("mix_")}
            x = jax.random.normal(jax.random.PRNGKey(pos),
                                  (mb, S, cfg.d_model))
            positions = jnp.arange(S)

            def fwd(mix, x):
                if kind == "attn":
                    h, _ = ML.attn_block(mix, x, cfg, positions=positions,
                                         kv_chunk=knobs.kv_chunk)
                elif kind == "mla":
                    h, _ = ML.mla_block(mix, x, cfg, positions=positions,
                                        kv_chunk=knobs.kv_chunk)
                elif kind == "mamba":
                    h, _ = MS.mamba_block(mix, x, cfg, chunk=knobs.ssm_chunk)
                elif kind == "mlstm":
                    h, _ = MS.mlstm_block(mix, x, cfg, chunk=knobs.ssm_chunk)
                else:
                    h, _ = MS.slstm_block(mix, x, cfg, chunk=knobs.ssm_chunk)
                return jnp.sum(h * h)
            if knobs.remat == "full":
                fwd = jax.checkpoint(fwd)
            fn = jax.jit(jax.grad(fwd))
            return (lambda m, xx: jax.block_until_ready(fn(m, xx))), (mix, x)
        return sig, build

    def _ffn_kernel(self, fk: str, pos: int, knobs: StepKnobs, mb: int):
        cfg = self.cfg
        S = self.seq
        extra = knobs.moe_dispatch if fk == "moe" else "-"
        sig = comp_sig(f"{fk}_fb", mb, S, cfg.d_model, extra, knobs.remat)

        def build():
            p = _block_params(Model(cfg), self.params, pos, 0)
            ffn = {k[len("ffn_"):]: v for k, v in p.items()
                   if k.startswith("ffn_")}
            x = jax.random.normal(jax.random.PRNGKey(100 + pos),
                                  (mb, S, cfg.d_model))

            def fwd(ffn, x):
                if fk == "dense":
                    h = ML.ffn_block(ffn, x, cfg)
                else:
                    h = MM.moe_ffn(ffn, x, cfg,
                                   dispatch=knobs.moe_dispatch)
                return jnp.sum(h * h)
            if knobs.remat == "full":
                fwd = jax.checkpoint(fwd)
            fn = jax.jit(jax.grad(fwd))
            return (lambda m, xx: jax.block_until_ready(fn(m, xx))), (ffn, x)
        return sig, build

    def _embed_loss_kernel(self, knobs: StepKnobs, mb: int):
        cfg = self.cfg
        sig = comp_sig("embed_loss_fb", mb, self.seq, cfg.vocab)

        def build():
            model = Model(cfg, ModelKnobs(kv_chunk=knobs.kv_chunk,
                                          ssm_chunk=knobs.ssm_chunk))
            data = jax.tree.map(lambda a: a[:mb], self.batch_data)

            def fwd(params):
                x = model._embed(params, data)
                x = ML.rms_norm(x, params["final"]["ln"], cfg.norm_eps)
                logits = model._head(params, x)
                return jnp.mean(logits.astype(jnp.float32) ** 2)
            fn = jax.jit(jax.grad(fwd))
            sub = {"embed": self.params["embed"],
                   "final": self.params["final"]}
            if "head" in self.params:
                sub["head"] = self.params["head"]
            return (lambda p: jax.block_until_ready(fn(p))), (sub,)
        return sig, build

    def _opt_kernel(self):
        sig = comp_sig("adamw", sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(self.params)))

        def build():
            from repro.train.optim import AdamWConfig, adamw_init, \
                adamw_update
            oc = AdamWConfig()
            st = adamw_init(self.params)
            g = jax.tree.map(jnp.ones_like, self.params)
            fn = jax.jit(lambda p, gg, s: adamw_update(oc, p, gg, s))
            return (lambda p, gg, s: jax.block_until_ready(fn(p, gg, s))), \
                (self.params, g, st)
        return sig, build

    # -- one configuration benchmark --------------------------------------------

    def kernel_sequence(self, knobs: StepKnobs):
        """The step's kernel occurrence list: (sig, build, freq)."""
        cfg = self.cfg
        mb = max(self.batch // knobs.grad_accum, 1)
        seq = []
        counts: Dict[Signature, int] = {}
        per_step = []
        for pos, (kind, fk) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
            per_step.append(self._mixer_kernel(kind, pos, knobs, mb))
            if fk != "none":
                per_step.append(self._ffn_kernel(fk, pos, knobs, mb))
        items = []
        for _ in range(knobs.grad_accum):
            for _ in range(cfg.n_periods):
                items.extend(per_step)
            items.append(self._embed_loss_kernel(knobs, mb))
        items.append(self._opt_kernel())
        for sig, _ in items:
            counts[sig] = counts.get(sig, 0) + 1
        return [(sig, build, counts[sig]) for sig, build in items]

    # -- session-API adapters ----------------------------------------------------

    def kernels_of(self, point):
        """``WallClockBackend`` provider: resolve a ``ConfigPoint`` (or a
        bare ``StepKnobs``) to the step's bound kernel occurrence list
        ``[(Signature, thunk, freq)]``; compilation happens here, outside
        any timed region (profiler span ``tuner.kernels_of``)."""
        with TraceAnnotation("tuner.kernels_of"):
            knobs = getattr(point, "payload", point) or point
            out = []
            for sig, build, freq in self.kernel_sequence(knobs):
                fn, args = self._kernel(sig, build)
                out.append((sig,
                            (lambda fn=fn, args=args: fn(*args)), freq))
        return out

    @staticmethod
    def stats_bank(*results):
        """Merge the kernel-statistics banks of completed LM study results
        (``AutotuneSession(..., collect_stats=True)``) into one transfer
        prior.  LM kernels are keyed by the knob subset that affects them,
        so a bank recorded on one StepKnobs subspace (or another arch
        sharing block shapes) warm-starts exactly the kernels the paper's
        theory says it should: pass the merged bank back as
        ``AutotuneSession(..., prior=bank)``."""
        from repro.api.transfer import StatisticsBank
        bank = StatisticsBank()
        for r in results:
            b = r.stats_bank() if hasattr(r, "stats_bank") else r
            if b:
                bank = bank.merge(b)
        return bank

    def session(self, *, policy: str = "conditional",
                tolerance: float = 0.25, search: str = "exhaustive",
                max_configs: Optional[int] = None, trials: int = 3,
                prior=None, clock=None, **kw):
        """The supported front-end over this study: an ``AutotuneSession``
        measuring StepKnobs points with ``WallClockBackend`` bound to
        ``kernels_of``.  Sweeps run through ``repro.api.scheduler`` like
        every other study (serially — wall-clock backends are not
        ``parallel_safe``); ``search="racing"`` races configurations by
        real wall clock (see ``race``).  ``clock`` overrides the backend's
        time source (deterministic tests, daemon parity checks)."""
        from repro.api import AutotuneSession, WallClockBackend
        return AutotuneSession(self.search_space(max_configs),
                               backend=WallClockBackend(self.kernels_of,
                                                        clock=clock),
                               policy=policy, tolerance=tolerance,
                               search=search, trials=trials, prior=prior,
                               **kw)

    def race(self, *, policy: str = "conditional", tolerance: float = 0.25,
             max_configs: Optional[int] = None, max_rounds: int = 6,
             prior=None, **kw):
        """Wall-clock racing study: successive elimination over the
        StepKnobs space driven by the paper's per-kernel CIs on real
        measured step times — each round gives every surviving
        configuration one selective trial and prunes configurations whose
        CI lower bound exceeds the incumbent's upper bound.  Returns the
        ``StudyResult`` (winner in ``extra["best"]``); far cheaper than
        the exhaustive protocol when only the optimum is wanted, because
        losing configurations stop being timed at all."""
        return self.session(policy=policy, tolerance=tolerance,
                            search="racing", max_configs=max_configs,
                            search_options={"max_rounds": max_rounds},
                            prior=prior, **kw).run()

    def search_space(self, max_configs: Optional[int] = None):
        """The session-API view of this study's StepKnobs space.  Resets
        follow the policy (eager's persistent models skip the reset), the
        convention of the measured LM benchmarks."""
        from repro.api.space import RESET_POLICY, ConfigPoint, SearchSpace
        pts = [ConfigPoint(name=kn.name, params={
                   "grad_accum": kn.grad_accum, "remat": kn.remat,
                   "kv_chunk": kn.kv_chunk, "ssm_chunk": kn.ssm_chunk,
                   "moe_dispatch": kn.moe_dispatch}, payload=kn)
               for kn in lm_config_space(self.cfg)]
        if max_configs is not None:
            pts = pts[:max_configs]
        return SearchSpace(name=f"lm-{self.cfg.name}", points=pts,
                           reset_between_configs=RESET_POLICY)

    def run_config(self, knobs: StepKnobs, timer: SelectiveTimer,
                   *, iters: int = 3):
        """Selective benchmark of one configuration; returns
        (predicted step time, full-execution reference time, cost)."""
        seqn = self.kernel_sequence(knobs)
        # full execution directly prior (reference; not fed to models)
        full = 0.0
        for sig, build, freq in seqn:
            fn, args = self._kernel(sig, build)
            t0 = timer.clock()
            fn(*args)
            full += timer.clock() - t0
        cost = 0.0
        pred = None
        for _ in range(iters):
            timer.begin_iteration()
            for sig, build, freq in seqn:
                fn, args = self._kernel(sig, build)
                timer.time_kernel(sig, lambda: fn(*args), freq)
            rep = timer.report()
            cost += rep.measured_time
            pred = rep.predicted_time
        return pred, full, cost
