"""Batched serving engine.

Slot-based continuous batching over a fixed-capacity decode batch:

- requests enter a queue; free slots are filled by running ``prefill`` for
  the incoming prompt (right-padded to the slot's capacity) and splicing its
  cache into the batch cache at the slot index: one jitted function, with
  the batch cache donated and the slot a traced argument, writes the slot
  in place and compiles once for every slot;
- one ``decode_step`` advances every active slot by a token; it is jitted
  with the cache donated, so each layer writes only its new row per slot
  in place, except where the rules shard the cache's ``kv_seq`` axis and
  the step blends the row into the whole cache (``layers.cache_update``);
- finished slots (eos or max tokens) are retired and refilled.

The decode step is jitted once per (batch capacity, s_max); prefill is
jitted per prompt-length bucket.  Sampling: greedy or temperature.
``Engine.counters`` counts the decode steps by the path they took
(``decode_in_place``, ``decode_blend``) and the admissions
(``splice_in_place``).

Profiler spans (``jax.profiler.TraceAnnotation``, recorded only while a
trace is active): once per admitted request ``engine.prefill`` (the
call), ``engine.splice`` and ``engine.first_token`` (the prefill's logits
pulled to the host and sampled); once per step ``engine.decode`` (the
call and its logits pulled to the host) and ``engine.sample``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models.model import Model, ModelKnobs
from repro.parallel.sharding import ShardingRules, axis_rules


def bucket_length(n: int, buckets: Sequence[int]) -> int:
    """Pad length ``n`` up to the smallest bucket that holds it (the last
    bucket when none does; ``n`` itself with no buckets).  THE bucketing
    function: the engine's prompt padding and the tuning daemon's shape
    keys both go through here, so a request can never be padded to one
    sequence length and tuned at another."""
    if not buckets:
        return n
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


@dataclass
class ServeConfig:
    batch_size: int = 8
    s_max: int = 512
    max_new_tokens: int = 64
    temperature: float = 0.0        # 0 = greedy
    eos_id: Optional[int] = None
    # () = jit per exact prompt length (keeps SSM states pad-free);
    # nonempty = pad prompts up to bucket sizes (attention-only archs)
    prompt_buckets: Sequence[int] = ()
    seed: int = 0


@dataclass
class Request:
    uid: int
    tokens: np.ndarray              # (S_prompt,) prompt token ids
    max_new_tokens: Optional[int] = None


@dataclass
class Result:
    uid: int
    tokens: List[int] = field(default_factory=list)


def _splice(cache, one, slot):
    """Write a batch-1 cache ``one`` into slot ``slot`` of ``cache``."""
    return jax.tree.map(
        lambda big, o: jax.lax.dynamic_update_slice_in_dim(
            big, o.astype(big.dtype), slot, axis=1), cache, one)


class Engine:
    """Single-host engine; rules=None runs unsharded (CPU smoke scale)."""

    def __init__(self, model: Model, params, sc: ServeConfig,
                 rules: Optional[ShardingRules] = None):
        self.model = model
        self.params = params
        self.sc = sc
        self.rules = rules
        self.cfg = model.cfg
        B, S = sc.batch_size, sc.s_max
        with axis_rules(rules):
            self.cache = model.init_cache(B, S)
        self.lengths = np.zeros(B, np.int64)         # per-slot position
        self.budget = np.zeros(B, np.int64)
        self.active = np.zeros(B, bool)
        self.slot_uid = np.full(B, -1, np.int64)
        self.results: Dict[int, Result] = {}
        self.queue: List[Request] = []
        self.last_token = np.zeros((B,) + self._tok_trailing(), np.int32)
        self._rng = np.random.default_rng(sc.seed)
        self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
        self._splice = jax.jit(_splice, donate_argnums=(0,))
        self._prefill_cache: Dict[int, Any] = {}
        with axis_rules(rules):
            in_place = model.kv_write_in_place(B, S)
        self._decode_path = "decode_in_place" if in_place else "decode_blend"
        self.counters = {"decode_in_place": 0, "decode_blend": 0,
                         "splice_in_place": 0}

    def _tok_trailing(self):
        return (self.cfg.n_codebooks,) if self.cfg.n_codebooks else ()

    # -- jitted closures -------------------------------------------------------

    def _decode_fn(self, params, cache, t_per_slot, tokens):
        """t_per_slot: (B,) int32 current positions (ragged batch)."""
        with axis_rules(self.rules):
            logits, cache = self.model.decode_step(
                params, cache, t_per_slot, {"tokens": tokens[:, None]})
        return logits, cache

    def _prefill_fn(self, params, batch, s_max, logits_at):
        with axis_rules(self.rules):
            return self.model.prefill(params, batch, s_max,
                                      logits_at=logits_at)

    # -- public API -------------------------------------------------------------

    def submit(self, req: Request):
        self.queue.append(req)
        self.results[req.uid] = Result(req.uid)

    def _bucket(self, n):
        return bucket_length(n, self.sc.prompt_buckets)

    def _admit(self):
        """Fill free slots from the queue (prefill + cache splice)."""
        for slot in np.nonzero(~self.active)[0]:
            if not self.queue:
                break
            req = self.queue.pop(0)
            with TraceAnnotation("engine.prefill"):
                S_p = self._bucket(len(req.tokens))
                toks = np.zeros((1, S_p) + self._tok_trailing(), np.int32)
                toks[0, :len(req.tokens)] = req.tokens
                fn = self._prefill_cache.get(S_p)
                if fn is None:
                    fn = jax.jit(lambda p, b, at: self._prefill_fn(
                        p, b, self.sc.s_max, at))
                    self._prefill_cache[S_p] = fn
                at = jnp.asarray([len(req.tokens) - 1], jnp.int32)
                logits, cache1, _ = fn(self.params,
                                       {"tokens": jnp.asarray(toks)}, at)
            with TraceAnnotation("engine.splice"):
                # its dispatch overlaps the prefill on the device
                self.cache = self._splice(self.cache, cache1,
                                          jnp.int32(slot))
                self.counters["splice_in_place"] += 1
            with TraceAnnotation("engine.first_token"):
                tok0 = self._sample(np.asarray(logits)[0])
            self.last_token[slot] = tok0
            self.lengths[slot] = len(req.tokens)
            # the prefill-sampled token is the first generated token
            self.budget[slot] = (req.max_new_tokens
                                 or self.sc.max_new_tokens) - 1
            self.active[slot] = True
            self.slot_uid[slot] = req.uid
            self.results[req.uid].tokens.append(int(np.ravel(tok0)[0])
                                                if not self.cfg.n_codebooks
                                                else list(map(int, tok0)))

    def _sample(self, logits):
        if self.sc.temperature <= 0:
            return np.argmax(logits, axis=-1).astype(np.int32)
        z = logits / self.sc.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        flat = p.reshape(-1, p.shape[-1])
        out = np.array([self._rng.choice(len(q), p=q) for q in flat],
                       np.int32)
        return out.reshape(p.shape[:-1])

    def step(self) -> int:
        """Admit + one decode step for all active slots; returns #active."""
        self._admit()
        if not self.active.any():
            return 0
        with TraceAnnotation("engine.decode"):
            t = jnp.asarray(self.lengths.astype(np.int32))
            logits, self.cache = self._decode(
                self.params, self.cache, t, jnp.asarray(self.last_token))
            self.counters[self._decode_path] += 1
            logits = np.asarray(logits)
        with TraceAnnotation("engine.sample"):
            for slot in np.nonzero(self.active)[0]:
                nxt = self._sample(logits[slot])
                self.last_token[slot] = nxt
                self.lengths[slot] += 1
                self.budget[slot] -= 1
                uid = int(self.slot_uid[slot])
                val = (int(np.ravel(nxt)[0]) if not self.cfg.n_codebooks
                       else list(map(int, nxt)))
                self.results[uid].tokens.append(val)
                eos = (self.sc.eos_id is not None
                       and not self.cfg.n_codebooks and val == self.sc.eos_id)
                if eos or self.budget[slot] <= 0 \
                        or self.lengths[slot] >= self.sc.s_max - 1:
                    self.active[slot] = False
                    self.slot_uid[slot] = -1
        return int(self.active.sum())

    def run(self) -> Dict[int, Result]:
        while self.queue or self.active.any():
            self.step()
        return self.results
