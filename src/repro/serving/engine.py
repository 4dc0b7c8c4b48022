"""Batched serving engine: continuous batching over fixed decode slots.

Requests (prompt token arrays) queue at the Ingress; the engine packs them
into ``n_slots`` decode lanes, prefilling lazily and recycling a lane as
soon as its request finishes (EOS or max tokens) — the serving counterpart
of the Databelt runtime: the KV-cache slot is the "function state", kept
device-local for the lifetime of the request.

With ``annotate=True`` the engine opens ``jax.profiler.TraceAnnotation``
spans at its layer boundaries, stamped by the profiler on the clock of the
device planes: ``serve.step`` (one decode step, admission included),
``serve.admit``, ``serve.prefill`` (one per prompt) and ``serve.emit``.
Off, each site costs one attribute check and builds no annotation.  The
decode program is the jitted ``decode_step``, so the device trace names
its module ``jit_decode_step``.
"""
from __future__ import annotations

import collections
import contextlib
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import forward_decode, forward_prefill, init_cache
from repro.models.io import make_batch

_NO_SPAN = contextlib.nullcontext()


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    tokens_out: List[int] = field(default_factory=list)
    done: bool = False


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, n_slots: int = 4,
                 max_len: int = 256, eos_id: int = 1,
                 annotate: bool = False):
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.annotate = annotate
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.pos = np.zeros(n_slots, np.int32)
        self.budget = np.zeros(n_slots, np.int32)
        self.cache = init_cache(cfg, n_slots, max_len)

        def decode_step(p, c, tok, pos):
            return forward_decode(p, cfg, c, tok, pos)
        self._decode = jax.jit(decode_step)
        self.completed: List[Request] = []

    def submit(self, req: Request):
        self.queue.append(req)

    # ------------------------------------------------------------------
    def _admit(self):
        with (jax.profiler.TraceAnnotation("serve.admit") if self.annotate
              else _NO_SPAN):
            for i in range(self.n_slots):
                if self.slots[i] is None and self.queue:
                    req = self.queue.popleft()
                    self.slots[i] = req
                    # lazy prefill: feed prompt tokens one by one through
                    # decode (keeps one compiled program; real TPU serving
                    # would use a separate prefill program — see
                    # serving/steps.py)
                    self.pos[i] = 0
                    self.budget[i] = req.max_new
                    self._feed_prompt(i, req)

    def _feed_prompt(self, i: int, req: Request):
        with (jax.profiler.TraceAnnotation("serve.prefill") if self.annotate
              else _NO_SPAN):
            logits = None
            for t in req.prompt:
                tok = jnp.full((self.n_slots, 1), int(t), jnp.int32)
                # only slot i's lane matters; the others decode a dummy
                # token at slot i's position, which corrupts a busy lane
                # (one scalar position per batch: ROADMAP queue 2)
                logits, self.cache = self._decode(
                    self.params, self.cache, tok, np.int32(self.pos[i]))
                self.pos[i] += 1
            # the first token comes from the prompt's last logits; decode
            # steps then feed it back at position len(prompt)
            if logits is not None:
                self._emit(i, int(jnp.argmax(
                    logits[i, -1].astype(jnp.float32))))

    def _emit(self, i: int, t: int):
        """Slot i's request takes token t; the slot frees when it is done."""
        with (jax.profiler.TraceAnnotation("serve.emit") if self.annotate
              else _NO_SPAN):
            r = self.slots[i]
            r.tokens_out.append(t)
            self.budget[i] -= 1
            if t == self.eos_id or self.budget[i] <= 0 or \
                    self.pos[i] >= self.max_len - 1:
                r.done = True
                self.completed.append(r)
                self.slots[i] = None

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One decode step across all active slots; returns #active."""
        with (jax.profiler.TraceAnnotation("serve.step") if self.annotate
              else _NO_SPAN):
            self._admit()
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return 0
            toks = np.zeros((self.n_slots, 1), np.int32)
            for i in active:
                r = self.slots[i]
                toks[i, 0] = r.tokens_out[-1] if r.tokens_out else 0
            # positions go in as strongly typed int32 here and in
            # _feed_prompt, so both call sites share one compiled decode
            # program
            pos = np.int32(max(self.pos[i] for i in active))
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(toks), pos)
            nxt = np.asarray(jnp.argmax(logits[:, -1].astype(jnp.float32),
                                        axis=-1))
            for i in active:
                self.pos[i] += 1
                self._emit(i, int(nxt[i]))
            return len(active)

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.completed
