"""Batched serving engine: continuous batching over fixed decode slots.

Requests (prompt token arrays) queue at the Ingress; the engine packs them
into ``n_slots`` decode lanes, feeding each prompt as it is admitted and
recycling a lane as soon as its request finishes (EOS or max tokens) — the
serving counterpart of the Databelt runtime: the KV-cache slot is the
"function state", kept device-local for the lifetime of the request.

A prompt of P tokens is fed to its own lane in ceil(P/C) chunks of C
tokens through one compiled program, ``prefill_chunk``
(``forward_chunk``); the other lanes are left untouched.  The model
decides C: ``prefill_chunk_len`` in ``models/transformer.py`` gives it,
or 0 for a configuration whose prompt is fed one token at a time through
the decode program, across all lanes.  ``stats`` counts which way the
prompt tokens went, and for MoE configurations the (token, held expert)
pairs that carry a weight (``expert_rows_prefill``,
``expert_rows_decode``, over every layer, a chunk's padding excluded, a
decode step's every lane included), the pairs routed
(``expert_pairs_routed``, tokens times k times layers) and the held
experts whose weights decode read
(``expert_blocks_decode``, over every layer and step: the distinct ones
routed to where decode takes the routed-experts kernel, else all).  Those
are added to on the device, inside the two programs, and copied to the
host when ``stats`` is read.  The first token comes from the logits of
the prompt's last position either way.

With ``annotate=True`` the engine opens ``jax.profiler.TraceAnnotation``
spans at its layer boundaries, stamped by the profiler on the clock of the
device planes: ``serve.step`` (one decode step, admission included),
``serve.admit``, ``serve.prefill`` (one per prompt) and ``serve.emit``.
Off, each site costs one attribute check and builds no annotation.  The
device trace names the two programs' modules ``jit_decode_step`` and
``jit_prefill_chunk``.
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
from repro.models import (expert_counts, forward_chunk, forward_decode,
                          init_cache, prefill_chunk_len)
from repro.models.modules import pdtype

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
        self.cache = init_cache(cfg, n_slots, max_len, pdtype(cfg))
        self.chunk = prefill_chunk_len(cfg, max_len)
        self._counts = {"prefill_chunks": 0, "prompt_tokens_chunked": 0,
                        "prompt_tokens_stepped": 0}

        def decode_step(p, c, tok, pos):
            return forward_decode(p, cfg, c, tok, pos)

        def prefill_chunk(p, c, tok, pos, last, lane):
            return forward_chunk(p, cfg, c, tok, pos, last, lane)
        self._decode_step = jax.jit(decode_step)
        self._prefill_chunk = jax.jit(prefill_chunk)
        self.completed: List[Request] = []

    @property
    def stats(self) -> Dict[str, int]:
        """The host's prompt counters, and the device's expert counters
        (fetched now) for an MoE configuration."""
        return {**self._counts, **expert_counts(self.cache)}

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
                    # the prompt goes in now: in chunks to lane i alone
                    # where the configuration allows, else a token at a
                    # time through decode (see the module docstring)
                    self.pos[i] = 0
                    self.budget[i] = req.max_new
                    self._feed_prompt(i, req)

    def _decode(self, params, cache, tokens, pos, *chunk):
        """The engine's one entry for a model step, returning (logits,
        cache): a (n_slots, 1) token block goes to ``decode_step``; one
        lane's (1, C) block, with its ``last`` row and ``lane``, to
        ``prefill_chunk``, whose logits are those of row ``last``."""
        if chunk:
            return self._prefill_chunk(params, cache, tokens, pos, *chunk)
        return self._decode_step(params, cache, tokens, pos)

    def _feed_prompt(self, i: int, req: Request):
        with (jax.profiler.TraceAnnotation("serve.prefill") if self.annotate
              else _NO_SPAN):
            logits, P, C = None, len(req.prompt), self.chunk
            if C:
                # positions, last row and lane go in as strongly typed
                # int32, so every prompt length shares one program
                for start in range(0, P, C):
                    part = np.asarray(req.prompt[start:start + C], np.int32)
                    tok = np.zeros((1, C), np.int32)
                    tok[0, :len(part)] = part
                    logits, self.cache = self._decode(
                        self.params, self.cache, tok, np.int32(start),
                        np.int32(len(part) - 1), np.int32(i))
                    self._counts["prefill_chunks"] += 1
                self._counts["prompt_tokens_chunked"] += P
                self.pos[i] = P
                lane = 0
            else:
                for t in req.prompt:
                    tok = jnp.full((self.n_slots, 1), int(t), jnp.int32)
                    # only slot i's lane matters; the others decode a dummy
                    # token at slot i's position, which corrupts a busy
                    # lane (one scalar position per batch: ROADMAP queue 2)
                    logits, self.cache = self._decode(
                        self.params, self.cache, tok, np.int32(self.pos[i]))
                    self.pos[i] += 1
                self._counts["prompt_tokens_stepped"] += P
                lane = i
            # the first token comes from the prompt's last logits; decode
            # steps then feed it back at position len(prompt)
            if logits is not None:
                self._emit(i, int(jnp.argmax(
                    logits[lane, -1].astype(jnp.float32))))

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
