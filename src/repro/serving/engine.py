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

Both programs also return the greedy token of each row's last position,
``argmax`` of the float32 logits as int32 ``(rows, 1)``, so the host
reads one small array a step and dispatches nothing else.  A decode step
runs one step ahead of the host:

1. step N is dispatched (or claimed, below);
2. where its tokens are the whole input of step N+1 (every lane of the
   batch is busy, or the prompt's lane is the batch) and every one of
   those lanes goes on past this token whatever it is (``budget`` after
   it at least 1, position below ``max_len - 1``), step N+1 is
   dispatched at once on step N's device tokens and cache;
3. only then does the host read step N's tokens and ``_emit`` them.

After a prompt the same holds for the last chunk (or prompt step): the
first decode step is queued from the program's own token output, and
the first token is read from that program, not behind the step.  The
next ``_decode`` call claims the step ahead only if it asks for exactly
what that step ran: the same parameters and parent cache object, the
same position and busy lanes, and host tokens equal to the device tokens
it was fed.  Otherwise the step is dropped, its cache discarded and the
parent's kept; so is one that what was emitted no longer asks for (an
end-of-sequence token, an ``_emit`` that took another token).  A dropped
step leaves no trace: recurrent state and expert counters advance by
claimed steps only.  ``stats`` counts ``decode_steps_ahead`` (steps
dispatched before their input tokens were read) and
``decode_steps_dropped`` (those not claimed).

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
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import (expert_counts, forward_chunk, forward_decode,
                          init_cache, prefill_chunk_len)
from repro.models.modules import pdtype

_NO_SPAN = contextlib.nullcontext()


def _greedy(logits):
    """The greedy token of each row's last position, (rows, 1) int32."""
    return jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1,
                      keepdims=True).astype(jnp.int32)


class _Ahead(NamedTuple):
    """A decode step dispatched before the host read its input tokens."""
    params: Any
    parent: Any                   # the cache it was fed
    tokens: jax.Array             # the (n_slots, 1) tokens it was fed
    pos: np.int32
    lanes: Tuple[int, ...]
    out: tuple                    # (logits, cache, greedy tokens)


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
                        "prompt_tokens_stepped": 0, "decode_steps_ahead": 0,
                        "decode_steps_dropped": 0}
        self._tok = None              # greedy tokens of the last model call
        self._ahead: Optional[_Ahead] = None

        def decode_step(p, c, tok, pos):
            logits, c = forward_decode(p, cfg, c, tok, pos)
            return logits, c, _greedy(logits)

        def prefill_chunk(p, c, tok, pos, last, lane):
            logits, c = forward_chunk(p, cfg, c, tok, pos, last, lane)
            return logits, c, _greedy(logits)
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
        cache) and keeping the step's greedy tokens in ``_tok``: a
        (n_slots, 1) token block goes to ``decode_step``, unless the step
        ahead ran exactly that, which is then handed back; one lane's
        (1, C) block, with its ``last`` row and ``lane``, to
        ``prefill_chunk``, whose logits are those of row ``last``.  A step
        ahead that is not handed back is dropped."""
        ahead, self._ahead = self._ahead, None
        if chunk:
            out = self._prefill_chunk(params, cache, tokens, pos, *chunk)
        elif ahead is not None and self._claims(ahead, params, cache,
                                                tokens, pos):
            out, ahead = ahead.out, None
        else:
            out = self._decode_step(params, cache, jnp.asarray(tokens), pos)
        if ahead is not None:
            self._counts["decode_steps_dropped"] += 1
        logits, cache, self._tok = out
        return logits, cache

    def _lanes(self) -> Tuple[int, ...]:
        return tuple(i for i, r in enumerate(self.slots) if r is not None)

    def _inputs(self, lanes):
        """A decode step's host tokens, each busy lane's last token (0
        elsewhere), and its position, the furthest of the lanes'."""
        toks = np.zeros((self.n_slots, 1), np.int32)
        for i in lanes:
            r = self.slots[i]
            toks[i, 0] = r.tokens_out[-1] if r.tokens_out else 0
        # positions go in as strongly typed int32 here, in _run_ahead and
        # in _feed_prompt, so all share one compiled decode program
        return toks, np.int32(max((self.pos[i] for i in lanes), default=0))

    def _claims(self, ahead: _Ahead, params, cache, tokens, pos) -> bool:
        """Whether a decode step on these arguments is the one ahead ran;
        its input tokens were read by the host before this is asked."""
        return (params is ahead.params and cache is ahead.parent
                and pos == ahead.pos and self._lanes() == ahead.lanes
                and np.array_equal(np.asarray(tokens),
                                   np.asarray(ahead.tokens)))

    def _run_ahead(self, lanes):
        """Dispatch the decode step after the model call whose greedy
        tokens ``_tok`` holds for ``lanes``, before the host reads them,
        where those tokens are its whole input and each lane goes on past
        this token whatever it is."""
        if len(lanes) < self.n_slots or not all(
                self.budget[i] > 1 and self.pos[i] < self.max_len - 1
                for i in lanes):
            return
        pos = np.int32(max(self.pos[i] for i in lanes))
        self._ahead = _Ahead(self.params, self.cache, self._tok, pos,
                             tuple(lanes), self._decode_step(
                                 self.params, self.cache, self._tok, pos))
        self._counts["decode_steps_ahead"] += 1

    def _emit_greedy(self, lanes, rows):
        """Read the last model call's greedy tokens and emit row ``rows[j]``
        to lane ``lanes[j]``; then drop the step ahead if what was emitted
        no longer asks for it."""
        got = np.asarray(self._tok)
        for i, row in zip(lanes, rows):
            self._emit(i, int(got[row, 0]))
        ahead = self._ahead
        if ahead is not None and not self._claims(
                ahead, self.params, self.cache, *self._inputs(self._lanes())):
            self._ahead = None
            self._counts["decode_steps_dropped"] += 1

    def _feed_prompt(self, i: int, req: Request):
        with (jax.profiler.TraceAnnotation("serve.prefill") if self.annotate
              else _NO_SPAN):
            P, C = len(req.prompt), self.chunk
            if C:
                # positions, last row and lane go in as strongly typed
                # int32, so every prompt length shares one program
                for start in range(0, P, C):
                    part = np.asarray(req.prompt[start:start + C], np.int32)
                    tok = np.zeros((1, C), np.int32)
                    tok[0, :len(part)] = part
                    _, self.cache = self._decode(
                        self.params, self.cache, tok, np.int32(start),
                        np.int32(len(part) - 1), np.int32(i))
                    self._counts["prefill_chunks"] += 1
                self._counts["prompt_tokens_chunked"] += P
                self.pos[i] = P
                row = 0
            else:
                for t in req.prompt:
                    tok = jnp.full((self.n_slots, 1), int(t), jnp.int32)
                    # only slot i's lane matters; the others decode a dummy
                    # token at slot i's position, which corrupts a busy
                    # lane (one scalar position per batch: ROADMAP queue 2)
                    _, self.cache = self._decode(
                        self.params, self.cache, tok, np.int32(self.pos[i]))
                    self.pos[i] += 1
                self._counts["prompt_tokens_stepped"] += P
                row = i
            # the first token comes from the prompt's last logits; decode
            # steps then feed it back at position len(prompt)
            if P:
                self._run_ahead((i,))
                self._emit_greedy((i,), (row,))

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
            lanes = self._lanes()
            if not lanes:
                return 0
            _, self.cache = self._decode(self.params, self.cache,
                                         *self._inputs(lanes))
            for i in lanes:
                self.pos[i] += 1
            self._run_ahead(lanes)
            self._emit_greedy(lanes, lanes)
            return len(lanes)

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.completed
