"""``ServingEngine`` runs one decode step ahead of the host: a step
dispatched before the previous token was read is claimed by the next
``_decode`` call only if it ran exactly what that call asks for, and is
dropped otherwise (an end-of-sequence token, an ``_emit`` that took
another token), leaving no trace in the cache or the counters."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import forward_prefill, init_params
from repro.serving.engine import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from perfbench import common  # noqa: E402

REF = common.load_module("refs", "moe_transformer")
SERVE = common.load_module("runners", "serve")
PREFILL = jax.jit(forward_prefill, static_argnums=1)
MAX_LEN = 128


def _model(arch):
    cfg = get_smoke_config(arch)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _prompt(n: int, vocab: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed + n)
    return rng.integers(2, vocab, size=n).astype(np.int32)


def _serve(cfg, params, prompts, max_new, n_slots=1, eos_id=-1):
    eng = ServingEngine(cfg, params, n_slots=n_slots, max_len=MAX_LEN,
                        eos_id=eos_id)
    reqs = [Request(i, p, max_new=max_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    return eng, [r.tokens_out for r in reqs]


def _in_order(monkeypatch):
    """Engines made after this run no step ahead."""
    monkeypatch.setattr(ServingEngine, "_run_ahead", lambda self, lanes: None)


def _first_new(tokens, start=2):
    """The first index from ``start`` whose token the output has not
    taken before."""
    return next(k for k in range(start, len(tokens))
                if tokens[k] not in tokens[:k])


def _same_tree(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("arch,n_slots", [("h2o-danube-1.8b", 1),
                                          ("h2o-danube-1.8b", 2),
                                          ("recurrentgemma-2b", 1)])
def test_each_token_is_the_argmax_of_the_logits_before_it(
        arch, n_slots, monkeypatch):
    """Wrapped as the benchmark's ``Recorder`` wraps them: the token
    handed to ``_emit`` is the greedy token of the logits the latest
    ``_decode`` call returned (row 0 of a chunk's, the lane's row of a
    decode step's).  The tokens and the final cache equal those of an
    engine that runs nothing ahead, and with one slot every decode step
    was dispatched ahead and claimed."""
    cfg, params = _model(arch)
    prompts = [_prompt(n, cfg.vocab_size) for n in (33, 20, 45)]
    eng = ServingEngine(cfg, params, n_slots=n_slots, max_len=MAX_LEN,
                        eos_id=-1)
    last, pairs = {}, []
    decode, emit = eng._decode, eng._emit

    def decode_kept(*args):
        logits, cache = decode(*args)
        last["logits"], last["chunk"] = logits, len(args) > 4
        return logits, cache

    def emit_checked(i, t):
        row = 0 if last["chunk"] else i
        want = np.argmax(np.asarray(last["logits"][row, -1], np.float32))
        pairs.append((t, int(want)))
        emit(i, t)
    eng._decode, eng._emit = decode_kept, emit_checked
    reqs = [Request(i, p, max_new=6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert len(pairs) == 6 * len(prompts)
    assert all(t == want for t, want in pairs)

    _in_order(monkeypatch)
    ref, ref_tokens = _serve(cfg, params, prompts, 6, n_slots)
    assert [r.tokens_out for r in reqs] == ref_tokens
    assert _same_tree(eng.cache, ref.cache)
    assert ref.stats["decode_steps_ahead"] == 0
    stats = eng.stats
    assert stats["decode_steps_ahead"] > 0
    assert stats["decode_steps_dropped"] == 0
    if n_slots == 1:
        # every decode step after the first token of each request
        assert stats["decode_steps_ahead"] == 5 * len(prompts)
    assert eng._decode_step._cache_size() == 1


def test_end_of_sequence_drops_the_step_ahead():
    """The greedy token at step k is made the end-of-sequence id: the
    output stops there, the one step dispatched past it is dropped, and
    the cache is bit for bit that of an engine whose request ends at the
    same token by its budget, which ran nothing past it."""
    cfg, params = _model("h2o-danube-1.8b")
    prompt = _prompt(33, cfg.vocab_size)
    _, (free,) = _serve(cfg, params, [prompt], 12)
    k = _first_new(free)
    eos, (cut,) = _serve(cfg, params, [prompt], 12, eos_id=free[k])
    assert cut == free[:k + 1]
    budget, (same,) = _serve(cfg, params, [prompt], k + 1)
    assert same == cut
    assert eos.stats["decode_steps_dropped"] == 1
    assert eos.stats["decode_steps_ahead"] == k + 1
    assert budget.stats["decode_steps_dropped"] == 0
    assert budget.stats["decode_steps_ahead"] == k
    assert eos._ahead is None
    assert _same_tree(eos.cache, budget.cache)


def test_an_altered_token_drops_the_step_ahead(monkeypatch):
    """``_emit`` takes another token than the device chose at index 2:
    the step dispatched on the device's token is dropped, and every
    served token after it is the greedy continuation of the altered
    sequence."""
    cfg, params = _model("h2o-danube-1.8b")
    prompt = _prompt(33, cfg.vocab_size)
    emit = ServingEngine._emit

    def altered(self, i, t):
        if len(self.slots[i].tokens_out) == 2:
            t = (t + 1) % self.cfg.vocab_size
        emit(self, i, t)
    monkeypatch.setattr(ServingEngine, "_emit", altered)
    eng, (served,) = _serve(cfg, params, [prompt], 6)
    assert eng.stats["decode_steps_dropped"] == 1
    seq = list(prompt)
    for j, t in enumerate(served):
        logits, _ = PREFILL(params, cfg, {"tokens": jnp.asarray([seq])})
        greedy = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        if j == 2:
            greedy = (greedy + 1) % cfg.vocab_size
        assert t == greedy
        seq.append(t)


def test_expert_counters_skip_the_dropped_step():
    """Mellum 2 at the smoke size, top 2 of 8 with experts 4-7 held (the
    routed-experts kernel at decode), ended by an end-of-sequence token:
    the counters are those of the served positions alone, as
    ``tests/test_moe_held_share.py`` counts them, though one more step
    ran on the device."""
    base = get_smoke_config("mellum2-12b-a2.5b")
    moe = dataclasses.replace(base.moe, experts_per_token=2, n_held=4,
                              first_held=4)
    cfg = dataclasses.replace(base, moe=moe, param_dtype="float32")
    params = SERVE.make_weights(cfg, 2**33 + 7)
    prompt = np.random.default_rng(4).integers(
        2, cfg.vocab_size, 45).astype(np.int32)
    _, (free,) = _serve(cfg, params, [prompt], 8)
    k = _first_new(free)
    eng, (toks,) = _serve(cfg, params, [prompt], 8, eos_id=free[k])
    assert toks == free[:k + 1]
    assert eng.stats["decode_steps_dropped"] == 1
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    per_pos = REF.held_pairs(params, dataclasses.asdict(cfg), seq)
    stats = eng.stats
    P = len(prompt)
    assert stats["expert_rows_decode"] == per_pos[P:].sum() > 0
    assert stats["expert_blocks_decode"] == per_pos[P:].sum()
    assert stats["expert_rows_prefill"] == per_pos[:P].sum()
