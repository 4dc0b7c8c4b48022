"""``ServingEngine`` feeds a prompt in chunks of C tokens to its own lane
through one compiled ``prefill_chunk`` program, where the configuration is
a stack of attention layers (with dense MLPs or experts); other
configurations feed it a token at a time through decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config, list_archs
from repro.models import forward_prefill, init_params, prefill_chunk_len
import repro.models.transformer as transformer
from repro.serving.engine import Request, ServingEngine

MAX_LEN = 128
MAX_NEW = 4
PREFILL = jax.jit(forward_prefill, static_argnums=1)


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ("h2o-danube-1.8b", "gemma3-1b"):
        cfg = get_smoke_config(arch)
        out[arch] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return out


def _prompt(n: int, vocab: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed + n)
    return rng.integers(2, vocab, size=n).astype(np.int32)


def _greedy(params, cfg, prompt, n: int):
    """The greedy continuation of ``prompt``, one prefill a token."""
    seq, out = list(prompt), []
    for _ in range(n):
        logits, _ = PREFILL(params, cfg, {"tokens": jnp.asarray([seq])})
        out.append(int(jnp.argmax(logits[0, -1].astype(jnp.float32))))
        seq.append(out[-1])
    return out


def _serve_alone(cfg, params, prompt, n_slots=2):
    eng = ServingEngine(cfg, params, n_slots=n_slots, max_len=MAX_LEN,
                        eos_id=-1)
    eng.submit(Request(0, prompt, max_new=MAX_NEW))
    return eng, eng.run_until_done()[0].tokens_out


# smoke window 32 and max_len 128, so C = 32: one short of a chunk, one
# chunk, one over, two and a bit, and a prompt longer than the window,
# whose ring wraps inside the prompt (its last chunk's padded rows would
# land on slots that still hold live positions)
@pytest.mark.parametrize("n", [31, 32, 33, 67, 40])
@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "gemma3-1b"])
def test_served_tokens_are_the_greedy_continuation(models, arch, n):
    cfg, params = models[arch]
    prompt = _prompt(n, cfg.vocab_size)
    eng, served = _serve_alone(cfg, params, prompt)
    assert eng.chunk == 32
    assert eng.stats["prefill_chunks"] == -(-n // 32)
    assert served == _greedy(params, cfg, prompt, MAX_NEW)


@pytest.mark.parametrize("n", [40, 67])
@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "gemma3-1b"])
def test_chunks_that_straddle_the_ring_end(models, monkeypatch, arch, n):
    """C = 12 against a ring of 32: the chunk at position 24 writes slots
    24-31 and 0-3; gemma's global layers read their 128 slots in blocks
    of 12 with a shorter last block."""
    monkeypatch.setattr(transformer, "PREFILL_CHUNK", 12)
    cfg, params = models[arch]
    prompt = _prompt(n, cfg.vocab_size, seed=1)
    eng, served = _serve_alone(cfg, params, prompt)
    assert eng.chunk == 12
    assert served == _greedy(params, cfg, prompt, MAX_NEW)


def test_ring_after_a_prompt_longer_than_the_window(models):
    """After a 40-token prompt the lane's ring holds positions 8-39, as
    prefill's own ring does: no padded row of the last chunk (positions
    40-63) was written over slots 8-31."""
    cfg, params = models["h2o-danube-1.8b"]
    prompt = _prompt(40, cfg.vocab_size)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=MAX_LEN, eos_id=-1)
    eng.slots[1] = Request(0, prompt, max_new=MAX_NEW)
    eng.budget[1] = MAX_NEW
    eng._feed_prompt(1, eng.slots[1])
    _, want = forward_prefill(params, cfg, {"tokens": jnp.asarray([prompt])})
    for name in ("k", "v"):
        got = np.asarray(eng.cache["blocks"][0][name][:, 1], np.float32)
        ref = np.asarray(want["blocks"][0][name][:, 0], np.float32)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=0.05 * np.abs(ref).max())


def test_other_lane_is_bit_identical_after_a_prompt(models):
    cfg, params = models["gemma3-1b"]
    eng = ServingEngine(cfg, params, n_slots=2, max_len=MAX_LEN, eos_id=-1)
    eng.submit(Request(0, _prompt(20, cfg.vocab_size), max_new=20))
    eng.step()                                    # lane 0 busy, decoding
    lane0 = jax.tree.map(lambda t: np.asarray(t[..., 0, :, :, :]), eng.cache)
    lane1 = jax.tree.map(lambda t: np.asarray(t[..., 1, :, :, :]), eng.cache)
    eng.submit(Request(1, _prompt(45, cfg.vocab_size), max_new=20))
    eng._admit()                                  # lane 1 takes 45 tokens
    assert eng.slots[1] is not None and eng.stats["prefill_chunks"] == 3
    after0 = jax.tree.map(lambda t: np.asarray(t[..., 0, :, :, :]), eng.cache)
    after1 = jax.tree.map(lambda t: np.asarray(t[..., 1, :, :, :]), eng.cache)
    for a, b in zip(jax.tree.leaves(lane0), jax.tree.leaves(after0)):
        assert np.array_equal(a, b)
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(lane1), jax.tree.leaves(after1)))


def test_prefill_chunk_compiles_once_for_every_prompt_length(models):
    cfg, params = models["h2o-danube-1.8b"]
    eng = ServingEngine(cfg, params, n_slots=1, max_len=MAX_LEN, eos_id=-1)
    for i, n in enumerate((5, 33, 70)):
        eng.submit(Request(i, _prompt(n, cfg.vocab_size), max_new=2))
    assert len(eng.run_until_done()) == 3
    assert eng._prefill_chunk._cache_size() == 1
    assert eng._decode_step._cache_size() == 1


def test_stats_count_chunks_and_tokens(models):
    cfg, params = models["h2o-danube-1.8b"]
    eng = ServingEngine(cfg, params, n_slots=2, max_len=MAX_LEN, eos_id=-1)
    for i, n in enumerate((31, 33, 67)):
        eng.submit(Request(i, _prompt(n, cfg.vocab_size), max_new=2))
    eng.run_until_done()
    assert eng.stats == {"prefill_chunks": 1 + 2 + 3,
                         "prompt_tokens_chunked": 31 + 33 + 67,
                         "prompt_tokens_stepped": 0}


def test_recurrent_config_feeds_a_token_at_a_time():
    cfg = get_smoke_config("recurrentgemma-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=64, eos_id=-1)
    eng.submit(Request(0, _prompt(9, cfg.vocab_size), max_new=2))
    assert len(eng.run_until_done()) == 1
    assert eng.chunk == 0
    assert eng.stats == {"prefill_chunks": 0, "prompt_tokens_chunked": 0,
                         "prompt_tokens_stepped": 9}
    assert eng._prefill_chunk._cache_size() == 0


# attention stacks with dense MLPs or experts take chunks; recurrent
# layers, an encoder (whisper) or an image prefix (paligemma) do not
CHUNKED = {"gemma2-9b": 32, "gemma3-1b": 32, "h2o-danube-1.8b": 32,
           "internlm2-20b": MAX_LEN, "qwen3-moe-235b-a22b": MAX_LEN,
           "arctic-480b": MAX_LEN, "mellum2-12b-a2.5b": 32}


@pytest.mark.parametrize("arch", list_archs())
def test_chunk_rule_follows_the_configuration(arch):
    cfg = get_smoke_config(arch)
    assert prefill_chunk_len(cfg, MAX_LEN) == CHUNKED.get(arch, 0)


def test_int8_cache_feeds_a_token_at_a_time():
    cfg = dataclasses.replace(get_smoke_config("h2o-danube-1.8b"),
                              kv_quant=True)
    assert prefill_chunk_len(cfg, MAX_LEN) == 0
