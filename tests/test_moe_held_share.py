"""The expert layer that holds a share of the experts (``moe_held``),
YaRN's rotary tables, and Mellum 2 served through ``ServingEngine``
against the plain reference ``perfbench/refs/moe_transformer.py``, on
seeded random weights at small sizes."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import YarnConfig, get_config, get_smoke_config
from repro.models import init_params
from repro.models import moe as M
from repro.models.modules import activation, mlp, rope, rope_freqs
from repro.serving.engine import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from perfbench import common  # noqa: E402

REF = common.load_module("refs", "moe_transformer")
SERVE = common.load_module("runners", "serve")
MELLUM = "mellum2-12b-a2.5b"


def _cfg(arch=MELLUM, k=4, n_held=0, first_held=0, dtype="float32"):
    """The smoke configuration, 8 experts, top-``k``, in ``dtype``."""
    cfg = get_smoke_config(arch)
    moe = dataclasses.replace(cfg.moe, experts_per_token=k, n_held=n_held,
                              first_held=first_held)
    return dataclasses.replace(cfg, moe=moe, param_dtype=dtype)


def _layer_params(cfg, seed=0):
    return M.init_moe(jax.random.PRNGKey(seed), cfg)


def _x(cfg, T=24, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T // 2, cfg.d_model),
                             jnp.float32).astype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
def _pair_loop(p, x, cfg, valid=None):
    """Every (token, choice) pair in a Python loop, from the routing."""
    m = cfg.moe
    xf = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(jnp.asarray(xf) @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, m.experts_per_token)
    top = np.asarray(top / top.sum(-1, keepdims=True))
    idx = np.asarray(idx)
    ok = np.ones(len(xf), bool) if valid is None \
        else np.asarray(valid).reshape(-1)
    act = activation(cfg.act)
    w = {n: np.asarray(p[n], np.float32) for n in ("w_gate", "w_up",
                                                    "w_down")}
    y = np.zeros_like(xf)
    for t in range(len(xf)):
        for g, e in zip(top[t], idx[t]):
            e = int(e) - m.first_held
            if ok[t] and 0 <= e < m.held:
                h = np.asarray(act(jnp.asarray(xf[t] @ w["w_gate"][e]))) \
                    * (xf[t] @ w["w_up"][e])
                y[t] += g * (h @ w["w_down"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("n_held,first_held", [(0, 0), (4, 0), (4, 4),
                                               (3, 2)])
def test_held_share_is_every_pair_of_its_experts(n_held, first_held):
    """float32 weights: the layer and the loop differ by summation order
    only (rtol 1e-5 of the largest output)."""
    cfg = _cfg(n_held=n_held, first_held=first_held)
    p = _layer_params(cfg)
    assert p["w_gate"].shape[0] == cfg.moe.held
    assert p["router"].shape == (cfg.d_model, 8)
    x = _x(cfg)
    y, aux = M.moe_held(p, x, cfg)
    want = _pair_loop(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), want,
                               atol=1e-5 * np.abs(want).max())
    assert np.isfinite(float(aux))


def test_padding_rows_carry_no_weight():
    cfg = _cfg(n_held=4, first_held=4)
    p = _layer_params(cfg)
    x = _x(cfg)
    valid = jnp.arange(12)[None, :] < jnp.asarray([[7], [12]])
    y, _ = M.moe_held(p, x, cfg, valid)
    want = _pair_loop(p, x, cfg, valid)
    assert np.all(np.asarray(y)[0, 7:] == 0)
    np.testing.assert_allclose(np.asarray(y), want,
                               atol=1e-5 * np.abs(want).max())
    rows, routed = M.held_rows(p, x, cfg, valid)
    _, idx = jax.lax.top_k(x.reshape(-1, cfg.d_model) @ p["router"], 4)
    held = (np.asarray(idx) >= 4) & np.asarray(valid).reshape(-1, 1)
    assert int(rows) == held.sum() and int(routed) == 19 * 4


@pytest.mark.parametrize("arch", [MELLUM, "arctic-480b"])
def test_two_shares_sum_to_the_whole_layer(arch):
    """Experts 0-3 on one chip and 4-7 on the other, the router whole on
    both: their parts add up to the uncut layer, with arctic's dense
    residual, which both compute alike, counted once."""
    whole = _cfg(arch)
    p = _layer_params(whole)
    x = _x(whole)
    y, _ = M.moe_held(p, x, whole)
    parts = []
    for first in (0, 4):
        share = _cfg(arch, n_held=4, first_held=first)
        sliced = {n: (v[first:first + 4] if n.startswith("w_") else v)
                  for n, v in p.items()}
        parts.append(M.moe_held(sliced, x, share)[0])
    total = parts[0] + parts[1]
    if "dense" in p:
        total = total - mlp(p["dense"], x, whole.act)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y),
                               atol=1e-5 * float(jnp.abs(y).max()))


def test_layer_drops_no_token_where_the_oracle_does():
    """All tokens routed to one expert: ``moe_dense`` drops those past its
    capacity, the held layer computes them all."""
    cfg = _cfg(k=1)
    p = dict(_layer_params(cfg))
    p["router"] = jnp.zeros_like(p["router"]).at[:, 5].set(1.0)
    x = jnp.abs(_x(cfg)) + 0.1
    y, _ = M.moe_held(p, x, cfg)
    dropped, _ = M.moe_dense(p, x, cfg)
    assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)
    assert np.any(np.abs(np.asarray(dropped)).sum(-1) == 0)
    np.testing.assert_allclose(np.asarray(y), _pair_loop(p, x, cfg),
                               atol=1e-5 * float(jnp.abs(y).max()))


def test_held_layer_has_gradients():
    cfg = _cfg(n_held=4, first_held=2)
    p = _layer_params(cfg)
    x = _x(cfg)
    g = jax.grad(lambda p: jnp.sum(M.moe_held(p, x, cfg)[0] ** 2))(p)
    assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(g))
    assert float(jnp.abs(g["w_down"]).sum()) > 0


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
MELLUM_YARN = get_config(MELLUM).rope_yarn


def test_yarn_tables_follow_the_formula():
    """Mellum 2's full layers: theta 500000, head 128, factor 16 over 8192
    positions, beta 32 / 1: the ramp runs from dimension 18 to 35."""
    hd, theta = 128, 500_000.0
    freq, scale = rope_freqs(hd, theta, MELLUM_YARN)
    low = math.floor(hd * math.log(8192 / (2 * math.pi * 32))
                     / (2 * math.log(theta)))
    high = math.ceil(hd * math.log(8192 / (2 * math.pi * 1))
                     / (2 * math.log(theta)))
    assert (low, high) == (18, 35)
    for i in range(hd // 2):
        extrap = theta ** (-2 * i / hd)
        m = 1 - min(max((i - low) / (high - low), 0.0), 1.0)
        want = extrap / 16 * (1 - m) + extrap * m
        assert freq[i] == pytest.approx(want, rel=1e-6)
    assert scale == pytest.approx(0.1 * math.log(16) + 1)
    plain, _ = rope_freqs(hd, theta)
    assert np.array_equal(freq[:low + 1], plain[:low + 1])
    np.testing.assert_allclose(freq[high:], plain[high:] / 16, rtol=1e-6)


def test_yarn_at_factor_one_is_plain_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 4, 128))
    pos = jnp.broadcast_to(jnp.arange(1000, 1040), (2, 40))
    flat = YarnConfig(factor=1.0, original_max_position_embeddings=8192,
                      attention_factor=1.0)
    np.testing.assert_allclose(np.asarray(rope(x, pos, 500_000.0, flat)),
                               np.asarray(rope(x, pos, 500_000.0)),
                               rtol=1e-6, atol=1e-6)
    scaled = rope(x, pos, 500_000.0, MELLUM_YARN)
    assert not np.allclose(np.asarray(scaled), np.asarray(
        rope(x, pos, 500_000.0)))


# ---------------------------------------------------------------------------
# served through the engine, against the plain reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """Mellum 2 at the smoke size, experts 4-7 of 8 held, top-4, float32
    weights of the benchmark's scales; window 32 and C = 32, so a
    70-token prompt is three chunks and wraps the sliding rings."""
    cfg = _cfg(n_held=4, first_held=4)
    params = SERVE.make_weights(cfg, 2**33 + 5)
    rng = np.random.default_rng(3)
    out = {}
    for P in (21, 70):
        eng = ServingEngine(cfg, params, n_slots=1, max_len=128, eos_id=-1)
        assert eng.chunk == 32
        got = []
        decode = eng._decode

        def keep(*a, decode=decode, got=got):
            logits, cache = decode(*a)
            got.append(np.asarray(logits[0, -1], np.float32))
            return logits, cache
        eng._decode = keep
        prompt = rng.integers(2, cfg.vocab_size, P).astype(np.int32)
        eng.submit(Request(0, prompt, max_new=5))
        req = eng.run_until_done()[0]
        out[P] = (eng, prompt, req.tokens_out, got)
    return cfg, params, out


@pytest.mark.parametrize("P", [21, 70])
def test_served_logits_match_the_reference(served, P):
    """Chunks, then decode through the cache, against the reference's one
    pass over prompt + served tokens.  float32 weights (the cache in
    float32 too): the two differ by summation order, ~1e-5 of logits of
    ~3; 1e-3 leaves room for that and none for a wrong rotary table, a
    lost expert or a window off by one (each moves logits by >1e-2)."""
    cfg, params, out = served
    eng, prompt, toks, got = out[P]
    chunks = -(-P // 32)
    assert len(got) == chunks + len(toks) - 1
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.asarray(REF.logits(params, dataclasses.asdict(cfg), seq))[P - 1:]
    served_logits = np.stack([got[chunks - 1]] + got[chunks:])
    assert served_logits.shape == want.shape
    np.testing.assert_allclose(served_logits, want, atol=1e-3)
    assert list(want.argmax(-1)) == toks


@pytest.mark.parametrize("P", [21, 70])
def test_expert_counters_match_the_reference_routing(served, P):
    cfg, params, out = served
    eng, prompt, toks, _ = out[P]
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    per_pos = REF.held_pairs(params, dataclasses.asdict(cfg), seq)
    stats = eng.stats
    assert stats["prompt_tokens_stepped"] == 0
    assert stats["prompt_tokens_chunked"] == P
    assert stats["expert_rows_prefill"] == per_pos[:P].sum()
    assert stats["expert_rows_decode"] == per_pos[P:].sum()
    assert stats["expert_pairs_routed"] == len(seq) * 4 * cfg.n_layers
    # about half the routed pairs land on the half of the experts held
    share = per_pos.sum() / stats["expert_pairs_routed"]
    assert 0.25 < share < 0.75


@pytest.mark.parametrize("P", [21, 70])
def test_dense_decode_reads_every_held_expert(served, P):
    """Top 4 with 4 held: a decode token's pairs could reach every held
    expert, so decode takes the dense form and counts all 4 read."""
    cfg, _, out = served
    eng, _, toks, _ = out[P]
    assert not M.reads_routed(1, cfg)
    assert eng.stats["expert_blocks_decode"] \
        == (len(toks) - 1) * cfg.n_layers * cfg.moe.held


@pytest.fixture(scope="module")
def served_routed():
    """As ``served``, at top 2 of 8 with experts 4-7 held: a decode
    token's 2 pairs cannot reach all 4, so decode takes the kernel."""
    cfg = _cfg(k=2, n_held=4, first_held=4)
    assert M.reads_routed(1, cfg) and not M.reads_routed(32, cfg)
    params = SERVE.make_weights(cfg, 2**33 + 7)
    eng = ServingEngine(cfg, params, n_slots=1, max_len=128, eos_id=-1)
    got = []
    decode = eng._decode

    def keep(*a):
        logits, cache = decode(*a)
        got.append(np.asarray(logits[0, -1], np.float32))
        return logits, cache
    eng._decode = keep
    prompt = np.random.default_rng(4).integers(
        2, cfg.vocab_size, 45).astype(np.int32)
    eng.submit(Request(0, prompt, max_new=6))
    req = eng.run_until_done()[0]
    return cfg, params, eng, prompt, req.tokens_out, got


def test_routed_decode_logits_match_the_reference(served_routed):
    """The kernel through the engine (two chunks, then five decode steps
    through the cache) against the reference's one pass, as
    ``test_served_logits_match_the_reference``."""
    cfg, params, _, prompt, toks, got = served_routed
    P = len(prompt)
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = np.asarray(REF.logits(params, dataclasses.asdict(cfg), seq))[P - 1:]
    np.testing.assert_allclose(np.stack(got[1:]), want, atol=1e-3)
    assert list(want.argmax(-1)) == toks


def test_expert_blocks_match_the_reference_routing(served_routed):
    """One token a step: the distinct held experts the kernel reads are
    that token's held choices, summed over layers and steps, which is
    ``expert_rows_decode``."""
    cfg, params, eng, prompt, toks, _ = served_routed
    P = len(prompt)
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    per_pos = REF.held_pairs(params, dataclasses.asdict(cfg), seq)
    stats = eng.stats
    assert stats["expert_blocks_decode"] == per_pos[P:].sum() > 0
    assert stats["expert_blocks_decode"] == stats["expert_rows_decode"]
    assert stats["expert_blocks_decode"] \
        < (len(toks) - 1) * cfg.n_layers * cfg.moe.held


def test_held_blocks_count_shared_experts_once():
    """Three tokens, two of them alike: the blocks read are the distinct
    held experts of their routing, fewer than the held pairs."""
    cfg = _cfg(k=2)
    p = _layer_params(cfg)
    x = _x(cfg, T=6)[:1, :3]
    x = x.at[0, 2].set(x[0, 0])
    assert M.reads_routed(3, cfg)
    _, idx = jax.lax.top_k(x.reshape(-1, cfg.d_model) @ p["router"], 2)
    want = len(set(np.asarray(idx).ravel().tolist()))
    rows, _ = M.held_rows(p, x, cfg)
    assert int(M.held_blocks(p, x, cfg)) == want < int(rows) == 6


def test_counters_carry_past_32_bits():
    from repro.models.transformer import (EXPERT_COUNTS, _count_experts,
                                          expert_counts)
    c = jnp.zeros((len(EXPERT_COUNTS), 2), jnp.uint32).at[0, 0].set(2**32 - 3)
    c = _count_experts(c, 0, jnp.int32(5), jnp.int32(7))
    cache = {"blocks": ({"experts": c[None]},), "tail": ({"experts": c},)}
    got = expert_counts(cache)
    assert got["expert_rows_prefill"] == 2 * (2**32 + 2)
    assert got["expert_pairs_routed"] == 14 and got["expert_rows_decode"] == 0


def test_dense_model_has_no_expert_counters():
    cfg = get_smoke_config("h2o-danube-1.8b")
    eng = ServingEngine(cfg, init_params(jax.random.PRNGKey(0), cfg),
                        n_slots=1, max_len=64)
    assert set(eng.stats) == {"prefill_chunks", "prompt_tokens_chunked",
                              "prompt_tokens_stepped"}


def test_benchmark_share_has_its_published_size():
    """20 of 28 layers and 32 of 64 experts: 4.84 B parameters; the whole
    model 12.15 B, 2.44 B active."""
    whole = get_config(MELLUM)
    assert whole.param_count() / 1e9 == pytest.approx(12.15, abs=0.01)
    assert whole.param_count(active_only=True) / 1e9 == pytest.approx(
        2.44, abs=0.01)
    share = dataclasses.replace(whole, n_layers=20, moe=dataclasses.replace(
        whole.moe, n_held=32))
    assert share.param_count() / 1e9 == pytest.approx(4.84, abs=0.01)
    assert share.layer_kinds().count("global") == 5
