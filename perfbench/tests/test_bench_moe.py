"""The benchmark's pieces for a configuration of sparse experts: the work
counted for its roofline shares, its plain reference against the program
at a tiny size on the CPU, its configuration file, and the ``azure-code``
traffic mix."""
import json
import time

import jax
import numpy as np
import pytest

from perfbench import common, traffic, work_moe

DATA = common.BENCH_DIR / "tests" / "data"
SERVE = common.load_module("runners", "serve")
REF = common.load_module("refs", "moe_transformer")
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
TINY = json.loads((DATA / "tiny-moe.json").read_text())
MIX = json.loads((common.BENCH_DIR / "traffic" / "azure-code.json")
                 .read_text())
MELLUM = json.loads((common.BENCH_DIR / "configs" /
                     "mellum2-12b-a2.5b-ep2.json").read_text())

# hand counts for tiny-moe: d 64, 4 heads over 2 of 16, vocab 512, 4
# layers (local, global) x 2 with window 16; 8 experts of 32, top-4, 4 held
ATTN = 64 * 64 + 2 * 64 * 32 + 64 * 64
ROUTER = 64 * 8
EXPERT = 3 * 64 * 32
ROWS = 4 * 4 / 8                              # held rows a token: 2
HEAD = 512 * 64
FIXED = (4 * ATTN + HEAD) * 2 + (4 * ROUTER + 9 * 64 + 2 * 4 * 16) * 4
KV = 2 * 2 * 16 * 2                           # K and V of a position, bf16


def test_work_of_a_decode_token():
    assert work_moe.held_rows_per_token(TINY) == ROWS == 2
    got = work_moe.decode_token(TINY, 10)
    per_token = ATTN + ROUTER + ROWS * EXPERT
    assert got["flops"] == 2 * (4 * per_token + HEAD) + 4 * 4 * 16 * 10 * 4
    assert got["bytes"] == (FIXED + 64 * 2 + 4 * ROWS * EXPERT * 2
                            + KV * 10 * 4 + KV * 4)
    # past the window the local layers read 16 positions, the global 40
    far = work_moe.decode_token(TINY, 40)
    assert far["bytes"] - got["bytes"] == KV * (2 * 6 + 2 * 30)


def test_work_of_a_prompt():
    got = work_moe.prompt(TINY, 40)
    causal = 2 * (16 * 17 // 2 + 24 * 16) + 2 * (40 * 41 // 2)
    per_token = ATTN + ROUTER + ROWS * EXPERT
    assert got["flops"] == 2 * 40 * 4 * per_token + 2 * HEAD \
        + 4 * 4 * 16 * causal
    assert got["bytes"] == (FIXED + 40 * 64 * 2 + 4 * 4 * EXPERT * 2
                            + 40 * 4 * KV)
    # each held expert is read once a 512-token chunk
    two = work_moe.prompt(TINY, 600)
    assert two["bytes"] - got["bytes"] == (560 * 64 * 2 + 4 * 4 * EXPERT * 2
                                           + 560 * 4 * KV)


def test_mellum_share_reads_about_2_gb_a_decode_token():
    """Attention and head 1.30 GB, 4 expected held experts a layer 0.99
    GB: ~2.3 GB at 4096 positions; all 32 held would be ~9.2 GB."""
    tok = work_moe.decode_token(MELLUM, 4096)
    assert 2.25e9 < tok["bytes"] < 2.4e9
    assert work_moe.held_rows_per_token(MELLUM) == 4


def _serve(seed=2**33 + 3):
    return SERVE.run(TINY, json.loads((DATA / "tiny-serving.json").read_text()),
                     seed, 0.5, False, jax.devices(), time.perf_counter(),
                     REF, PEAK)


def test_reference_agrees_with_the_program_and_the_control_does_not():
    """float32 weights in the tiny file, so program and reference differ
    by summation order (~2e-6, limits 1e-3); int8 and fp8 weights move the
    logits by 0.09 or more (CPU, at three seeds)."""
    out = _serve()
    assert out["checks"].correct, out["checks"].items
    assert out["checks"].items["logit_err"]["value"] < 1e-4
    params, picked = out["kept"]
    for quant in ("int8", "fp8"):
        ch = SERVE.check(params, TINY, picked, {}, REF, quant=quant)
        assert not ch.correct, (quant, ch.items)
    for name in ("prefill_mfu.moe", "decode_mfu.moe"):
        assert 0 < common.load_module("metrics", name).read(out["run"]) < 100


def test_moe_readers_leave_out_a_dense_configuration():
    run = SERVE.ServeRun("serve", {"n_layers": 1}, PEAK, [])
    for name in ("prefill_mfu.moe", "decode_mfu.moe"):
        assert common.load_module("metrics", name).read(run) is None


def test_mellum_file_builds_the_stated_cut():
    """The catalog's keys as run here agree with the program's keys."""
    cfg = SERVE.model_config(MELLUM)
    assert cfg.n_layers == MELLUM["num_hidden_layers"] == 20
    assert cfg.layer_kinds().count("local") == 15
    assert [("local" if t.startswith("sliding") else "global")
            for t in MELLUM["layer_types"]] == cfg.layer_kinds()
    assert cfg.d_model == MELLUM["hidden_size"]
    assert cfg.n_heads == MELLUM["num_attention_heads"]
    assert cfg.n_kv_heads == MELLUM["num_key_value_heads"]
    assert cfg.window_size == MELLUM["sliding_window"]
    assert cfg.moe.held == MELLUM["num_experts"] == 32
    assert cfg.moe.n_experts == 64
    assert cfg.moe.experts_per_token == MELLUM["num_experts_per_tok"]
    assert cfg.moe.d_ff_expert == MELLUM["moe_intermediate_size"]
    full = MELLUM["rope_parameters"]["full_attention"]
    assert cfg.rope_theta == full["rope_theta"]
    assert cfg.rope_yarn.factor == full["factor"]
    assert cfg.rope_yarn.attention_factor == full["attention_factor"]
    assert cfg.param_count() / 1e9 == pytest.approx(4.84, abs=0.01)


def test_azure_code_mix():
    """Splitwise's code trace: prompt median 1500, output median 13; the
    longest prompt first."""
    want = [[2996, 12], [1666, 4], [751, 24], [1351, 48], [2101, 8],
            [1071, 14]]
    assert MIX["requests"] == want
    it = traffic.serving_requests(MIX, 98_304, 2**40)
    got = [next(it) for _ in range(7)]
    assert [(len(p), k) for p, k in got] == [tuple(r) for r in want] + [
        (2996, 12)]
    assert np.median([p for p, _ in want]) == pytest.approx(1500, abs=10)
    assert np.median([k for _, k in want]) == 13
    assert max(p for p, _ in want) == want[0][0]
