"""Assigned-architecture configs: exact spec compliance + param counts."""
import pytest

from repro.configs.base import (LM_SHAPES, LONG_CONTEXT_OK, get_config,
                                get_smoke_config, list_archs, shapes_for)

EXPECTED = {
    # arch: (layers, d_model, heads, kv, d_ff, vocab)
    "paligemma-3b": (18, 2048, 8, 1, 16384, 257_216),
    "whisper-small": (12, 768, 12, 12, 3072, 51_865),
    "gemma3-1b": (26, 1152, 4, 1, 6912, 262_144),
    "gemma2-9b": (42, 3584, 16, 8, 14336, 256_000),
    "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32_000),
    "internlm2-20b": (48, 6144, 48, 8, 16384, 92_544),
    "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151_936),
    "arctic-480b": (35, 7168, 56, 8, 4864, 32_000),
    "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256_000),
    "rwkv6-7b": (32, 4096, 64, 64, 14336, 65_536),
    "mellum2-12b-a2.5b": (28, 2304, 32, 4, 7168, 98_304),
}

PARAM_RANGES = {  # total params (billions), generous bounds
    "paligemma-3b": (2.0, 4.0),
    "whisper-small": (0.15, 0.5),
    "gemma3-1b": (0.7, 1.6),
    "gemma2-9b": (7.0, 12.0),
    "h2o-danube-1.8b": (1.3, 2.4),
    "internlm2-20b": (15.0, 25.0),
    "qwen3-moe-235b-a22b": (180.0, 260.0),
    "arctic-480b": (400.0, 540.0),
    "recurrentgemma-2b": (1.8, 3.4),
    "rwkv6-7b": (5.0, 9.0),
    "mellum2-12b-a2.5b": (11.0, 13.5),
}


def test_all_archs_registered():
    assert sorted(list_archs()) == sorted(EXPECTED)


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_exact_spec(arch):
    cfg = get_config(arch)
    L, d, h, kv, ff, v = EXPECTED[arch]
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.n_heads == h
    assert cfg.n_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v


@pytest.mark.parametrize("arch", sorted(PARAM_RANGES))
def test_param_count(arch):
    cfg = get_config(arch)
    lo, hi = PARAM_RANGES[arch]
    n = cfg.param_count() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo},{hi}]"


def test_moe_active_params():
    cfg = get_config("qwen3-moe-235b-a22b")
    active = cfg.param_count(active_only=True) / 1e9
    assert 15.0 <= active <= 30.0, active


def test_layer_pattern_coverage():
    for arch in list_archs():
        cfg = get_config(arch)
        kinds = cfg.layer_kinds()
        assert len(kinds) == cfg.n_layers
        assert cfg.n_superblocks * cfg.pattern_len + cfg.n_tail \
            == cfg.n_layers


def test_shape_assignment():
    assert set(LM_SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                              "long_500k"}
    for arch in list_archs():
        names = {s.name for s in shapes_for(arch)}
        if arch in LONG_CONTEXT_OK:
            assert "long_500k" in names
        else:
            assert "long_500k" not in names


def test_smoke_configs_reduced():
    for arch in list_archs():
        cfg = get_smoke_config(arch)
        assert cfg.d_model <= 128 and cfg.vocab_size <= 1024
        assert cfg.param_count() < 5e6
