"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass at the smoke config (the chip runs them at full width)."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_smoke_config

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.check_device()
    assert "platform=cpu" in capsys.readouterr().out


def test_serve_phase_at_smoke_config(chip_smoke):
    rep = chip_smoke.serve_phase(get_smoke_config("gemma3-1b"),
                                 jax.devices()[0])
    assert rep["requests"] == chip_smoke.N_REQUESTS + 1
    assert rep["decode_compiles"] == 1
    assert rep["logits_max_abs_diff"] == 0.0     # exact on the CPU
    assert rep["alone_argmax_agree"] == rep["alone_tokens"] > 1


def test_kernel_phase_at_small_widths(chip_smoke):
    """The chip's kernel checks, at small widths in the interpreter."""
    small = {
        "flash_attention_f32": dict(dtype=jnp.float32, BK=1, S=256, G=2,
                                    hd=128, window=128),
        "flash_attention_bf16": dict(dtype=jnp.bfloat16, BK=1, S=256, G=2,
                                     hd=128, window=128),
        "rglru_scan": dict(B=1, S=256, C=256),
        "wkv6": dict(BH=2, S=128, hd=64),
    }
    assert set(small) == set(chip_smoke.KERNEL_CASES)
    cases = {name: (build, small[name], tol)
             for name, (build, _, tol) in chip_smoke.KERNEL_CASES.items()}
    rep = chip_smoke.kernel_phase(jax.devices()[0], cases)
    for name, k in rep.items():
        assert 0.0 <= k["frac"] <= k["tol"], name


def test_flood_phase_keeps_outputs_on_the_device(chip_smoke):
    rep = chip_smoke.flood_phase(jax.devices()[0],
                                 payload_bytes=64 * 32 * 32 * 4, runs=1)
    assert rep["frames"] == 64
    for strategy in ("databelt", "stateless"):
        assert rep[strategy]["instances"] == 1
        assert rep[strategy]["device_arrays"] > 0


def test_compile_cache_is_placed_from_outside(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/deployment")
        assert compile_cache.enable_compile_cache() == "/set/by/deployment"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = SMOKE.parent / ".jax_cache"
        assert compile_cache.enable_compile_cache() == str(fixed)
        assert jax.config.jax_compilation_cache_dir == str(fixed)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
