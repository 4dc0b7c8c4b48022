"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
only described, so this catches what Mosaic or XLA:TPU would refuse
(tiling, unsupported primitives, device memory) at no chip time.  Nothing
runs: results and times come only from a chip run (``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # the process is on the CPU, where kernels inside a model step
        # would take the interpreter: compile them with Mosaic here
        from repro.kernels.routed_experts import kernel as routed
        mp.setattr(routed, "resolve_interpret",
                   lambda interpret: bool(interpret))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one, so keep the cache out of it
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_at_gemma3_1b_widths(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    # gemma3-1b: 4 query heads over 1 kv head, head_dim 256, window 512
    B, S, K, G, hd = 2, 1024, 1, 4, 256
    q = _spec(one_chip, (B, S, K, G, hd), jnp.bfloat16)
    kv = _spec(one_chip, (B, S, K, hd), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_attention(
        q, k, v, window=512, scale=hd ** -0.5, bq=128, bk=128,
        interpret=False), q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_rglru_scan_compiles_at_recurrentgemma_2b_width(one_chip):
    from repro.kernels.rglru_scan.ops import rglru_scan
    a = _spec(one_chip, (2, 1024, 2560))          # d_rnn = 2560
    c = _compile(lambda a, b: rglru_scan(a, b, interpret=False), a, a)
    assert "tpu_custom_call" in c.as_text()


def test_wkv6_compiles_at_rwkv6_7b_head_width(one_chip):
    from repro.kernels.rwkv6_chunk.ops import wkv6
    B, S, H, hd = 1, 512, 64, 64                  # 64 heads of 64
    x = _spec(one_chip, (B, S, H, hd))
    u = _spec(one_chip, (H, hd))
    c = _compile(lambda r, k, v, w, u: wkv6(r, k, v, w, u, interpret=False),
                 x, x, x, x, u)
    assert "tpu_custom_call" in c.as_text()


def test_gemma3_1b_decode_step_compiles_at_engine_shapes(one_chip):
    """The engine's own jitted decode at full width, from parameter shapes
    (``jax.eval_shape``), so no 2 GB of weights are made here."""
    from repro.configs.base import get_config
    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    cfg = get_config("gemma3-1b")
    eng = ServingEngine(cfg, None, n_slots=4, max_len=128)
    on_chip = lambda t: _spec(one_chip, t.shape, t.dtype)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, eng.cache)
    c = eng._decode_step.lower(params, cache,
                               _spec(one_chip, (4, 1), jnp.int32),
                               _spec(one_chip, (), jnp.int32)).compile()
    mem = c.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights > 1.5e9
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_danube_prefill_chunk_compiles_at_engine_shapes(one_chip):
    """The engine's prompt chunk for h2o-danube-1.8b at full width, at the
    benchmark's engine shapes (one slot, max_len 8192: a 512-token chunk
    against the 4096-position ring), within one chip's memory."""
    from repro.configs.base import get_config
    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    cfg = get_config("h2o-danube-1.8b")
    eng = ServingEngine(cfg, None, n_slots=1, max_len=8192)
    assert eng.chunk == 512
    on_chip = lambda t: _spec(one_chip, t.shape, t.dtype)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, eng.cache)
    i32 = _spec(one_chip, (), jnp.int32)
    c = eng._prefill_chunk.lower(
        params, cache, _spec(one_chip, (1, eng.chunk), jnp.int32),
        i32, i32, i32).compile()
    mem = c.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights > 3.5e9
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES


def _mellum_share(one_chip):
    """(engine, parameter shapes, cache shapes, weight bytes) of the
    benchmark's ``mellum2-12b-a2.5b-ep2`` on the described chip."""
    import json
    import sys
    from pathlib import Path

    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from perfbench import common
    c = json.loads((common.BENCH_DIR / "configs" /
                    "mellum2-12b-a2.5b-ep2.json").read_text())
    cfg = common.load_module("runners", "serve").model_config(c)
    eng = ServingEngine(cfg, None, n_slots=1, max_len=c["max_len"])
    on_chip = lambda t: _spec(one_chip, t.shape, t.dtype)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    cache = jax.tree.map(on_chip, eng.cache)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    return eng, params, cache, weights


def test_mellum_share_programs_compile_at_engine_shapes(one_chip):
    """The benchmark's ``mellum2-12b-a2.5b-ep2`` (20 layers, 32 of 64
    experts held, max_len 4096, one slot): the prompt chunk and the decode
    step compile for a v5e, each within one chip's memory beside its
    9.69 GB of weights."""
    eng, params, cache, weights = _mellum_share(one_chip)
    assert eng.chunk == 512
    assert 9.6e9 < weights < 9.8e9
    i32 = _spec(one_chip, (), jnp.int32)
    for program, args in (
            (eng._prefill_chunk, (_spec(one_chip, (1, 512), jnp.int32),
                                  i32, i32, i32)),
            (eng._decode_step, (_spec(one_chip, (1, 1), jnp.int32), i32))):
        mem = program.lower(params, cache, *args).compile().memory_analysis()
        assert mem.argument_size_in_bytes >= weights
        assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
            + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_mellum_decode_reads_the_expert_stacks_in_place(one_chip):
    """Mellum's decode step takes the routed-experts kernel, which reads a
    layer's routed experts straight out of the [5, 32, ...] stacks: the
    compiled program holds Mosaic kernels and writes no buffer of one
    layer's held experts (no copy, dynamic-slice or fusion that makes a
    bf16[32,2304,896] or bf16[32,896,2304]), and fits one chip with
    less than 64 MB of temporaries."""
    import re

    eng, params, cache, weights = _mellum_share(one_chip)
    c = eng._decode_step.lower(
        params, cache, _spec(one_chip, (1, 1), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    text = c.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    layer_copy = re.compile(r"= bf16\[32,(2304,896|896,2304)\]")
    assert not [line for line in text.splitlines() if layer_copy.search(line)]
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes >= weights
    assert mem.temp_size_in_bytes < 64e6
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES
