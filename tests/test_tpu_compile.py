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
    c = eng._decode.lower(params, cache,
                          _spec(one_chip, (4, 1), jnp.int32),
                          _spec(one_chip, (), jnp.int32)).compile()
    mem = c.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights > 1.5e9
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES
