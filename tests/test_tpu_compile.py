"""The paged flash-decode kernel compiled by Mosaic for a described TPU v5e
at qwen3-4b widths (32 heads / 8 KV heads, head_dim 128), page 16, 8 slots
of 64 pages, over a 3000-page pool.  Nothing runs: the TPU compiler refuses
here what it would refuse on the chip (block tiling, fast-memory use), and
the compiled program must hold the kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and a machine that cannot describe
it skips these tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_decode import paged_flash_decode

B, KV, G, D, PAGE, M, POOL = 8, 8, 4, 128, 16, 64, 3000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(one_chip, pool_dtype, quantized):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,   # noqa: E731
                                               sharding=one_chip)
    pool = s((POOL, PAGE, KV, D), pool_dtype)
    args = [s((B, KV, G, D), jnp.bfloat16), pool, pool,
            s((B, M), jnp.int32), s((B,), jnp.int32)]
    if quantized:
        scale = s((POOL, PAGE, KV), jnp.float32)
        args += [scale, scale]
    return args


@pytest.mark.parametrize("mode", ["bf16", "int8", "partials"])
def test_paged_decode_compiles_for_v5e(one_chip, no_compile_cache, mode):
    quantized = mode == "int8"

    def step(q, kp, vp, pt, pos, *scales):
        ks, vs = scales or (None, None)
        return paged_flash_decode(
            q, kp, vp, pt, pos, k_scale=ks, v_scale=vs,
            page_offset=0 if mode == "partials" else None,
            partials=mode == "partials", interpret=False)

    args = _args(one_chip, jnp.int8 if quantized else jnp.bfloat16,
                 quantized)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read in place: nothing of its size is staged
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < POOL * PAGE * KV * D // 64, mem
