"""The serving path's Pallas kernels compile for a TPU v5e at TinyLlama-1.1B
widths. The chip is described, not attached: the TPU compiler in the
installed jaxlib refuses here what it would refuse on the chip (misaligned
blocks, too much VMEM), at no chip time. Nothing runs; these tests say
nothing about results or speed."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.paged_attention import paged_attention_decode

# TinyLlama-1.1B decode: 8 slots, 32 query / 4 KV heads of width 64,
# 16-token pages, 128 pages per row (max_seq 2048)
B, H, KV, HD, PS, P = 8, 32, 4, 64, 16, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [0, 256])
def test_paged_attention_decode_compiles(one_chip, window):
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, k_pages, v_pages, table, pos, k_new, v_new):
        return paged_attention_decode(q, k_pages, v_pages, table, pos,
                                      window=window, new_kv=(k_new, v_new))

    text = compiled_text(step, sds((B, 1, H, HD)), sds((B * P, PS, KV, HD)),
                         sds((B * P, PS, KV, HD)), sds((B, P), jnp.int32),
                         sds((B,), jnp.int32), sds((B, 1, KV, HD)),
                         sds((B, 1, KV, HD)))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 512, H, HD), jnp.bfloat16, sharding=one_chip)
    text = compiled_text(lambda q, k, v: flash_attention(q, k, v), q, q, q)
    assert "tpu_custom_call" in text


def test_matmul_compiles(one_chip):
    a = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((2048, 5632), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in compiled_text(matmul, a, b)
