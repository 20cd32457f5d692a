"""The serving path's Pallas kernels compile for a TPU v5e at TinyLlama-1.1B
widths, and the live-block decode attention at granite-3-2b widths. The
chip is described, not attached: the TPU compiler in the installed jaxlib
refuses here what it would refuse on the chip (misaligned blocks, too much
VMEM), at no chip time. Nothing runs; these tests say nothing about results
or speed."""
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


# granite-3-2b decode in the benchmark: 32 slots, 32 query / 8 KV heads of
# width 64, 16-token pages, 272 pages per row (max_seq 4352), 2304 pages
G_B, G_H, G_KV, G_P, G_NP = 32, 32, 8, 272, 2305


@pytest.mark.parametrize("end", ["smallest", "largest", "ladder"])
def test_live_block_decode_attention_compiles(one_chip, end):
    from repro.models.layers import (attention_decode_blocks,
                                     attention_decode_live, block_pages,
                                     decode_block_ladder, live_block_list)
    c = block_pages(PS, G_P)
    ladder = decode_block_ladder(G_B, -(-G_P // c))
    n_blocks = ladder[0] if end == "smallest" else ladder[-1]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, k_pages, v_pages, table, pos, k_new, v_new):
        blocks = live_block_list(table, pos, n_blocks=n_blocks,
                                 block_pages=c, page_size=PS)
        if end == "ladder":
            return attention_decode_live(q, k_pages, v_pages, blocks, pos,
                                         (k_new, v_new), ladder)
        return attention_decode_blocks(q, k_pages, v_pages, blocks, pos,
                                       (k_new, v_new))

    text = compiled_text(attend, sds((G_B, 1, G_H, HD)),
                         sds((G_NP, PS, G_KV, HD)), sds((G_NP, PS, G_KV, HD)),
                         sds((G_B, G_P), jnp.int32), sds((G_B,), jnp.int32),
                         sds((G_B, 1, G_KV, HD)), sds((G_B, 1, G_KV, HD)))
    # the gathered view holds n_blocks blocks of c pages, not every row;
    # the ladder gathers each width in a branch of one conditional
    for n in (ladder if end == "ladder" else (n_blocks,)):
        assert f"bf16[{n},{c},{PS},{G_KV},{HD}]" in text
