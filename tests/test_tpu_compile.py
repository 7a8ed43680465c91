"""Compile rehearsals of the serving kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present, and refuses what the chip
would refuse (blocks that are not tile-legal, too much VMEM).  Interpret
mode cannot catch either, so these compiles guard the kernels at yi-9b's
published widths (32 query and 4 KV heads of 128, bf16), and the paged
kernel at the benchmark cells' decode shapes (yi-9b's, and yi-34b's 56
query and 8 KV heads).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and pytest-xdist
workers each import every test file.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import (flash_attention_pallas,
                                           tuned_flash_blocks)
from repro.kernels.paged_attention import paged_decode_attention

B, H, HK, DH = 8, 32, 4, 128          # yi-9b decode: GQA group 8
N_POOL, MAX_PAGES = 512, 34           # a 544-token row (512 prompt + 32 new)


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page_size,pages_per_block",
                         [(16, 0), (16, 1), (8, 0)])
def test_paged_decode_compiles_for_v5e(one_chip, page_size, pages_per_block):
    kv = _shape(one_chip, (N_POOL, page_size, HK, DH), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, pt, sl: paged_decode_attention(
        q, k, v, pt, sl, pages_per_block=pages_per_block, interpret=False))
    compiled = fn.lower(_shape(one_chip, (B, H, DH), jnp.bfloat16), kv, kv,
                        _shape(one_chip, (B, MAX_PAGES), jnp.int32),
                        _shape(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


# (B, H, Hk, max_pages, pool pages) of the benchmark's cells, page 16
CELL_SHAPES = {"yi9b": (28, 32, 4, 128, 3413),
               "yi34b": (24, 56, 8, 228, 4096)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_paged_decode_compiles_for_v5e_at_cell_shapes(one_chip, cell):
    """The benchmark cells' decode shapes lower through Mosaic, and the
    pool reaches the kernel as a view of its stored layout: no copy or
    relayout of a ``(P, page, Hk, Dh)`` pool precedes the custom call."""
    b, h, hk, max_pages, n_pool = CELL_SHAPES[cell]
    kv = _shape(one_chip, (n_pool, 16, hk, DH), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, pt, sl: paged_decode_attention(
        q, k, v, pt, sl, interpret=False))
    compiled = fn.lower(_shape(one_chip, (b, h, DH), jnp.bfloat16), kv, kv,
                        _shape(one_chip, (b, max_pages), jnp.int32),
                        _shape(one_chip, (b,), jnp.int32)).compile()
    _assert_kernel(compiled)
    pool = f"bf16[{n_pool},"
    moved = [line for line in compiled.as_text().splitlines()
             if pool in line and
             any(f" {op}(" in line for op in ("copy", "copy-start",
                                               "reshape", "transpose"))]
    assert not moved, moved


def test_flash_prefill_compiles_for_v5e(one_chip):
    q_blk, kv_blk = tuned_flash_blocks(DH, H // HK)
    fn = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, q_blk=q_blk, kv_blk=kv_blk, interpret=False))
    compiled = fn.lower(_shape(one_chip, (1, 512, H, DH), jnp.bfloat16),
                        _shape(one_chip, (1, 512, HK, DH), jnp.bfloat16),
                        _shape(one_chip, (1, 512, HK, DH), jnp.bfloat16)
                        ).compile()
    _assert_kernel(compiled)
