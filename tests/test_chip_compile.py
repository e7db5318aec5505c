"""The served Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests cannot see what the chip's compiler refuses: block
shapes not aligned to its tiling, more fast memory than a kernel may use.
These tests compile each kernel of the served path for one chip of a
described (not attached) v5e:2x2, at Qwen2.5-3B widths (K=2 KV heads,
G=8 query heads per KV head, hd=128, 16-token blocks, d_model 2048, LoRA
rank 16), plus SGMV at phi3-medium's d_model 5120.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this
file.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.paged_attn import paged_decode_attention
from repro.kernels.paged_prefill.prefill_attn import paged_prefill_attention
from repro.kernels.sgmv.sgmv import sgmv

K, G, HD, BS = 2, 8, 128, 16          # Qwen2.5-3B: 16 query / 2 KV heads
NB, MB = 2048, 128                    # pool blocks, blocks per slot
RANK, N_ADAPTERS = 16, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described topology, with the persistent compile
    cache off: entries compiled for a described chip cannot be read back
    without one, and would only warn on the next lookup."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_compiles_for_v5e(one_chip, dtype):
    B = 8
    compiled = paged_decode_attention.lower(
        _sds((B, K, G, HD), dtype, one_chip),
        _sds((K, NB, BS, HD), dtype, one_chip),
        _sds((K, NB, BS, HD), dtype, one_chip),
        _sds((B, MB), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("window", [None, 2047])
def test_paged_decode_compiles_at_chat_cell_shapes(one_chip, window):
    """The benchmark's chat cell: 32 slots of 256 blocks over a 3072-block
    pool, bf16.  The whole block table rides in SMEM and each compute step's
    pages in double-buffered VMEM, so this is where an overflow of either
    would show; a window changes the walk's first block."""
    B, NB_CELL, MB_CELL = 32, 3072, 256
    compiled = paged_decode_attention.lower(
        _sds((B, K, G, HD), jnp.bfloat16, one_chip),
        _sds((K, NB_CELL, BS, HD), jnp.bfloat16, one_chip),
        _sds((K, NB_CELL, BS, HD), jnp.bfloat16, one_chip),
        _sds((B, MB_CELL), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip), window=window).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_prefill_compiles_for_v5e(one_chip, dtype):
    B, C = 4, 128
    compiled = paged_prefill_attention.lower(
        _sds((B, K, G, C, HD), dtype, one_chip),
        _sds((K, NB, BS, HD), dtype, one_chip),
        _sds((K, NB, BS, HD), dtype, one_chip),
        _sds((B, MB), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("R,D,O", [
    (8, 2048, 2048),        # decode rows, q/o LoRA sites
    (512, 2048, 256),       # 4 x 128-token prefill rows, k/v sites
    (512, 5120, 5120),      # phi3-medium d_model: tiles must divide 5120
])
def test_sgmv_compiles_for_v5e(one_chip, R, D, O):
    row_block = 8
    compiled = sgmv.lower(
        _sds((R, D), jnp.bfloat16, one_chip),
        _sds((N_ADAPTERS, D, RANK), jnp.bfloat16, one_chip),
        _sds((N_ADAPTERS, RANK, O), jnp.bfloat16, one_chip),
        _sds((R // row_block,), jnp.int32, one_chip),
        row_block=row_block, scaling=2.0).compile()
    _assert_kernel(compiled)
