"""SGMV Pallas TPU kernel — segmented gather LoRA matmul (multi-LoRA batch).

TPU adaptation of Punica's SGMV: instead of warp-level per-row gathers, rows
are pre-grouped into *blocks that share one adapter* (the engine sorts the
batch by adapter and pads each segment to the row-block size).  The adapter
id of each block is a **scalar-prefetch** operand, so the BlockSpec
``index_map`` gathers the right A/B tiles HBM→VMEM ahead of the matmuls —
the gather happens in the DMA engine, not the MXU.

Block shapes are MXU-friendly: row block × D in VMEM, full (D, r) adapter
tile (r ≤ 128 keeps it one lane tile), (r, O-tile) up-projection tile.
D and O are tiled when large so the VMEM working set stays bounded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import largest_divisor_block


def _sgmv_kernel(idx_ref, x_ref, a_ref, b_ref, y_ref, acc_ref, *,
                 n_d: int, scaling: float):
    """Grid: (row_blocks, o_tiles, d_tiles). d is the innermost (arbitrary)
    dim; xa accumulates over d tiles in f32 scratch, y written at last d."""
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xa = jax.lax.dot_general(
        x_ref[...], a_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (rows, r)
    acc_ref[...] += xa

    @pl.when(d == n_d - 1)
    def _():
        y = jax.lax.dot_general(
            acc_ref[...], b_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (rows, o_tile)
        y_ref[...] = (scaling * y).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_block", "d_block",
                                             "o_block", "scaling",
                                             "interpret"))
def sgmv(x, a, b, block_idx, *, row_block: int = 8,
         d_block: int = 2048, o_block: int = 2048,
         scaling: float = 1.0, interpret: bool = False):
    """y[rows in block g] = scaling * (x @ A[block_idx[g]]) @ B[block_idx[g]].

    x: (R, D) with R % row_block == 0; every ``row_block`` rows share one
    adapter, given by block_idx: (R // row_block,) int32.
    a: (N, D, r); b: (N, r, O).  Returns (R, O) in x.dtype.
    """
    R, D = x.shape
    N, _, r = a.shape
    O = b.shape[-1]
    assert R % row_block == 0, (R, row_block)
    d_block = largest_divisor_block(D, d_block)
    o_block = largest_divisor_block(O, o_block)
    n_rows, n_o, n_d = R // row_block, O // o_block, D // d_block

    grid = (n_rows, n_o, n_d)
    kernel = functools.partial(_sgmv_kernel, n_d=n_d, scaling=scaling)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((row_block, d_block),
                             lambda i, j, d, idx: (i, d)),
                pl.BlockSpec((1, d_block, r),
                             lambda i, j, d, idx: (idx[i], d, 0)),
                pl.BlockSpec((1, r, o_block),
                             lambda i, j, d, idx: (idx[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((row_block, o_block),
                                   lambda i, j, d, idx: (i, j)),
            scratch_shapes=[pltpu.VMEM((row_block, r), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, O), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_idx, x, a, b)
