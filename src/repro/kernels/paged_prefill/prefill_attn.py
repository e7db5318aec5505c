"""Chunked paged prefill GQA attention — Pallas TPU kernel that walks the
block table IN-KERNEL (the serving join-path hot spot).

The serving runtime prefills each prompt in fixed ``prefill_chunk``-sized
slices whose K/V are written straight into pool blocks before attention
runs (write-before-attend, ``models/layers.py``).  This kernel then
computes the chunk's queries against the row's *entire* paged history —
prefix-shared blocks, earlier chunks, and the chunk itself — in one pass:

* the (B, MB) block table and the (B,) per-row chunk start positions are
  **scalar-prefetched**; the BlockSpec index map resolves each logical
  block to its physical pool block, so the DMA engine streams exactly one
  (sub, hd) pool tile per step and no gathered K/V copy ever exists
  (the decode kernel, ``paged_attention/paged_attn.py``, instead walks
  each row's live pages in-kernel, many per step);
* the grid is (batch, kv-head, q-tile, logical-block, sub-block) with the
  two kv dims innermost ("arbitrary" -> VMEM scratch persists) and online
  softmax accumulates across them;
* masking is in-kernel from positions: logical key index == absolute
  position, so ONE causal rule ``kpos <= qpos`` covers paged history and
  in-chunk causality; -1 table entries and the sliding window mask the
  same way; no mask tensor touches HBM;
* kv steps that are entirely in the future of the q tile (or entirely
  left of its sliding window) skip their matmuls under ``pl.when`` — the
  static grid still iterates, but prefill's triangular structure prunes
  about half the MXU work (flash_attention's trick, applied to a paged
  layout).

All G = H/K query heads of a kv head ride with the q tile in one
(G*qt, hd) operand, so the MXU sees a (G*qt, hd) x (hd, sub) matmul per
step — GQA without K/V replication.  Rows that are pure padding (chunk
tail past the prompt) produce junk finite output the runtime discards.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import largest_divisor_block

NEG_INF = -1e30


def _prefill_kernel(tbl_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, scale: float,
                    window: Optional[int], bs: int, sub: int, qt: int,
                    n_blk: int, n_sub: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)                             # q tile within chunk
    j = pl.program_id(3)                              # logical block
    i = pl.program_id(4)                              # sub-block within it

    @pl.when((j == 0) & (i == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = start_ref[b] + qi * qt        # first absolute q position of tile
    kv0 = j * bs + i * sub
    live = kv0 <= q0 + qt - 1          # not entirely in the tile's future
    if window is not None:
        live = live & (kv0 + sub - 1 > q0 - window)   # not entirely stale

    @pl.when(live)
    def _():
        G = q_ref.shape[2]
        hd = q_ref.shape[4]
        q = q_ref[0, 0].reshape(G * qt, hd)           # rows r = g*qt + c
        k = k_ref[0, 0]                               # (sub, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (G * qt, sub), 0) % qt
        kpos = kv0 + jax.lax.broadcasted_iota(
            jnp.int32, (G * qt, sub), 1)
        ok = (kpos <= qpos) & (tbl_ref[b, j] >= 0)
        if window is not None:
            ok = ok & (kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((j == n_blk - 1) & (i == n_sub - 1))
    def _():
        G = o_ref.shape[2]
        hd = o_ref.shape[4]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).reshape(G, qt, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "q_block", "s_block",
                                             "interpret"))
def paged_prefill_attention(q, kp, vp, block_tbl, start, *,
                            window: Optional[int] = None, q_block: int = 256,
                            s_block: int = 512, interpret: bool = False):
    """q: (B, K, G, C, hd) chunk queries; kp, vp: (K, NB, bs, hd) physical
    block pools (the chunk's K/V already written); block_tbl: (B, MB)
    int32, -1 = unallocated; start: (B,) int32 absolute position of each
    row's first query (queries are contiguous: row b query c sits at
    ``start[b] + c``).  Returns (B, K, G, C, hd).

    ``q_block`` / ``s_block`` cap the q / kv tiles; both split into the
    largest equal divisor <= the target (tail-safe tiling rule)."""
    B, K, G, C, hd = q.shape
    bs = kp.shape[2]
    MB = block_tbl.shape[1]
    sub = largest_divisor_block(bs, s_block)
    n_sub = bs // sub
    qt = largest_divisor_block(C, q_block)
    n_q = C // qt
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_prefill_kernel, scale=scale, window=window,
                               bs=bs, sub=sub, qt=qt, n_blk=MB, n_sub=n_sub)

    def kv_map(b, h, qi, j, i, tbl, start):
        return (h, jnp.maximum(tbl[b, j], 0), i, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, n_q, MB, n_sub),
            in_specs=[
                pl.BlockSpec((1, 1, G, qt, hd),
                             lambda b, h, qi, j, i, tbl, start:
                             (b, h, 0, qi, 0)),
                pl.BlockSpec((1, 1, sub, hd), kv_map),
                pl.BlockSpec((1, 1, sub, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, qt, hd),
                                   lambda b, h, qi, j, i, tbl, start:
                                   (b, h, 0, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((G * qt, hd), jnp.float32),
                pltpu.VMEM((G * qt,), jnp.float32),
                pltpu.VMEM((G * qt,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, C, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tbl, start.astype(jnp.int32), q, kp, vp)
