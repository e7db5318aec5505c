"""Paged single-token GQA decode attention — Pallas TPU kernel that walks
the block table IN-KERNEL (continuous-batching TPOT hot spot).

The serving runtime keeps each layer's K/V in a pool of fixed-size blocks
(``models/cache.py::paged_attn_cache``, heads-major (K, NB, bs, hd)) and a
host-side block table (B, MB) mapping each slot's logical block to a
physical one.  The legacy path gathers every slot's blocks into a dense
(B, MB*bs, K, hd) view per layer per token — the exact HBM-traffic pattern
paged attention exists to avoid.  This kernel instead:

* scalar-prefetches the block table and the per-row decode positions
  (``decode_attention`` only takes a single scalar ``pos``, so it cannot
  serve the continuous runtime where every slot decodes at its own depth);
* grids over (batch, kv-head, logical-block, sub-block) and resolves the
  physical block *in the BlockSpec index map* from the prefetched table —
  the DMA engine fetches exactly one (sub, hd) pool tile per step, no
  gathered K/V copy ever exists;
* masks in-kernel from positions (causal validity, -1 table entries,
  sliding window), so no mask tensor touches HBM, and accumulates with
  online softmax across the sequence grid ("arbitrary" dims -> VMEM
  scratch persists).

All G = H/K query heads of a kv head ride in one (G, hd) tile, so the MXU
sees a (G, hd) x (hd, sub) matmul per step — GQA without K/V replication.
Rows whose table is all -1 (inactive decode slots) produce junk finite
output that the runtime discards; a -1 entry clips onto physical block 0
(the reserved garbage block) for the fetch and is masked out of the
softmax.
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


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float,
                  window: Optional[int], bs: int, sub: int,
                  n_blk: int, n_sub: int):
    b = pl.program_id(0)
    j = pl.program_id(2)                              # logical block
    i = pl.program_id(3)                              # sub-block within it

    @pl.when((j == 0) & (i == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                   # (G, hd)
    k = k_ref[0, 0]                                   # (sub, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = pos_ref[b]
    # logical key index == absolute token position
    kpos = j * bs + i * sub + jax.lax.broadcasted_iota(
        jnp.int32, (1, sub), 1)
    ok = (kpos <= pos) & (tbl_ref[b, j] >= 0)
    if window is not None:
        ok = ok & (kpos > pos - window)
    s = jnp.where(ok, s, NEG_INF)                     # (G, sub) vs (1, sub)

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
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "s_block",
                                             "interpret"))
def paged_decode_attention(q, kp, vp, block_tbl, pos, *,
                           window: Optional[int] = None, s_block: int = 512,
                           interpret: bool = False):
    """q: (B, K, G, hd); kp, vp: (K, NB, bs, hd) physical block pools;
    block_tbl: (B, MB) int32, -1 = unallocated; pos: (B,) int32 per-row
    decode positions.  Returns (B, K, G, hd).

    ``s_block`` caps the per-step sequence tile: pool blocks larger than it
    are split into the largest equal sub-blocks <= s_block (same
    largest-divisor rule as decode_attention's non-divisible-length fix)."""
    B, K, G, hd = q.shape
    bs = kp.shape[2]
    MB = block_tbl.shape[1]
    sub = largest_divisor_block(bs, s_block)
    n_sub = bs // sub
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               bs=bs, sub=sub, n_blk=MB, n_sub=n_sub)

    def k_map(b, h, j, i, tbl, pos):
        return (h, jnp.maximum(tbl[b, j], 0), i, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, MB, n_sub),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, j, i, tbl, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, sub, hd), k_map),
                pl.BlockSpec((1, 1, sub, hd), k_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, j, i, tbl, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, hd), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(block_tbl, pos.astype(jnp.int32), q, kp, vp)
