"""Paged single-token GQA decode attention — Pallas TPU kernel that walks
only each row's live blocks of the block table, many pages per step
(continuous-batching TPOT hot spot).

The serving runtime keeps each layer's K/V in a pool of fixed-size blocks
(``models/cache.py::paged_attn_cache``, heads-major (K, NB, bs, hd)) and a
host-side block table (B, MB) mapping each slot's logical block to a
physical one.  The legacy path gathers every slot's blocks into a dense
(B, MB*bs, K, hd) view per layer per token — the exact HBM-traffic pattern
paged attention exists to avoid.  This kernel instead:

* scalar-prefetches the block table and the per-row decode positions
  (``decode_attention`` only takes a single scalar ``pos``, so it cannot
  serve the continuous runtime where every slot decodes at its own depth);
* grids over the batch alone, all kv heads of a row in one step, and
  leaves the K/V pools in HBM (``memory_space=pl.ANY``).  An in-kernel
  loop walks the row's live logical blocks only — from
  ``max(0, pos - window + 1) // bs`` (0 without a window) to
  ``pos // bs`` — ``P`` pages per compute step (``_pages_per_step``:
  about 256 keys, capped at MB), copying each page straight from the
  pool into a double-buffered VMEM scratch with ``make_async_copy`` while
  the previous step computes.  Pages past the row's last block and ``-1``
  entries are never fetched (their V slots are zeroed, so stale or
  uninitialised VMEM cannot leak in); a row whose own block is ``-1`` (an
  inactive slot, or a scan step past a row's allocation whose output the
  runtime discards) walks nothing and writes zeros;
* masks in-kernel from positions (causal validity, -1 table entries,
  sliding window), so no mask tensor touches HBM, and accumulates with an
  f32 online softmax carried through the walk.

All G = H/K query heads of a kv head ride in one (G, hd) tile, so the MXU
sees a (G, hd) x (hd, P*bs) matmul per head per step — GQA without K/V
replication.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
KEYS_PER_STEP = 256     # keys one compute step covers: enough that the
#   step's page copies, not its loop overhead, set its time


def _pages_per_step(bs: int, max_blocks: int) -> int:
    """Pages one compute step fetches: about ``KEYS_PER_STEP`` keys,
    at least one page, at most the whole table."""
    return max(1, min(max_blocks, KEYS_PER_STEP // bs))


def _paged_kernel(tbl_ref, pos_ref, q_ref, kp_hbm, vp_hbm, o_ref,
                  k_buf, v_buf, sems, *, scale: float,
                  window: Optional[int], bs: int, pages: int, n_blk: int):
    b = pl.program_id(0)
    K, _, _, hd = k_buf.shape[1:]
    T = pages * bs
    pos = pos_ref[b]
    last = jnp.minimum(pos // bs, n_blk - 1)
    first = 0 if window is None else jnp.minimum(
        jnp.maximum(pos - window + 1, 0) // bs, last)
    n_steps = jnp.where(tbl_ref[b, last] >= 0, (last - first) // pages + 1,
                        0)

    def page_copies(i, slot, p):
        """Whether page ``p`` of compute step ``i`` is fetched (inside the
        walk and allocated), and its K and V copies into buffer ``slot``."""
        j = first + i * pages + p
        phys = tbl_ref[b, jnp.minimum(j, n_blk - 1)]
        fetched = (j <= last) & (phys >= 0)
        phys = jnp.maximum(phys, 0)
        return fetched, [
            pltpu.make_async_copy(pool.at[h, phys], buf.at[slot, h, p],
                                  sems.at[w, slot])
            for h in range(K)
            for w, (pool, buf) in enumerate(((kp_hbm, k_buf),
                                             (vp_hbm, v_buf)))]

    def start(i, slot):
        def one(p, _):
            fetched, copies = page_copies(i, slot, p)

            @pl.when(fetched)
            def _():
                for c in copies:
                    c.start()

            @pl.when(jnp.logical_not(fetched))
            def _():
                v_buf[slot, :, p] = jnp.zeros((K, bs, hd), v_buf.dtype)

            return 0
        jax.lax.fori_loop(0, pages, one, 0)

    def wait(i, slot):
        """Wait for step ``i``'s copies; return its (1, T) per-column
        fetched mask (pages the step skipped read as 0)."""
        col_page = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) // bs

        def one(p, cols):
            fetched, copies = page_copies(i, slot, p)

            @pl.when(fetched)
            def _():
                for c in copies:
                    c.wait()

            return jnp.where(col_page == p, fetched.astype(jnp.int32), cols)
        return jax.lax.fori_loop(0, pages, one,
                                 jnp.zeros((1, T), jnp.int32)) > 0

    q = [q_ref[0, h] for h in range(K)]                # (G, hd) each
    G = q[0].shape[0]

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_steps)
        def _():
            start(i + 1, 1 - slot)

        ok = wait(i, slot)
        # logical key index == absolute token position
        kpos = (first + i * pages) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, T), 1)
        ok = ok & (kpos <= pos)
        if window is not None:
            ok = ok & (kpos > pos - window)
        out = []
        for h in range(K):
            m, l, acc = carry[h]
            s = jax.lax.dot_general(
                q[h], k_buf[slot, h].reshape(T, hd),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (G, T)
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot, h].reshape(T, hd),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            out.append((m_new, l, acc))
        return tuple(out)

    @pl.when(n_steps > 0)
    def _():
        start(0, 0)

    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, hd), jnp.float32)) for _ in range(K))
    carry = jax.lax.fori_loop(0, n_steps, body, init)
    for h in range(K):
        _, l, acc = carry[h]
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, kp, vp, block_tbl, pos, *,
                           window: Optional[int] = None,
                           interpret: bool = False):
    """q: (B, K, G, hd); kp, vp: (K, NB, bs, hd) physical block pools;
    block_tbl: (B, MB) int32, -1 = unallocated; pos: (B,) int32 per-row
    decode positions.  Returns (B, K, G, hd)."""
    B, K, G, hd = q.shape
    bs = kp.shape[2]
    MB = block_tbl.shape[1]
    pages = _pages_per_step(bs, MB)
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd),
                               window=window, bs=bs, pages=pages, n_blk=MB)

    def row_map(b, tbl, pos):
        return (b, 0, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, K, G, hd), row_map),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, K, G, hd), row_map),
            scratch_shapes=[
                pltpu.VMEM((2, K, pages, bs, hd), kp.dtype),
                pltpu.VMEM((2, K, pages, bs, hd), vp.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(block_tbl, pos.astype(jnp.int32), q, kp, vp)
