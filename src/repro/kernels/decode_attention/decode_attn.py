"""Single-token GQA decode attention — Pallas TPU kernel (TPOT hot spot).

Decode attends one query token per sequence against the ring KV cache.
TPU-native layout: grid (batch, kv-head, s-block) with the cache-sequence
dim innermost ("arbitrary" → online-softmax scratch persists across
blocks).  All G = H/K query heads of a kv head ride in one (G, hd) tile,
so the MXU sees a (G, hd) × (hd, s_block) matmul per step — GQA without
K/V replication.  The decode position is a scalar-prefetch operand; slot
validity (ring buffer, sliding window) is evaluated in-kernel from the
slot-position vector, so no mask tensor ever touches HBM.

VMEM per step at defaults (s_block=512, hd≤256): k,v tiles ≤ 512 KiB + a
(G, s_block) f32 score tile — far below the 16 MiB budget; s_block can be
raised to 2048 for long caches to amortize grid overhead.
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


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, spos_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float,
                   window: Optional[int], n_s: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                   # (G, hd)
    k = k_ref[0, 0]                                   # (sb, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = pos_ref[0]
    spos = spos_ref[...]                              # (sb,) int32
    ok = (spos >= 0) & (spos <= pos)
    if window is not None:
        ok = ok & (spos > pos - window)
    s = jnp.where(ok[None, :], s, NEG_INF)            # (G, sb)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_s - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "s_block",
                                             "interpret"))
def decode_attention(q, k, v, slot_pos, pos, *,
                     window: Optional[int] = None, s_block: int = 512,
                     interpret: bool = False):
    """q: (B, K, G, hd); k, v: (B, K, S, hd); slot_pos: (S,) int32;
    pos: () int32 — current absolute decode position.
    Returns (B, K, G, hd)."""
    B, K, G, hd = q.shape
    S = k.shape[2]
    # Largest valid block <= s_block: min(s_block, S) alone breaks on
    # non-divisible cache lengths (e.g. S=768 with the default 512).
    s_block = largest_divisor_block(S, s_block)
    n_s = S // s_block
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               n_s=n_s)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, n_s),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, j, pos: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, s_block, hd),
                             lambda b, h, j, pos: (b, h, j, 0)),
                pl.BlockSpec((1, 1, s_block, hd),
                             lambda b, h, j, pos: (b, h, j, 0)),
                pl.BlockSpec((s_block,), lambda b, h, j, pos: (j,)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, h, j, pos: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, hd), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.reshape(1), q, k, v, slot_pos)
