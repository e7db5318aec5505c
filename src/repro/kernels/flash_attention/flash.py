"""Blocked flash attention — Pallas TPU kernel (prefill hot spot).

Online-softmax attention tiled for VMEM: grid (batch, q-head, q-block,
kv-block) with the kv dimension innermost ("arbitrary" semantics → scratch
accumulators persist across kv steps).  GQA is handled in the BlockSpec
index maps (q head h reads kv head h // G) — no K/V replication in HBM.
Causal and sliding-window masks are applied from absolute block offsets;
fully-masked kv blocks still iterate (grid is static) but skip the matmuls
under ``pl.when`` — on real silicon this prunes ~half the MXU work.

Block sizes default to 512×512 tiles: q(512, hd) + k,v(512, hd) + scores
(512, 512) f32 ≈ 1.6 MB VMEM at hd=128, well inside the 16 MB/core budget
while keeping the MXU fed with 128-aligned dims.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_block: int, kv_block: int, n_kv: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(2)
    q_off = i * q_block
    k_off = j * kv_block

    # skip kv blocks that are fully masked (strictly future, or left of the
    # sliding window) — grid is static, so this is a predicated no-op step
    fully_future = causal & (k_off > q_off + q_block - 1)
    fully_stale = (window is not None) and \
        (k_off + kv_block - 1 <= q_off - window)

    @pl.when(jnp.logical_not(fully_future | fully_stale))
    def _():
        q = q_ref[0, 0]                                   # (qb, hd)
        k = k_ref[0, 0]                                   # (kb, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (qb, kb)
        qp = q_off + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        kp = k_off + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok = ok & (kp <= qp)
        if window is not None:
            ok = ok & (kp > qp - window)
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

    @pl.when(j == n_kv - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_block: int = 512,
                    kv_block: int = 512, interpret: bool = False):
    """q: (B, H, Tq, hd); k, v: (B, K, Tk, hd), H % K == 0.
    Tq % q_block == 0 and Tk % kv_block == 0 (caller pads)."""
    B, H, Tq, hd = q.shape
    K, Tk = k.shape[1], k.shape[2]
    G = H // K
    q_block = min(q_block, Tq)
    kv_block = min(kv_block, Tk)
    assert Tq % q_block == 0 and Tk % kv_block == 0
    n_q, n_kv = Tq // q_block, Tk // kv_block
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, n_kv=n_kv)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, q_block, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kv_block, hd),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, hd), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
