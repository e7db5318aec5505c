"""Dispatch wrapper for paged decode attention.

``use_kernel=True`` picks the fastest block-table walk for the current
backend: the Pallas TPU kernel (each row walks only its live blocks, many
pages a step; no gathered K/V, no mask tensor in HBM) on TPU, or a fused
jnp block-walk off-TPU that keeps the blocked (K, B, MB, bs, hd) operand
layout end-to-end — no (B, MB*bs, K, hd) reshaped copy and no additive
mask tensor, which measurably beats the legacy gather path on CPU as
well.  ``use_kernel=False`` is the plain
gather reference (``ref.py``).  ``interpret=True`` forces the Pallas
kernel in interpret mode so CPU tests exercise the real kernel logic.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attn import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _paged_decode_jnp(q, kp, vp, block_tbl, pos, *,
                      window: Optional[int] = None,
                      full_walk: bool = False):
    """Fused jnp block walk: same math as the kernel, blocked layout kept
    throughout (the XLA analogue of the in-kernel walk).

    Online-softmax ``fori_loop`` over logical blocks whose trip count is
    the GROUP's max live block count — ``max(pos) // bs + 1``, a traced
    scalar, so one compile covers every occupancy — instead of the full
    table capacity MB.  Every row rides every step of that walk, one
    block at a time; the TPU kernel instead bounds each row's walk by its
    own position and window and fetches many pages a step.
    Blocks past every row's position are fully masked and contribute
    exact float identities (p masked to literal 0, corr = exp(0) = 1),
    so the bounded walk is bitwise-identical to ``full_walk=True`` (all
    MB blocks — kept for the regression test)."""
    B, H, hd = q.shape
    K, _, bs, _ = kp.shape
    G = H // K
    MB = block_tbl.shape[1]
    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    sm = 1.0 / math.sqrt(hd)
    if full_walk:
        nb_live = MB
    else:
        nb_live = jnp.minimum(jnp.max(pos) // bs + 1, MB)

    def body(j, carry):
        m, l, acc = carry
        phys = jnp.maximum(block_tbl[:, j], 0)       # (B,)
        kb = kp[:, phys]                             # (K, B, bs, hd)
        vb = vp[:, phys]
        s = jnp.einsum("bkgh,kbsh->bkgs", qg,
                       kb.astype(jnp.float32)) * sm  # (B, K, G, bs)
        kpos = j * bs + jnp.arange(bs)               # (bs,)
        ok = (kpos[None] <= pos[:, None]) & \
            (block_tbl[:, j] >= 0)[:, None]          # (B, bs)
        if window is not None:
            ok = ok & (kpos[None] > pos[:, None] - window)
        s = jnp.where(ok[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # masked keys are EXACT zeros (not exp(-1e30 - m), which is only
        # 0 once a real key raised m): an all-masked block is then a
        # strict float identity (corr = exp(0) = 1, l += 0, acc += 0),
        # which is what makes the bounded walk bitwise-equal to the
        # full one
        p = jnp.where(ok[:, None, None],
                      jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgs,kbsh->bkgh", p, vb.astype(jnp.float32))
        return m_new, l, acc

    m0 = jnp.full((B, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G), jnp.float32)
    acc0 = jnp.zeros((B, K, G, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb_live, body, (m0, l0, acc0))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, H, hd).astype(q.dtype)


def paged_decode_gqa(q, kp, vp, block_tbl, pos, *,
                     window: Optional[int] = None,
                     use_kernel: bool = True,
                     interpret: Optional[bool] = None):
    """q: (B, H, hd); kp, vp: (K, NB, bs, hd); block_tbl: (B, MB) int32;
    pos: (B,) int32.  Returns (B, H, hd)."""
    if not use_kernel:
        return paged_attention_ref(q, kp, vp, block_tbl, pos, window=window)
    if interpret is None:
        if not _on_tpu():
            return _paged_decode_jnp(q, kp, vp, block_tbl, pos,
                                     window=window)
        interpret = False
    B, H, hd = q.shape
    K = kp.shape[0]
    o = paged_decode_attention(q.reshape(B, K, H // K, hd), kp, vp,
                               block_tbl, pos, window=window,
                               interpret=interpret)
    return o.reshape(B, H, hd)
