"""Operations and bytes the algorithm needs, from the live shapes.

Model FLOPs count the dense and LoRA matmuls, the head, and attention over
each token's live context: padded rows, garbage rows, stalled slots,
tokens a chunk computed and then dropped, and prefix-cache-covered tokens
are not counted.  A kernel's bytes are what its call must move for the
rows that are live in it: for paged attention the K/V of the positions
each query attends (not the blocks the kernel's grid walks), plus Q and O;
for SGMV the rows in and out and the A/B factors of the distinct adapters
in the call.  All operands are bf16 (2 bytes).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

BYTES = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    D: int          # hidden
    H: int          # query heads
    K: int          # kv heads
    hd: int         # head dim
    F: int          # MLP width
    V: int          # vocabulary
    L: int          # layers
    r: int          # LoRA rank
    window: Optional[int] = None

    @classmethod
    def of(cls, cfg) -> "Dims":
        return cls(cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                   cfg.head_dim_, cfg.d_ff, cfg.vocab_size, cfg.num_layers,
                   cfg.lora.rank, cfg.sliding_window)

    def sites(self) -> List[Tuple[int, int]]:
        """(in, out) widths of the LoRA sites q, k, v, o."""
        D, H, K, hd = self.D, self.H, self.K, self.hd
        return [(D, H * hd), (D, K * hd), (D, K * hd), (H * hd, D)]


def ctx(d: Dims, pos: int) -> int:
    """Keys a query at absolute position ``pos`` attends."""
    n = pos + 1
    return n if d.window is None else min(n, d.window)


def dense_flops(d: Dims) -> float:
    """Backbone matmuls of one token, all layers (no head)."""
    D, H, K, hd, F = d.D, d.H, d.K, d.hd, d.F
    return 2.0 * d.L * (D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F)


def lora_flops(d: Dims) -> float:
    return 2.0 * d.L * d.r * sum(i + o for i, o in d.sites())


def head_flops(d: Dims) -> float:
    return 2.0 * d.D * d.V


def attn_flops(d: Dims, pos: int) -> float:
    """QK^T and PV of one query over its live context, all layers."""
    return 4.0 * d.L * d.H * d.hd * ctx(d, pos)


def token_flops(d: Dims, pos: int, *, head: bool = True) -> float:
    return (dense_flops(d) + lora_flops(d) + attn_flops(d, pos)
            + (head_flops(d) if head else 0.0))


# ---------------------------------------------------------------- model
def decode_model_flops(d: Dims, pos: Sequence[int],
                       accepted: Sequence[int]) -> float:
    """Useful FLOPs of one decode call: each running row's accepted tokens,
    the j-th computed at position pos + j."""
    return sum(token_flops(d, p + j)
               for p, a in zip(pos, accepted) for j in range(a))


def prefill_model_flops(d: Dims, prompt_len: int, start: int) -> float:
    """Useful FLOPs of one request's prefill: the positions the chunk loop
    computed (past the prefix-cache-covered blocks), and one head row for
    the first token."""
    return (sum(token_flops(d, p, head=False)
                for p in range(start, prompt_len)) + head_flops(d))


# -------------------------------------------------------------- kernels
def paged_decode_call(d: Dims, pos: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one paged-decode kernel call (one layer, one
    step) over the running rows at positions ``pos``."""
    fl = by = 0.0
    for p in pos:
        c = ctx(d, p)
        fl += 4.0 * d.H * d.hd * c
        by += BYTES * (2 * d.K * d.hd * c + 2 * d.H * d.hd)
    return fl, by


def paged_prefill_call(d: Dims, rows: Iterable[Tuple[int, int]]
                       ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one paged-prefill kernel call (one layer) over
    its real rows, each (first position, real tokens)."""
    fl = by = 0.0
    for c0, n in rows:
        fl += sum(4.0 * d.H * d.hd * ctx(d, p) for p in range(c0, c0 + n))
        last = c0 + n - 1
        first_key = 0 if d.window is None else max(0, c0 - d.window + 1)
        by += BYTES * (2 * d.K * d.hd * (last + 1 - first_key)
                       + 2 * n * d.H * d.hd)
    return fl, by


def sgmv_call(d: Dims, rows: int, adapters: int, din: int, dout: int
              ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one SGMV call: ``rows`` live rows over
    ``adapters`` distinct adapters, one LoRA site."""
    fl = 2.0 * rows * d.r * (din + dout)
    by = BYTES * (rows * (din + dout) + adapters * d.r * (din + dout))
    return fl, by


def roofline_seconds(fl: float, by: float, pk: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(fl / pk["bf16_flops_per_s"], by / pk["hbm_bytes_per_s"])


def prefill_rows(prompt_len: int, start: int, chunk: int
                 ) -> List[Tuple[int, int]]:
    """The chunk loop's rounds for one request: (first position, real
    tokens) per round."""
    return [(c0, min(chunk, prompt_len - c0))
            for c0 in range(start, prompt_len, chunk)]
