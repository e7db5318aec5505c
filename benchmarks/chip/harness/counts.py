"""Operations and bytes the algorithm needs, from the live shapes.

What an architecture's work costs is its reference module's to say
(``counts_of(conf)`` in ``references/<reference>.py``, an ``ArchCounts``
built from the configuration file).  What every architecture shares is
here: the roofline, the chunk loop's rounds, and the SGMV call.

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

from typing import Dict, Iterable, List, Protocol, Sequence, Tuple

BYTES = 2


class ArchCounts(Protocol):
    """What ``readings.Run`` reads of an architecture's counts."""

    @property
    def lora_rank(self) -> int: ...

    def lora_sites(self) -> List[Tuple[int, int, int]]:
        """(in, out, layers that carry it) of each LoRA site."""

    def decode_flops(self, pos: Sequence[int],
                     accepted: Sequence[int]) -> float:
        """Useful FLOPs of one decode call: each running row at position
        ``pos`` keeps ``accepted`` tokens."""

    def prefill_flops(self, prompt_len: int, start: int) -> float:
        """Useful FLOPs of one request's prefill from ``start``."""

    def paged_decode_step(self, pos: Iterable[int]) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode step's paged-decode calls, over all
        the layers that run them."""

    def paged_prefill_step(self, rows: Iterable[Tuple[int, int]]
                           ) -> Tuple[float, float]:
        """(FLOPs, bytes) of one prefill round's paged-prefill calls over
        its real rows, each (first position, real tokens), over all the
        layers that run them."""


def sgmv_call(rows: int, adapters: int, din: int, dout: int, rank: int
              ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one SGMV call: ``rows`` live rows over
    ``adapters`` distinct adapters, one LoRA site."""
    fl = 2.0 * rows * rank * (din + dout)
    by = BYTES * (rows * (din + dout) + adapters * rank * (din + dout))
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
