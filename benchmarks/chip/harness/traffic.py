"""One general generator for every traffic file.

A traffic file gives distributions (lengths, rates, adapter popularity,
sharing); the generator turns them into a fixed *set* of sizes and gaps,
drawn at stratified quantiles and paired into requests (popularity rank,
user turn, output) by a fixed arrangement; the seed only decides the order
of the requests and of the gaps, the adapters' identities and the token
ids.  The pre-roll and the window each get a set of their own, so the
requests due in the window are the same for every seed, arranged
differently.

An open loop may also name the requests already in service when the
pre-roll starts (``in_service``): as many as Little's law puts in the
slots at the cell's rate, each part-way through its output as in the
equilibrium of the arrival process (its output length drawn in proportion
to length times frequency, its progress uniform over it).  Their prompts
carry the tokens already served, and they ask only for the rest, so the
window opens on slots that finish at a steady rate and not on a batch
that started together.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the client sends it."""
    idx: int
    adapter: int                 # adapter id, 0..count-1
    prompt: np.ndarray           # int32 token ids
    output_len: int
    due: float                   # seconds from the window's opening
    #   (negative: pre-roll)


@dataclasses.dataclass
class Traffic:
    items: List[Item]            # the open loop's arrivals
    preroll_s: float = 0.0
    in_service: List[Item] = dataclasses.field(default_factory=list)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int, scale: float = 1.0) -> np.ndarray:
    """``n`` lengths at stratified quantiles of ``spec``, clipped to its
    [min, max], in ascending order."""
    u = _quantiles(n)
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.clip(np.rint(v), lo, hi) * scale
    return np.maximum(np.rint(v), 1).astype(np.int64)


def length_biased(spec: Dict[str, Any], n: int,
                  scale: float = 1.0) -> np.ndarray:
    """``n`` lengths at stratified quantiles of the length-biased law of
    ``spec`` (frequency times length: the output lengths of the requests
    in service at a random instant), ascending."""
    grid = lengths(spec, 4096, scale).astype(np.float64)
    cdf = np.cumsum(grid) / grid.sum()
    return grid[np.searchsorted(cdf, _quantiles(n))].astype(np.int64)


def zipf_counts(n: int, count: int, s: float) -> np.ndarray:
    """Requests per popularity rank: ``n`` split over ``count`` ranks in
    proportion to 1/(rank+1)^s, rounded by largest remainder."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - out), kind="stable")
    out[order[: n - out.sum()]] += 1
    return out


def generate(traffic: Dict[str, Any], seed: int, *, vocab: int,
             max_total: int, seconds: float, slots: int,
             scale: float = 1.0, rate_per_s: Optional[float] = None,
             in_service: Optional[int] = None) -> Traffic:
    """The requests of one run of ``seconds`` after the pre-roll: as many
    arrivals as the rate offers in the pre-roll and, apart, in the window,
    and the file's ``in_service`` requests (at most ``slots``).  ``scale``
    shrinks every length (the CPU rehearsal); ``rate_per_s`` and
    ``in_service`` override the file's (the knee sweep)."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    fixed = np.random.default_rng(0)      # seed-independent arrangement
    rate = rate_per_s or traffic["arrivals"]["rate_per_s"]
    pre = traffic["preroll_s"]
    n_pre, n_win = round(rate * pre), max(1, round(rate * seconds))
    if in_service is None:
        in_service = traffic.get("in_service", {}).get("requests", 0)
    m = min(in_service, slots)
    A = traffic["adapters"]["count"]

    # popularity rank -> adapter id is the seed's; rank -> request count
    # and rank -> system prompt length are fixed
    adapter_of_rank = np.argsort(rng.permutation(A))
    sys_spec = traffic.get("system_prompt")
    sys_tok = []
    if sys_spec:
        sys_len = fixed.permutation(lengths(
            dict(sys_spec, dist="uniform"), A, scale))
        sys_tok = [rng.integers(0, vocab, int(sys_len[r]), dtype=np.int32)
                   for r in range(A)]

    def requests(k: int, out: np.ndarray, served: np.ndarray,
                 due: np.ndarray, first: int) -> List[Item]:
        """``k`` requests: request ``j`` pairs rank, user length, output
        and served lengths by a fixed arrangement; the seed sends them in
        its order, the ``i``-th at ``due[i]``."""
        ranks = np.repeat(np.arange(A), zipf_counts(
            k, A, traffic["adapters"]["zipf_s"]))[fixed.permutation(k)]
        user = lengths(traffic["user_prompt"], k, scale)[
            fixed.permutation(k)]
        order = rng.permutation(k)
        items = []
        for i in range(k):
            j = int(order[i])
            r = int(ranks[j])
            prompt = np.concatenate([
                sys_tok[r] if sys_spec else np.zeros(0, np.int32),
                rng.integers(0, vocab, int(user[j]) + int(served[j]),
                             dtype=np.int32)])
            total = len(prompt) + int(out[j])
            if total > max_total:
                raise ValueError(f"request {first + i}: {total} tokens "
                                 f"exceed {max_total}")
            items.append(Item(first + i, int(adapter_of_rank[r]), prompt,
                              int(out[j]), float(due[i])))
        return items

    def arrivals(k: int, start: float, first: int) -> List[Item]:
        """``k`` arrivals from ``start`` on: Exp(rate) gaps and output
        lengths at stratified quantiles, in the seed's order."""
        if k == 0:
            return []
        gaps = -np.log1p(-_quantiles(k)) / rate
        due = start + np.cumsum(gaps[rng.permutation(k)])
        return requests(k, lengths(traffic["output"], k, scale),
                        np.zeros(k, np.int64), due, first)

    items = arrivals(n_pre, -pre, 0) + arrivals(n_win, 0.0, n_pre)
    n = n_pre + n_win
    # in service: output drawn length-biased, progress uniform over it
    # (stratified, paired by a fixed permutation), due before the pre-roll
    whole = length_biased(traffic["output"], m, scale)
    done = np.floor(fixed.permutation(_quantiles(m)) * whole).astype(
        np.int64)
    busy = requests(m, whole - done, done, np.full(m, -pre), n)
    return Traffic(items, traffic["preroll_s"], busy)
