"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests the program finished
(drawn from the seed, with the longest of them in it) goes through the
plain reference: each prompt followed by its served tokens, once.  At each
served token the reference gives its logits; the number compared is the
widest gap by which a served token's logit lies below the reference's best
there.  Greedy decoding at the stated bf16 serves a token whose reference
logit is within rounding of the best; a token altered where it is
produced, a cache left unwritten, a wrong adapter or a lower precision
opens the gap.

The control (``calibrate.py``, never the benchmark's own runs) is the
reference in fp8 put in the program's place: at each position of the same
prompts and tokens, the gap of the token the fp8 logits put first.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

SAMPLE = 4          # requests compared: the longest and three drawn
MIN_TOKENS = 256    # fewer served tokens compared is no check


@dataclasses.dataclass
class Gaps:
    program: float               # widest gap of the served tokens
    control: Optional[float]     # widest gap of the fp8 control's picks
    tokens: int                  # served tokens compared
    requests: int


def sample(recs, seed: int, k: int = SAMPLE,
           min_tokens: int = MIN_TOKENS) -> List:
    """Finished requests: the longest (prompt plus output) and k - 1 more
    drawn from the seed, one of them with prefix-cache-covered blocks
    where any finished request had them (its prefill started past them),
    and more drawn while they serve fewer than ``min_tokens`` tokens."""
    done = [r for r in recs if not r.failed and r.done == r.done
            and len(r.tokens) == r.output_len]
    if not done:
        return []
    done.sort(key=lambda r: r.idx)
    longest = max(done, key=lambda r: (len(r.prompt) + r.output_len, -r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = [rest[i] for i in sorted(rng.choice(
        len(rest), size=min(k - 1, len(rest)), replace=False))]
    covered = [r for r in rest if r.covered > 0]
    if pick and covered and not any(r.covered > 0
                                    for r in pick + [longest]):
        pick[-1] = covered[int(rng.integers(len(covered)))]
    out = [longest] + pick
    # short outputs: draw more until the sample serves enough tokens
    more = [r for r in rest if r not in pick]
    for i in rng.permutation(len(more)):
        if sum(len(r.tokens) for r in out) >= min_tokens:
            break
        out.append(more[i])
    return out


def min_tokens(rehearse: bool) -> int:
    """The CPU rehearsal's lengths are an eighth of the chip's."""
    return MIN_TOKENS // 8 if rehearse else MIN_TOKENS


def verdict(conf: Dict[str, Any], gap: float, tokens: int,
            compiles: int = 0, min_tokens: int = MIN_TOKENS):
    """Whether a run is correct, and each number compared with its limit
    and rule: the rule of every run and of the control's readings."""
    checks = {
        "max_logit_gap": (gap, conf["check"]["max_logit_gap"], "<="),
        "served_tokens_compared": (tokens, min_tokens, ">="),
        "compiles_in_window": (compiles, 0, "<="),
    }
    ok = all(v <= lim if rule == "<=" else v >= lim
             for v, lim, rule in checks.values())
    return ok, checks


def reference_weights(backbone) -> Dict[str, Any]:
    """The reference's view of the benchmark's backbone: the one period of
    layers (stacked), LoRA banks left out."""
    layers = {k: v for k, v in backbone["periods"]["p0"].items()}
    layers["attn"] = {k: v for k, v in layers["attn"].items()
                      if k != "lora"}
    w = {"embed": backbone["embed"], "layers": layers,
         "final_norm": backbone["final_norm"]}
    if "lm_head" in backbone:
        w["lm_head"] = backbone["lm_head"]
    return w


def adapter_lora(adapter_tree) -> Dict[str, Any]:
    return adapter_tree["periods"]["p0"]["attn"]["lora"]


def gaps(conf: Dict[str, Any], ref, weights, adapter_of, recs, *,
         control: bool = False) -> Gaps:
    """Widest gaps over ``recs``.  ``adapter_of(adapter id)`` regenerates
    that adapter's LoRA tree from the seed."""
    spec = ref.spec_of(conf)
    worst, worst_c, n = 0.0, (0.0 if control else None), 0
    for r in recs:
        served = np.asarray(r.tokens, np.int64)
        L = len(r.prompt)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        read = np.arange(L - 1, L - 1 + len(served), dtype=np.int32)
        lora = adapter_lora(adapter_of(r.adapter))
        z = ref.logits(conf, weights, lora, seq, read, spec=spec)
        best = z.max(axis=1)
        rows = np.arange(len(served))
        worst = max(worst, float(np.max(best - z[rows, served])))
        if control:
            zc = ref.logits(conf, weights, lora, seq, read, spec=spec,
                            fp8=True)
            pick = zc.argmax(axis=1)
            worst_c = max(worst_c, float(np.max(best - z[rows, pick])))
        n += len(served)
    return Gaps(worst, worst_c, n, len(recs))
