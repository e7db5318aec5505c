"""Plain reference of a dense GQA decoder with LoRA on q/k/v/o.

The published equations of Qwen2 and Phi-3 (both ``*ForCausalLM`` of
Hugging Face transformers): token embedding; per layer an RMSNorm, q/k/v
projections (with a bias where the architecture has one) plus each row's
LoRA delta ``alpha/rank * (h A) B``, rotate-half RoPE, causal grouped-query
attention with 1/sqrt(head_dim) scaling (keys within the sliding window,
where the configuration has one), the output projection plus its delta, a
residual; an RMSNorm and a SwiGLU MLP with a residual; a final RMSNorm and
the head (the embedding's transpose when tied).

It computes in float32 at "highest" matmul precision, one sequence at a
time and the layers in a scan, so only one layer's weights are ever in
float32.  It imports nothing of the program; it reads the benchmark's
weights by the names the benchmark gave their leaves.

``precision="fp8"`` is the control: every matmul operand of the linear
layers (weights, activations, LoRA factors, the head) rounded to
float8_e4m3 with a scale per output channel or per token, as a lower
precision serving path would.

Besides the forward pass the module owns the harness's two other views of
this architecture: ``reference_weights`` and ``adapter_lora`` (the
benchmark's trees as the forward pass takes them) and ``counts_of`` (the
operations and bytes the served programs' work needs, for the per-layer
readers).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.counts import BYTES

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block
MASKED = -1e30           # a masked score: exp() of it less any real one is 0


def _q8(x, axis):
    """Round to float8_e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    """x (..., n) @ w (n, m) in float32; fp8 rounds x per token and w per
    output column first."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, heads, hd); rotate-half convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, n_valid, window):
    """q (T, H, hd), k/v (T, K, hd); causal over the first ``n_valid``
    positions, in query blocks."""
    T, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    kpos = jnp.arange(T)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, K, G, hd)

    def block(args):
        i, qi = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgh,skh->kgqs", qi, k, precision=HI)
        s = s / math.sqrt(hd)
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        # a finite floor: a padding query that no key is left to (one
        # past the last real position by more than the window) reads
        # finite garbage, not NaN, which 0 * NaN would carry into every
        # real row through the next layer's padded keys
        s = jnp.where(ok[None, None], s, MASKED)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    return out.reshape(T, H * hd)


@functools.partial(jax.jit, static_argnames=("spec", "fp8"))
def _forward(weights, lora, tokens, n_valid, read_pos, *, spec, fp8):
    """Logits (len(read_pos), V) of one sequence ``tokens`` (T,), whose
    first ``n_valid`` are real; ``lora``: this row's adapter, per layer."""
    H, K, hd, eps, theta, window, scaling, tied = spec
    T = tokens.shape[0]
    x = weights["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(T)

    def layer(x, xs):
        w, a = xs
        at = w["attn"]
        h = _rms(x, w["norm1"]["scale"], eps)

        def proj(name, site, inp):
            y = _mm(inp, at[name]["w"], fp8)
            if "b" in at[name]:
                y = y + at[name]["b"].astype(jnp.float32)
            if site in a:
                y = y + scaling * _mm(_mm(inp, a[site]["a"], fp8),
                                      a[site]["b"], fp8)
            return y

        q = _rope(proj("wq", "q", h).reshape(T, H, hd), pos, theta)
        k = _rope(proj("wk", "k", h).reshape(T, K, hd), pos, theta)
        v = proj("wv", "v", h).reshape(T, K, hd)
        x = x + proj("wo", "o", _attention(q, k, v, n_valid, window))
        h = _rms(x, w["norm2"]["scale"], eps)
        m = w["mlp"]
        g = jax.nn.silu(_mm(h, m["wg"]["w"], fp8)) * _mm(h, m["wi"]["w"], fp8)
        return x + _mm(g, m["wo"]["w"], fp8), None

    x, _ = jax.lax.scan(layer, x, (weights["layers"], lora))
    h = _rms(x[read_pos], weights["final_norm"]["scale"], eps)
    head = weights["embed"].T if tied else weights["lm_head"]
    return _mm(h, head, fp8)


def _window(conf: Dict[str, Any]):
    if conf.get("use_sliding_window") is False:
        return None
    return conf.get("sliding_window")


def spec_of(conf: Dict[str, Any]):
    """The static numbers of the forward pass, from a configuration file."""
    lora = conf["lora"]
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["architecture"]["head_dim"], float(conf["rms_norm_eps"]),
            float(conf["rope_theta"]), _window(conf),
            float(lora["alpha"]) / lora["rank"],
            bool(conf["tie_word_embeddings"]))


def logits(conf: Dict[str, Any], weights, lora, tokens: np.ndarray,
           read_pos: np.ndarray, *, fp8: bool = False,
           spec=None) -> np.ndarray:
    """Reference logits at ``read_pos`` of one sequence.

    ``weights``: {"embed", "layers" (stacked per layer: norm1, attn{wq,
    wk, wv, wo: {w[, b]}}, norm2, mlp{wg, wi, wo}), "final_norm"[,
    "lm_head"]}; ``lora``: per layer {site: {"a", "b"}}, stacked.  The
    sequence is padded to a whole number of 1024 tokens and the reads to
    a whole number of 256, so few shapes compile."""
    T = len(tokens)
    Tp = -(-T // 1024) * 1024
    n = len(read_pos)
    npad = -(-n // 256) * 256
    tok = np.zeros(Tp, np.int32)
    tok[:T] = tokens
    rp = np.zeros(npad, np.int32)
    rp[:n] = read_pos
    out = _forward(weights, lora, jnp.asarray(tok), jnp.int32(T),
                   jnp.asarray(rp), spec=spec or spec_of(conf), fp8=fp8)
    return np.asarray(out[:n])


def reference_weights(backbone) -> Dict[str, Any]:
    """The forward pass's view of the benchmark's backbone: the one period
    of layers (stacked), LoRA banks left out."""
    layers = dict(backbone["periods"]["p0"])
    layers["attn"] = {k: v for k, v in layers["attn"].items()
                      if k != "lora"}
    w = {"embed": backbone["embed"], "layers": layers,
         "final_norm": backbone["final_norm"]}
    if "lm_head" in backbone:
        w["lm_head"] = backbone["lm_head"]
    return w


def adapter_lora(adapter_tree) -> Dict[str, Any]:
    """One adapter's LoRA factors, per layer (stacked), by site."""
    return adapter_tree["periods"]["p0"]["attn"]["lora"]


# ------------------------------------------------------------------ counts
# What is counted, and what is not: ``harness/counts.py``.

@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the counts depend on; with the methods at its end it is
    the ``harness.counts.ArchCounts`` that ``readings.Run`` holds."""
    D: int          # hidden
    H: int          # query heads
    K: int          # kv heads
    hd: int         # head dim
    F: int          # MLP width
    V: int          # vocabulary
    L: int          # layers
    r: int          # LoRA rank
    window: Optional[int] = None

    def sites(self) -> List[Tuple[int, int]]:
        """(in, out) widths of the LoRA sites q, k, v, o."""
        D, H, K, hd = self.D, self.H, self.K, self.hd
        return [(D, H * hd), (D, K * hd), (D, K * hd), (H * hd, D)]

    # ------------------------------------------ what the harness reads
    @property
    def lora_rank(self) -> int:
        return self.r

    def lora_sites(self) -> List[Tuple[int, int, int]]:
        """(in, out, layers that carry it) of each LoRA site."""
        return [(i, o, self.L) for i, o in self.sites()]

    def decode_flops(self, pos: Sequence[int],
                     accepted: Sequence[int]) -> float:
        return decode_model_flops(self, pos, accepted)

    def prefill_flops(self, prompt_len: int, start: int) -> float:
        return prefill_model_flops(self, prompt_len, start)

    def paged_decode_step(self, pos: Iterable[int]) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode step's paged-decode calls, every
        layer one."""
        fl, by = paged_decode_call(self, pos)
        return self.L * fl, self.L * by

    def paged_prefill_step(self, rows: Iterable[Tuple[int, int]]
                           ) -> Tuple[float, float]:
        """(FLOPs, bytes) of one prefill round's paged-prefill calls, every
        layer one."""
        fl, by = paged_prefill_call(self, rows)
        return self.L * fl, self.L * by


def counts_of(conf: Dict[str, Any]) -> Dims:
    """The counts of a configuration file, as it is run."""
    return Dims(D=conf["hidden_size"], H=conf["num_attention_heads"],
                K=conf["num_key_value_heads"],
                hd=conf["architecture"]["head_dim"],
                F=conf["intermediate_size"], V=conf["vocab_size"],
                L=conf["num_hidden_layers"], r=conf["lora"]["rank"],
                window=_window(conf))


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
