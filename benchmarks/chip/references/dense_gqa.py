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
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block


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
        s = jnp.where(ok[None, None], s, -jnp.inf)
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


def spec_of(conf: Dict[str, Any]):
    """The static numbers of the forward pass, from a configuration file."""
    window = conf.get("sliding_window")
    if conf.get("use_sliding_window") is False:
        window = None
    lora = conf["lora"]
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["architecture"]["head_dim"], float(conf["rms_norm_eps"]),
            float(conf["rope_theta"]), window,
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
