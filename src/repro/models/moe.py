"""Mixture-of-Experts MLP (top-k router, ragged grouped matmul).

Token dispatch is sort-based: tokens are ordered by assigned expert and fed
through ``jax.lax.ragged_dot`` so compiled FLOPs reflect only *active*
experts (capacity-free / dropless).  This is the TPU-native analogue of the
CUDA grouped-GEMM path.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _normal

Params = Dict[str, Any]


def moe_init(key, cfg: ModelConfig, dtype) -> Params:
    import math
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(D)
    return {
        "router": {"w": _normal(ks[0], (D, E), dtype, scale)},
        "wi": _normal(ks[1], (E, D, F), dtype, scale),
        "wg": _normal(ks[2], (E, D, F), dtype, scale),
        "wo": _normal(ks[3], (E, F, D), dtype, 1.0 / math.sqrt(F)),
    }


# Set by the launcher/dryrun (see launch.sharding.ShardingOptions
# .moe_shard_map) to enable the locality-preserving dispatch below.
_PARALLEL_MESH = None


def set_parallel_mesh(mesh) -> None:
    """Enable shard_map token routing: each data shard routes ONLY its own
    tokens (routing is per-token independent, so this is exact), removing
    the global argsort/gather that otherwise all-gathers activations."""
    global _PARALLEL_MESH
    _PARALLEL_MESH = mesh


def _moe_math(p: Params, cfg: ModelConfig, xf, *, psum_axis=None):
    """Core routed computation on a flat (N, D) token block.

    Expert weights may be sharded on the F dim (shard_map path): the
    silu/mul are elementwise in F; the wo contraction then psums partial
    sums over ``psum_axis``."""
    N, D = xf.shape
    E, k = cfg.num_experts, cfg.experts_per_token

    logits = (xf @ p["router"]["w"]).astype(jnp.float32)        # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                         # (N, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    flat_e = topi.reshape(-1)                                    # (N*k,)
    order = jnp.argsort(flat_e)                                  # stable
    token_of = order // k
    xs = jnp.take(xf, token_of, axis=0)                          # (N*k, D)
    group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], group_sizes)) * \
        jax.lax.ragged_dot(xs, p["wi"], group_sizes)             # (N*k, F?)
    ys = jax.lax.ragged_dot(h.astype(xs.dtype), p["wo"], group_sizes)

    w_sorted = jnp.take(topw.reshape(-1), order, axis=0).astype(ys.dtype)
    out = jnp.zeros((N, D), ys.dtype).at[token_of].add(ys * w_sorted[:, None])
    if psum_axis is not None:
        out = jax.lax.psum(out.astype(xf.dtype), psum_axis)
    me = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    ce = jnp.mean(probs, axis=0)
    aux = {"load_balance_loss": E * jnp.sum(me * ce),
           "router_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)}
    return out, aux


# dispatch algorithm for the routed matmuls:
#   "ragged"   — jax.lax.ragged_dot (grouped matmul; efficient native TPU
#                lowering, but dense-over-all-experts on backends without it)
#   "capacity" — GShard-style fixed-capacity batched matmul: exactly
#                E × cap × 3DF·2 FLOPs (cap = 1.25·N·k/E); overflow tokens
#                fall back to their top-1 weight renormalised (dropped from
#                the overflowing expert), reported in aux["drop_fraction"].
_DISPATCH = "ragged"


def set_dispatch(mode: str) -> None:
    global _DISPATCH
    assert mode in ("ragged", "capacity")
    _DISPATCH = mode


def _moe_capacity_math(p: Params, cfg: ModelConfig, xf, *,
                       capacity_factor: float = 1.25, psum_axis=None):
    """Fixed-capacity dispatch: flops bounded at capacity_factor × active."""
    N, D = xf.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    F = p["wi"].shape[-1]

    logits = (xf @ p["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    flat_e = topi.reshape(-1)                              # (N*k,)
    order = jnp.argsort(flat_e)
    token_of = order // k
    idx_s = jnp.take(flat_e, order, axis=0)
    counts = jnp.bincount(flat_e, length=E)
    seg_start = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                 jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(N * k) - jnp.take(seg_start, idx_s)
    cap = max(int(N * k / E * capacity_factor), 1)
    keep = rank < cap
    slot = jnp.where(keep, idx_s * cap + rank, E * cap)    # overflow slot

    xs = jnp.take(xf, token_of, axis=0)                    # (N*k, D)
    buf = jnp.zeros((E * cap + 1, D), xf.dtype).at[slot].set(
        jnp.where(keep[:, None], xs, 0))
    xb = buf[:-1].reshape(E, cap, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", xb, p["wi"])            # (E, cap, F?)
    yb = jnp.einsum("ecf,efd->ecd", h.astype(xf.dtype), p["wo"])
    y_flat = jnp.concatenate(
        [yb.reshape(E * cap, D), jnp.zeros((1, D), yb.dtype)])
    ys = jnp.take(y_flat, slot, axis=0)                    # sorted rows

    w_sorted = jnp.take(topw.reshape(-1), order, axis=0).astype(ys.dtype)
    w_sorted = jnp.where(keep, w_sorted, 0)
    out = jnp.zeros((N, D), ys.dtype).at[token_of].add(ys * w_sorted[:, None])
    if psum_axis is not None:
        out = jax.lax.psum(out.astype(xf.dtype), psum_axis)
    me = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    ce = jnp.mean(probs, axis=0)
    aux = {"load_balance_loss": E * jnp.sum(me * ce),
           "router_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
           "drop_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return out, aux


def _apply_moe_shard_map(p: Params, cfg: ModelConfig, x):
    """Locality-preserving MoE: tokens stay on their data shard (local sort
    + local ragged matmuls against F-sharded experts), one output psum over
    the model axis. Exact same math as the dense path."""
    from jax.sharding import PartitionSpec as P
    mesh = _PARALLEL_MESH
    B, T, D = x.shape
    da = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_da = 1
    for a in da:
        n_da *= mesh.shape[a]
    F = cfg.d_ff
    # shard_map routing pays a per-layer expert-weight regather (F-sharded
    # in_specs vs fsdp2d storage); it only amortizes when each data shard
    # has a meaningful token block. Decode (few tokens/shard) keeps the
    # plain path — measured 1.8× regression otherwise (§Perf).
    tokens_local = (B // max(n_da, 1)) * T
    if B % n_da != 0 or F % mesh.shape["model"] != 0 or tokens_local < 64:
        out, aux = _moe_math(p, cfg, x.reshape(B * T, D))
        return out.reshape(B, T, D).astype(x.dtype), aux

    p_specs = {"router": {"w": P(None, None)},
               "wi": P(None, None, "model"),
               "wg": P(None, None, "model"),
               "wo": P(None, "model", None)}
    x_spec = P(da, None, None)

    math_fn = _moe_capacity_math if _DISPATCH == "capacity" else _moe_math

    def local(pl, xl):
        b, t, d = xl.shape
        out, aux = math_fn(pl, cfg, xl.reshape(b * t, d),
                           psum_axis="model")
        aux = {k: jax.lax.pmean(v, da) for k, v in aux.items()}
        return out.reshape(b, t, d), aux

    out, aux = jax.shard_map(local, mesh=mesh,
                             in_specs=(p_specs, x_spec),
                             out_specs=(x_spec, P()),
                             check_vma=False)(p, x)
    return out.astype(x.dtype), aux


def apply_moe(p: Params, cfg: ModelConfig, x, *, return_aux: bool = False):
    """x: (B, T, D) -> (B, T, D) [, aux losses dict]."""
    B, T, D = x.shape
    if _PARALLEL_MESH is not None:
        out, aux = _apply_moe_shard_map(p, cfg, x)
    else:
        math_fn = _moe_capacity_math if _DISPATCH == "capacity" else _moe_math
        out, aux = math_fn(p, cfg, x.reshape(B * T, D))
        out = out.reshape(B, T, D).astype(x.dtype)
    if return_aux:
        return out, aux
    return out
