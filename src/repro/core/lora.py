"""LoRA parameter management: partition, merge, multi-adapter stacking.

The paper's C1 requires *unmerged* inference — backbone weights stay
read-only and shared, LoRA deltas are applied as separate low-rank matmuls.
These utilities let us (a) split a parameter tree into the frozen backbone
and the trainable adapter, (b) fold an adapter into a *copy* of the backbone
(oracle for testing unmerged == merged), and (c) stack many adapters for
multi-LoRA serving with per-request adapter indices.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

Params = Dict[str, Any]


def _is_lora_path(path) -> bool:
    return any(getattr(k, "key", None) == "lora" for k in path)


def partition_lora(params: Params) -> Tuple[Params, Params]:
    """Split into (backbone, adapters): leaves under a "lora" key go to the
    adapter tree, everything else to the backbone. Both keep full structure
    with None placeholders so they can be recombined with combine_lora."""
    backbone = jax.tree_util.tree_map_with_path(
        lambda p, x: None if _is_lora_path(p) else x, params)
    adapters = jax.tree_util.tree_map_with_path(
        lambda p, x: x if _is_lora_path(p) else None, params)
    return backbone, adapters


def combine_lora(backbone: Params, adapters: Params) -> Params:
    return jax.tree_util.tree_map(
        lambda b, a: b if b is not None else a, backbone, adapters,
        is_leaf=lambda x: x is None)


def lora_param_count(params: Params) -> int:
    _, ad = partition_lora(params)
    return sum(x.size for x in jax.tree_util.tree_leaves(ad) if x is not None)


def backbone_param_count(params: Params) -> int:
    bb, _ = partition_lora(params)
    return sum(x.size for x in jax.tree_util.tree_leaves(bb) if x is not None)


# --------------------------------------------------------------------- merging
_TARGET_TO_W = {"q": "wq", "k": "wk", "v": "wv", "o": "wo"}


def merge_adapter(params: Params, cfg: ModelConfig,
                  adapter_idx: Optional[int] = None) -> Params:
    """Fold  W' = W + s·A·B  into a COPY of the backbone (testing oracle —
    production serving never merges, per the paper's shared-backbone design).

    Handles period-stacked layer params (leading dims) transparently.  If
    the tree holds a multi-adapter bank (..., N, D, r), pass ``adapter_idx``
    to select one adapter.
    """
    s = cfg.lora.scaling

    def merge_attn(attn: Params) -> Params:
        if "lora" not in attn:
            return attn
        out = {k: v for k, v in attn.items() if k != "lora"}
        for tgt, l in attn["lora"].items():
            a, b = l["a"], l["b"]
            wkey = _TARGET_TO_W[tgt]
            w = out[wkey]["w"]
            if adapter_idx is not None and a.ndim == w.ndim + 1:
                a = jnp.take(a, adapter_idx, axis=-3)
                b = jnp.take(b, adapter_idx, axis=-3)
            if a.ndim != w.ndim:
                raise ValueError(
                    f"adapter rank mismatch for {tgt}: {a.shape} vs {w.shape}"
                    " (multi-adapter bank needs adapter_idx)")
            delta = s * jnp.einsum("...dr,...ro->...do",
                                   a.astype(jnp.float32),
                                   b.astype(jnp.float32))
            out[wkey] = dict(out[wkey])
            out[wkey]["w"] = (w.astype(jnp.float32) + delta).astype(w.dtype)
        return out

    def walk(tree):
        if isinstance(tree, dict):
            if "wq" in tree and "wo" in tree:  # attention param group
                return merge_attn(tree)
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v) for v in tree)
        return tree

    return walk(params)


# --------------------------------------------------------- multi-LoRA stacking
def stack_adapters(adapter_trees) -> Params:
    """Stack N single-adapter trees (leaves (D,r)/(r,O)) into a multi-LoRA
    tree with leading adapter dim (N, D, r)/(N, r, O)."""
    return jax.tree_util.tree_map(
        lambda *xs: None if xs[0] is None else jnp.stack(xs),
        *adapter_trees, is_leaf=lambda x: x is None)


def select_adapter(adapters: Params, i: int) -> Params:
    """Extract adapter i from a stacked multi-LoRA tree."""
    return jax.tree_util.tree_map(
        lambda x: None if x is None else x[i], adapters,
        is_leaf=lambda x: x is None)


def take_adapter(adapters: Params, i: int) -> Params:
    """Extract adapter i from a stacked bank along the ADAPTER axis (-3).

    Unlike ``select_adapter`` (axis 0 — only valid for trees built by
    ``stack_adapters`` before any layer stacking), this works on banks
    living inside full param trees, where period-scanned layers prepend a
    period axis: leaves are (..., N, D, r) / (..., N, r, O) and the
    adapter axis is always third-from-last (the same axis
    ``merge_adapter`` folds over)."""
    return jax.tree_util.tree_map(
        lambda x: None if x is None else jnp.take(x, i, axis=-3), adapters,
        is_leaf=lambda x: x is None)


def random_adapter(params: Params, seed: int) -> Params:
    """One adapter for the bank in ``params`` (its leaves without the
    adapter axis) with random ``a`` AND ``b`` (std 0.05) — ``init_params``
    leaves b = 0, a zero delta that would make multi-adapter outputs
    identical."""
    _, bank = partition_lora(params)
    one = jax.tree_util.tree_map(
        lambda x: None if x is None else x[..., 0, :, :],
        bank, is_leaf=lambda x: x is None)
    leaves, treedef = jax.tree_util.tree_flatten(
        one, is_leaf=lambda x: x is None)
    ks = jax.random.split(jax.random.PRNGKey(seed), max(len(leaves), 1))
    new = [None if lf is None else
           jax.random.normal(k, lf.shape, lf.dtype) * 0.05
           for lf, k in zip(leaves, ks)]
    return jax.tree_util.tree_unflatten(treedef, new)


def bank_size(adapters: Params) -> int:
    """Capacity N of a stacked adapter bank (size of the adapter axis)."""
    for leaf in jax.tree_util.tree_leaves(adapters):
        return int(leaf.shape[-3])
    raise ValueError("empty adapter tree")


def init_adapter_bank(key, cfg: ModelConfig, num_adapters: int,
                      base_params: Optional[Params] = None) -> Params:
    """Fresh multi-LoRA bank matching ``base_params`` structure. Each adapter
    gets independent A init (B = 0)."""
    from repro.models.transformer import init_params
    multi = init_params(key, cfg, lora_adapters=num_adapters)
    _, adapters = partition_lora(multi)
    return adapters


def adapter_bytes(cfg: ModelConfig) -> int:
    """Per-adapter artifact size (bytes) for the serverless artifact model."""
    r = cfg.lora.rank
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    sizes = {"q": (D + H * hd), "k": (D + K * hd), "v": (D + K * hd),
             "o": (H * hd + D)}
    per_layer = sum(sizes[t] * r for t in cfg.lora.targets if t in sizes)
    n_attn = sum(1 for k in (cfg.pattern * cfg.num_periods +
                             cfg.remainder_layers) if k == "attn")
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return int(per_layer * max(n_attn, cfg.num_layers // 2) * itemsize)
