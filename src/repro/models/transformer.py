"""Unified causal LM covering all six architecture families.

Layer stack = ``lax.scan`` over repeating heterogeneous *periods* (pattern of
mixer kinds, e.g. ("rec","rec","attn") for recurrentgemma) with stacked
parameters — HLO size is O(period), not O(depth), which keeps 96-layer
compiles tractable and is the idiomatic TPU form.

Three entry points:
  * ``forward``      — train/prefill: tokens (+ stub modality embeddings) →
                       logits; optionally fills a decode cache.
  * ``decode_step``  — ONE token against an existing cache (serve_step body).
  * ``encode``       — encoder stack for enc-dec (whisper).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import cache as cache_lib
from repro.models.config import ATTN, MOE, NONE, REC, SSD, ModelConfig
from repro.models.layers import (_normal, apply_attention, apply_mlp,
                                 apply_norm, attn_init, mlp_init, norm_init)
from repro.models.moe import apply_moe, moe_init
from repro.models.rglru import apply_rglru_block, rglru_init
from repro.models.ssm import apply_ssd, ssd_init

Params = Dict[str, Any]

# Optional activation-sharding constraint (set by the launcher): pins the
# residual stream to (batch over data axes, replicated in D) right after
# the embedding gather, so the embed table's model-axis sharding does not
# propagate into per-layer D all-gathers (§Perf iteration 4).
_ACT_SPEC = None


def set_activation_spec(spec) -> None:
    global _ACT_SPEC
    _ACT_SPEC = spec


def _constrain(x):
    if _ACT_SPEC is None:
        return x
    return jax.lax.with_sharding_constraint(x, _ACT_SPEC)


# ------------------------------------------------------------------ layer init
def _add_inout_lora(key, block: Params, cfg: ModelConfig, dtype, *,
                    d_in_out, lora_adapters: Optional[int]) -> None:
    """LoRA on the in/out projections of recurrent/SSM blocks (the paper's
    technique applied to attention-free mixers) when the config targets
    include "in"/"out"."""
    from repro.models.layers import lora_init
    wanted = [t for t in cfg.lora.targets if t in ("in", "out")]
    if not cfg.lora or not wanted:
        return
    di_in, do_in, di_out, do_out = d_in_out
    ks = jax.random.split(key, 2)
    lora: Params = {}
    if "in" in wanted:
        lora["in"] = lora_init(ks[0], di_in, do_in, cfg.lora.rank, dtype,
                               lora_adapters)
    if "out" in wanted:
        lora["out"] = lora_init(ks[1], di_out, do_out, cfg.lora.rank, dtype,
                                lora_adapters)
    block["lora"] = lora


def _layer_init(key, kind: str, cfg: ModelConfig, dtype,
                lora_adapters: Optional[int]) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": norm_init(cfg.d_model, cfg.norm_type, dtype)}
    if kind == ATTN:
        p["attn"] = attn_init(ks[0], cfg, dtype, lora_adapters=lora_adapters)
        if cfg.cross_attention:
            p["normx"] = norm_init(cfg.d_model, cfg.norm_type, dtype)
            p["xattn"] = attn_init(ks[1], cfg, dtype, cross=True)
    elif kind == REC:
        p["rec"] = rglru_init(ks[0], cfg, dtype)
        _add_inout_lora(ks[3], p["rec"], cfg, dtype,
                        d_in_out=(cfg.d_model, cfg.d_inner,
                                  cfg.d_inner, cfg.d_model),
                        lora_adapters=lora_adapters)
    elif kind == SSD:
        p["ssd"] = ssd_init(ks[0], cfg, dtype)
        fused = 2 * cfg.d_inner + 2 * cfg.ssm_state_dim + cfg.ssm_num_heads
        _add_inout_lora(ks[3], p["ssd"], cfg, dtype,
                        d_in_out=(cfg.d_model, fused,
                                  cfg.d_inner, cfg.d_model),
                        lora_adapters=lora_adapters)
    else:
        raise ValueError(kind)
    if cfg.mlp_for == MOE:
        p["norm2"] = norm_init(cfg.d_model, cfg.norm_type, dtype)
        p["moe"] = moe_init(ks[2], cfg, dtype)
    elif cfg.mlp_for != NONE:
        p["norm2"] = norm_init(cfg.d_model, cfg.norm_type, dtype)
        p["mlp"] = mlp_init(ks[2], cfg, dtype)
    return p


@functools.partial(jax.jit, static_argnames=("cfg", "lora_adapters"))
def init_params(key, cfg: ModelConfig,
                lora_adapters: Optional[int] = None) -> Params:
    """lora_adapters=None → single adapter per target (training);
    int N → N stacked adapters (multi-LoRA serving).

    Jitted so that one program writes every leaf once, in its own dtype.
    An eager build holds each period stack twice (the layers, then their
    stack) and passes each leaf through f32 first: 13.5 GiB of a 16 GB
    TPU v5e for Qwen2.5-3B, against 5.8 GiB jitted."""
    dtype = cfg.jnp_dtype
    keys = jax.random.split(key, cfg.num_layers + 8)
    pat = cfg.pattern
    periods: Params = {}
    for j, kind in enumerate(pat):
        layer_keys = keys[j: cfg.num_periods * len(pat): len(pat)]
        periods[f"p{j}"] = jax.vmap(
            lambda k, kind=kind: _layer_init(k, kind, cfg, dtype,
                                             lora_adapters))(layer_keys)
    tail = tuple(
        _layer_init(keys[cfg.num_periods * len(pat) + i], kind, cfg, dtype,
                    lora_adapters)
        for i, kind in enumerate(cfg.remainder_layers))
    p: Params = {
        "embed": _normal(keys[-1], (cfg.vocab_size, cfg.d_model), dtype, 0.02),
        "periods": periods,
        "tail": tail,
        "final_norm": norm_init(cfg.d_model, cfg.norm_type, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = _normal(keys[-2], (cfg.d_model, cfg.vocab_size), dtype,
                               0.02)
    if cfg.encoder_layers:
        ek = jax.random.split(keys[-3], cfg.encoder_layers)
        enc_cfg = cfg.with_(cross_attention=False, num_kv_heads=cfg.num_heads,
                            layer_pattern=(ATTN,))
        enc_stack = [{
            "norm1": norm_init(cfg.d_model, cfg.norm_type, dtype),
            "attn": attn_init(jax.random.split(ek[i])[0], enc_cfg, dtype,
                              cross=True),   # cross=True → no LoRA on encoder
            "norm2": norm_init(cfg.d_model, cfg.norm_type, dtype),
            "mlp": mlp_init(jax.random.split(ek[i])[1],
                            cfg.with_(mlp_type="gelu", layer_pattern=(ATTN,)),
                            dtype),
        } for i in range(cfg.encoder_layers)]
        p["encoder"] = {
            "layers": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                             *enc_stack),
            "norm": norm_init(cfg.d_model, cfg.norm_type, dtype),
        }
    return p


# --------------------------------------------------------------- layer apply
def _cross_attention(lp: Params, cfg: ModelConfig, h, enc_out, cache):
    """Cross-attn: K/V from encoder output (computed once, then cached)."""
    from repro.models.layers import attention_core, dense
    B, T, D = h.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    q = dense(h, lp["wq"]).reshape(B, T, H, hd)
    if cache is not None and "xk" in cache:
        k, v = cache["xk"], cache["xv"]
        new = None  # unchanged
    else:
        k = dense(enc_out, lp["wk"]).reshape(B, -1, K, hd)
        v = dense(enc_out, lp["wv"]).reshape(B, -1, K, hd)
        new = (k, v)
    S = k.shape[1]
    mask = jnp.zeros((B, T, S), jnp.float32)  # bidirectional over encoder
    out = attention_core(q, k, v, mask).reshape(B, T, H * hd)
    return dense(out, lp["wo"]), new


def _apply_layer(kind: str, lp: Params, cfg: ModelConfig, x, *, positions,
                 cache, mask_kind: str, prefix_len: int, adapter_idx,
                 enc_out, use_chunked: bool, fill_cache: bool,
                 block_tbl=None, chunk_ids=None,
                 use_paged_kernel: bool = False,
                 lora_kernel: Optional[bool] = None,
                 state_rows=None, state_seq=None):
    """One residual block. Returns (x, new_cache, aux_loss).

    ``state_rows`` (B,) int32 switches REC/SSD layers into *paged slot
    state* mode (serving): the layer cache is (num_slots + 1, ...) rows,
    each dispatch row gathers its slot's row (zeroed when it starts a
    fresh prompt at position 0), runs decode (T == 1) or chunked-prefill
    continuation (T > 1, ``state_seq`` valid-token counts masking the
    chunk tail), and scatters the updated state back to its row."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(x, lp["norm1"], cfg.norm_type)
    new_cache = cache
    if kind == ATTN:
        T = h.shape[1]
        ring_overflow = (cache is not None and fill_cache and "k" in cache
                         and T > cache["k"].shape[1])
        attn_cache_in = None if (cache is None or ring_overflow) else cache
        mix, upd = apply_attention(
            lp["attn"], cfg, h, positions=positions, cache=attn_cache_in,
            mask_kind=mask_kind, prefix_len=prefix_len,
            window=cfg.sliding_window, adapter_idx=adapter_idx,
            use_chunked=use_chunked, use_rope=True, block_tbl=block_tbl,
            chunk_ids=chunk_ids, use_paged_kernel=use_paged_kernel,
            lora_kernel=lora_kernel)
        if ring_overflow:
            # SWA prefill longer than the window: keep only the last Tc K/V.
            from repro.models.layers import dense, rope
            B = h.shape[0]
            K, hd = cfg.num_kv_heads, cfg.head_dim_
            lora = lp["attn"].get("lora", {})
            s = cfg.lora.scaling if cfg.lora else 1.0
            k = dense(h, lp["attn"]["wk"], lora.get("k"), scaling=s,
                      adapter_idx=adapter_idx,
                      lora_kernel=lora_kernel).reshape(B, T, K, hd)
            v = dense(h, lp["attn"]["wv"], lora.get("v"), scaling=s,
                      adapter_idx=adapter_idx,
                      lora_kernel=lora_kernel).reshape(B, T, K, hd)
            pos2 = positions if positions.ndim == 2 else \
                jnp.broadcast_to(positions[None], (B, T))
            k = rope(k, pos2, cfg.rope_theta)
            Tc = cache["k"].shape[1]
            new_cache = dict(cache)
            new_cache["k"] = k[:, -Tc:].astype(cache["k"].dtype)
            new_cache["v"] = v[:, -Tc:].astype(cache["v"].dtype)
            new_cache["slot_pos"] = pos2[0, -Tc:].astype(jnp.int32)
            new_cache["idx"] = cache["idx"] + T
        elif upd is not None:
            new_cache = upd
        x = x + mix
        if cfg.cross_attention and (enc_out is not None or (
                cache is not None and "xk" in cache and not fill_cache)):
            hx = apply_norm(x, lp["normx"], cfg.norm_type)
            mixx, kv = _cross_attention(lp["xattn"], cfg, hx, enc_out,
                                        None if fill_cache else cache)
            if kv is not None and isinstance(new_cache, dict):
                new_cache["xk"] = kv[0].astype(new_cache["k"].dtype)
                new_cache["xv"] = kv[1].astype(new_cache["k"].dtype)
            x = x + mixx
    elif kind == REC:
        lora = lp["rec"].get("lora")
        if state_rows is not None and cache is not None:
            st = cache_lib.gather_slot_state(cache, state_rows, positions)
            mix, upd = apply_rglru_block(
                lp["rec"], cfg, h, state=st, seq_lens=state_seq, lora=lora,
                lora_scaling=cfg.lora.scaling, adapter_idx=adapter_idx,
                lora_kernel=lora_kernel)
            new_cache = cache_lib.scatter_slot_state(cache, upd, state_rows)
        else:
            mix, new_cache = apply_rglru_block(
                lp["rec"], cfg, h, state=cache if not fill_cache else None,
                lora=lora, lora_scaling=cfg.lora.scaling,
                adapter_idx=adapter_idx, lora_kernel=lora_kernel)
        x = x + mix
    elif kind == SSD:
        lora = lp["ssd"].get("lora")
        if state_rows is not None and cache is not None:
            st = cache_lib.gather_slot_state(cache, state_rows, positions)
            mix, upd = apply_ssd(
                lp["ssd"], cfg, h, state=st, seq_lens=state_seq, lora=lora,
                lora_scaling=cfg.lora.scaling, adapter_idx=adapter_idx,
                lora_kernel=lora_kernel)
            new_cache = cache_lib.scatter_slot_state(cache, upd, state_rows)
        else:
            mix, new_cache = apply_ssd(
                lp["ssd"], cfg, h, state=cache if not fill_cache else None,
                lora=lora, lora_scaling=cfg.lora.scaling,
                adapter_idx=adapter_idx, lora_kernel=lora_kernel)
        x = x + mix
    else:
        raise ValueError(kind)

    if cfg.mlp_for == MOE:
        h2 = apply_norm(x, lp["norm2"], cfg.norm_type)
        out, moe_aux = apply_moe(lp["moe"], cfg, h2, return_aux=True)
        aux = aux + moe_aux["load_balance_loss"]
        x = x + out
    elif cfg.mlp_for != NONE:
        h2 = apply_norm(x, lp["norm2"], cfg.norm_type)
        x = x + apply_mlp(lp["mlp"], cfg, h2)
    return x, new_cache, aux


# -------------------------------------------------------------------- encoder
def encode(params: Params, cfg: ModelConfig, frame_embeds) -> jnp.ndarray:
    """Bidirectional encoder over precomputed frontend embeddings (STUB
    frontend per assignment: conv/mel or ViT runs upstream)."""
    enc = params["encoder"]
    x = frame_embeds.astype(cfg.jnp_dtype)
    B, T, D = x.shape
    positions = jnp.arange(T)

    def body(x, lp):
        h = apply_norm(x, lp["norm1"], cfg.norm_type)
        mix, _ = apply_attention(
            lp["attn"], cfg.with_(num_kv_heads=cfg.num_heads), h,
            positions=positions, mask_kind="bidir", use_rope=True)
        x = x + mix
        h2 = apply_norm(x, lp["norm2"], cfg.norm_type)
        from repro.models.layers import apply_encoder_mlp
        x = x + apply_encoder_mlp(lp["mlp"], h2)
        return x, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return apply_norm(x, enc["norm"], cfg.norm_type)


# -------------------------------------------------------------------- forward
def _run_stack(params, cfg: ModelConfig, x, *, positions, cache, mask_kind,
               prefix_len, adapter_idx, enc_out, use_chunked, fill_cache,
               remat: bool, block_tbl=None, chunk_ids=None,
               use_paged_kernel: bool = False,
               lora_kernel: Optional[bool] = None,
               state_rows=None, state_seq=None):
    pat = cfg.pattern
    aux_total = jnp.zeros((), jnp.float32)

    def period_body(carry, xs):
        x, aux = carry
        lps, cs = xs
        new_cs = {}
        for j, kind in enumerate(pat):
            c_j = cs[f"p{j}"] if cs is not None else None
            x, nc, a = _apply_layer(
                kind, lps[f"p{j}"], cfg, x, positions=positions, cache=c_j,
                mask_kind=mask_kind, prefix_len=prefix_len,
                adapter_idx=adapter_idx, enc_out=enc_out,
                use_chunked=use_chunked, fill_cache=fill_cache,
                block_tbl=block_tbl, chunk_ids=chunk_ids,
                use_paged_kernel=use_paged_kernel, lora_kernel=lora_kernel,
                state_rows=state_rows, state_seq=state_seq)
            new_cs[f"p{j}"] = nc
            aux = aux + a
        return (x, aux), new_cs

    body = jax.checkpoint(period_body) if remat else period_body
    cache_periods = cache["periods"] if cache is not None else None
    if cache_periods is None:
        cache_xs = None
        (x, aux_total), _ = jax.lax.scan(
            lambda c, lp: (body(c, (lp, None))[0], None),
            (x, aux_total), params["periods"])
        new_periods = None
    else:
        (x, aux_total), new_periods = jax.lax.scan(
            body, (x, aux_total), (params["periods"], cache_periods))

    new_tail = []
    for i, kind in enumerate(cfg.remainder_layers):
        c_i = cache["tail"][i] if cache is not None else None
        x, nc, a = _apply_layer(
            kind, params["tail"][i], cfg, x, positions=positions, cache=c_i,
            mask_kind=mask_kind, prefix_len=prefix_len,
            adapter_idx=adapter_idx, enc_out=enc_out,
            use_chunked=use_chunked, fill_cache=fill_cache,
            block_tbl=block_tbl, chunk_ids=chunk_ids,
            use_paged_kernel=use_paged_kernel, lora_kernel=lora_kernel,
            state_rows=state_rows, state_seq=state_seq)
        new_tail.append(nc)
        aux_total = aux_total + a

    new_cache = None
    if cache is not None:
        new_cache = {"periods": new_periods, "tail": tuple(new_tail)}
    return x, new_cache, aux_total


def _logits(params, cfg: ModelConfig, x):
    x = apply_norm(x, params["final_norm"], cfg.norm_type)
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


def forward(params: Params, cfg: ModelConfig, tokens, *,
            embeds: Optional[jnp.ndarray] = None,
            frame_embeds: Optional[jnp.ndarray] = None,
            cache: Optional[Dict] = None,
            adapter_idx=None, remat: bool = False,
            use_chunked: Optional[bool] = None,
            last_only: bool = False,
            last_pos: Optional[jnp.ndarray] = None,
            start_pos: Optional[jnp.ndarray] = None,
            block_tbl=None, chunk_ids=None,
            use_paged_kernel: bool = False,
            lora_kernel: Optional[bool] = None,
            state_rows=None
            ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Train (cache=None) or prefill (cache=zeros pytree → filled).

    tokens: (B, T) int32.  embeds: (B, P, D) VLM prefix patch embeddings
    (stub frontend).  frame_embeds: (B, S_enc, D) audio frames (stub).
    Chunked paged prefill (cache = paged block pools): ``start_pos`` (B,)
    offsets the positions to ``start_pos[b] + [0, T)``, ``chunk_ids``
    (B, T//bs) names the pool blocks this chunk writes, and ``block_tbl``
    (B, MB) maps the row's full logical history for attention.
    ``state_rows`` (B,) maps each row to its REC/SSD slot-state row
    (hybrid serving): chunk r > 0 continues the recurrent scan from the
    carried state, and ``last_pos`` doubles as the in-chunk valid-token
    bound so chunk-tail padding never advances the state.
    Returns (logits, filled_cache, aux_loss)."""
    B, T = tokens.shape
    x = _constrain(jnp.take(params["embed"], tokens, axis=0))
    prefix_len = 0
    if embeds is not None:  # VLM: image prefix + prefix-LM mask
        x = _constrain(jnp.concatenate([embeds.astype(x.dtype), x], axis=1))
        prefix_len = embeds.shape[1]
    Ttot = x.shape[1]
    if start_pos is not None:
        positions = (start_pos[:, None]
                     + jnp.arange(Ttot)[None, :]).astype(jnp.int32)
    else:
        positions = jnp.arange(Ttot)
    enc_out = None
    if cfg.encoder_layers and frame_embeds is not None:
        enc_out = encode(params, cfg, frame_embeds)
    if use_chunked is None:
        use_chunked = Ttot > 2048
    mask_kind = "prefix" if prefix_len else "causal"
    state_seq = None
    if state_rows is not None and last_pos is not None:
        state_seq = last_pos.astype(jnp.int32) + 1
    x, new_cache, aux = _run_stack(
        params, cfg, x, positions=positions, cache=cache, mask_kind=mask_kind,
        prefix_len=prefix_len, adapter_idx=adapter_idx, enc_out=enc_out,
        use_chunked=use_chunked, fill_cache=cache is not None, remat=remat,
        block_tbl=block_tbl, chunk_ids=chunk_ids,
        use_paged_kernel=use_paged_kernel, lora_kernel=lora_kernel,
        state_rows=state_rows, state_seq=state_seq)
    if last_pos is not None:
        # bucketed serving prefill: rows are right-padded, so the logit that
        # samples the first output token lives at a per-row index, not -1
        idx = jnp.broadcast_to(last_pos[:, None, None].astype(jnp.int32),
                               (x.shape[0], 1, x.shape[-1]))
        logits = _logits(params, cfg, jnp.take_along_axis(x, idx, axis=1))
        return logits, new_cache, aux
    if last_only:
        # prefill fast path: only the last position feeds the LM head —
        # avoids a (B, T, V) logits tensor (and its vocab-parallel
        # collective) entirely
        logits = _logits(params, cfg, x[:, -1:])
        return logits, new_cache, aux
    logits = _logits(params, cfg, x[:, -T:] if prefix_len else x)
    return logits, new_cache, aux


def decode_step(params: Params, cfg: ModelConfig, token, cache, pos, *,
                adapter_idx=None, block_tbl=None,
                use_paged_kernel: bool = False,
                lora_kernel: Optional[bool] = None,
                state_rows=None
                ) -> Tuple[jnp.ndarray, Dict]:
    """ONE decode step. token: (B,) int32; pos: () int32 absolute position,
    or (B,) int32 per-row positions (continuous batching: each slot decodes
    at its own depth); cache: filled cache pytree — contiguous ring caches,
    or a paged block-pool cache addressed via block_tbl (B, MB) int32.
    ``use_paged_kernel`` routes paged attention through the in-kernel
    block-table walk instead of the gather reference.  ``state_rows``
    (B,) int32 addresses REC/SSD per-slot state rows (hybrid serving) —
    rows redirected to the garbage row (stalled slots) compute on junk and
    write junk back there, leaving their real state untouched.
    Returns (logits (B, V), new_cache)."""
    B = token.shape[0]
    x = _constrain(jnp.take(params["embed"], token[:, None],
                            axis=0))  # (B, 1, D)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    else:
        positions = pos[:, None].astype(jnp.int32)
    x, new_cache, _ = _run_stack(
        params, cfg, x, positions=positions, cache=cache, mask_kind="causal",
        prefix_len=0, adapter_idx=adapter_idx, enc_out=None,
        use_chunked=False, fill_cache=False, remat=False,
        block_tbl=block_tbl, use_paged_kernel=use_paged_kernel,
        lora_kernel=lora_kernel, state_rows=state_rows)
    return _logits(params, cfg, x)[:, 0], new_cache


init_cache = cache_lib.init_cache
