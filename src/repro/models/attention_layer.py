"""GQA/MQA/MHA attention layer with runtime-selectable paper modes.

Phases:
  train    — dense causal SDA (paper techniques target inference traffic)
  prefill  — dense compute; builds the mode-specific decode cache
  decode   — one token; T1/T2/T3 paths via repro.core.attention

Decomposed (T1) rope handling: position rotations do not commute with W_K, so
on RoPE architectures the decomposed mode uses the *decoupled* form (a small
roped slice of each head cached verbatim; content dims decomposed through the
X-cache) — exactly DeepSeek-MLA's construction. On absolute-position archs
(musicgen, opt) rope_dims == 0 and T1 is EXACT vs dense. See DESIGN.md §2.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.common.param import ParamDef
from repro.configs.base import AttentionRuntime, CPQCfg, ModelConfig
from repro.core import attention as core_attn
from repro.core import kv_cache as kvc
from repro.core.flash_ref import attention_auto
from repro.distributed.sharding import constrain
from repro.models.layers import apply_rope, apply_rope_rows, rms_norm_vec, rope_tables


def decoupled_rope_dims(cfg: ModelConfig) -> int:
    """Roped head-dim slice cached verbatim in decomposed mode (0 => exact T1)."""
    if cfg.pos_embedding != "rope":
        return 0
    return min(32, (cfg.head_dim // 4) * 2)


# -------------------------------------------------------------------- defs


def attn_defs(cfg: ModelConfig, cross: bool = False):
    d, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    p = {
        "wq": ParamDef((d, H * Dh), dt, ("embed", "heads"), init="fan_in"),
        "wk": ParamDef((d, KV * Dh), dt, ("embed", "kv_heads"), init="fan_in"),
        "wv": ParamDef((d, KV * Dh), dt, ("embed", "kv_heads"), init="fan_in"),
        "wo": ParamDef((H * Dh, d), dt, ("heads", "embed"), init="fan_in"),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamDef((H * Dh,), jnp.float32, (None,), init="zeros")
        p["bk"] = ParamDef((KV * Dh,), jnp.float32, (None,), init="zeros")
        p["bv"] = ParamDef((KV * Dh,), jnp.float32, (None,), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = ParamDef((Dh,), jnp.float32, (None,), init="ones")
        p["k_norm"] = ParamDef((Dh,), jnp.float32, (None,), init="ones")
    if cross:
        p["gate"] = ParamDef((), jnp.float32, (), init="zeros")
    return p


# ----------------------------------------------------------------- helpers


def _project_qkv(cfg: ModelConfig, p, x: jax.Array, xkv: Optional[jax.Array] = None):
    """x: (B, T, D) -> q (B,T,H,Dh), k/v (B,S,KV,Dh). xkv overrides the kv
    source (cross-attention)."""
    B, T, _ = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    src = x if xkv is None else xkv
    S = src.shape[1]
    q = x @ p["wq"]
    k = src @ p["wk"]
    v = src @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = constrain(q.reshape(B, T, H, Dh), "act_batch", None, "act_heads", None)
    k = constrain(k.reshape(B, S, KV, Dh), "act_batch", None, "act_kv", None)
    v = constrain(v.reshape(B, S, KV, Dh), "act_batch", None, "act_kv", None)
    if cfg.qk_norm:
        q = rms_norm_vec(q, p["q_norm"])
        k = rms_norm_vec(k, p["k_norm"])
    return q, k, v


def _rope_qk(cfg: ModelConfig, q, k, positions_q, positions_k, dims: int | None = None):
    """Apply rope to the first ``dims`` head dims (all if None)."""
    if cfg.pos_embedding != "rope":
        return q, k
    d = q.shape[-1] if dims is None else dims
    if d == 0:
        return q, k
    cq, sq = rope_tables(positions_q, d, cfg.rope_theta)
    ck, sk = rope_tables(positions_k, d, cfg.rope_theta)
    q = q.at[..., :d].set(apply_rope(q[..., :d], cq, sq)) if d < q.shape[-1] else apply_rope(q, cq, sq)
    k = k.at[..., :d].set(apply_rope(k[..., :d], ck, sk)) if d < k.shape[-1] else apply_rope(k, ck, sk)
    return q, k


def _rope_qk_rows(cfg: ModelConfig, q, k, positions, dims: int | None = None):
    """Per-row decode rope: positions (B,), q/k (B, 1, H|KV, D) — every
    request row sits at its own position (continuous batching)."""
    if cfg.pos_embedding != "rope":
        return q, k
    d = q.shape[-1] if dims is None else dims
    if d == 0:
        return q, k
    cos, sin = rope_tables(positions, d, cfg.rope_theta)  # (B, d/2)
    q = (q.at[..., :d].set(apply_rope_rows(q[..., :d], cos, sin))
         if d < q.shape[-1] else apply_rope_rows(q, cos, sin))
    k = (k.at[..., :d].set(apply_rope_rows(k[..., :d], cos, sin))
         if d < k.shape[-1] else apply_rope_rows(k, cos, sin))
    return q, k


def _wk_wv_heads(cfg: ModelConfig, p):
    """Weight views for the T1 decomposed path: (Dm, KV, Dh) each, with the
    roped slice removed from W_K (content dims only)."""
    d, KV, Dh = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    r = decoupled_rope_dims(cfg)
    wk = p["wk"].reshape(d, KV, Dh)
    wv = p["wv"].reshape(d, KV, Dh)
    return wk[..., r:], wv, r


def _out(cfg: ModelConfig, p, o: jax.Array) -> jax.Array:
    B, T = o.shape[:2]
    y = o.reshape(B, T, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return constrain(y, "act_batch", None, None)


def _scale(cfg: ModelConfig) -> float:
    return cfg.head_dim ** -0.5


# ------------------------------------------------------------------- train


def attn_train(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array) -> jax.Array:
    q, k, v = _project_qkv(cfg, p, x)
    q, k = _rope_qk(cfg, q, k, positions, positions)
    o = attention_auto(q, k, v, _scale(cfg), causal=True)
    return _out(cfg, p, o)


def xattn_train(cfg: ModelConfig, p, x: jax.Array, patches: jax.Array) -> jax.Array:
    """Gated cross-attention over (stub) patch embeddings; non-causal."""
    q, k, v = _project_qkv(cfg, p, x, xkv=patches)
    o = attention_auto(q, k, v, _scale(cfg), causal=False)
    return _out(cfg, p, o) * jnp.tanh(p["gate"]).astype(x.dtype)


# ----------------------------------------------------------------- serving


class AttnCacheBundle(NamedTuple):
    """Cache plus the static per-layer side data decode needs."""

    cache: kvc.Cache


def init_attn_cache(cfg: ModelConfig, rt: AttentionRuntime, batch: int, n_max: int):
    return core_attn.init_cache(
        rt, batch=batch, n_max=n_max, kv=cfg.num_kv_heads, dh=cfg.head_dim,
        d_model=cfg.d_model, rope_dims=decoupled_rope_dims(cfg), dtype=cfg.param_dtype)


def attn_prefill(cfg: ModelConfig, rt: AttentionRuntime, p, x: jax.Array,
                 positions: jax.Array, cache: kvc.Cache):
    """Dense prefill compute + mode-specific cache build. x is the NORMED
    block input (the exact T1 operand)."""
    q, k, v = _project_qkv(cfg, p, x)
    r = decoupled_rope_dims(cfg)
    if rt.mode in ("decomposed", "decomposed_cpq"):
        # decoupled: rope only the cached slice; content dims stay position-free
        q, k = _rope_qk(cfg, q, k, positions, positions, dims=r)
        k_rope = k[..., :r]
        scores_k, scores_v = k, v  # exact dense math for the prefill pass
        cache = core_attn.prefill_into_cache(
            rt, cache, k=k, v=v, x=x, k_rope=k_rope,
            length=jnp.asarray(x.shape[1], jnp.int32))
    else:
        q, k = _rope_qk(cfg, q, k, positions, positions)
        scores_k, scores_v = k, v
        cache = core_attn.prefill_into_cache(
            rt, cache, k=k, v=v, x=x, k_rope=None,
            length=jnp.asarray(x.shape[1], jnp.int32))
    o = attention_auto(q, scores_k, scores_v, _scale(cfg), causal=True)
    return _out(cfg, p, o), cache


def attn_decode(cfg: ModelConfig, rt: AttentionRuntime, p, x_t: jax.Array,
                pos: jax.Array, cache: kvc.Cache):
    """One-token decode. x_t: (B, 1, D) normed block input; pos: () int32."""
    q, k, v = _project_qkv(cfg, p, x_t)
    r = decoupled_rope_dims(cfg)
    positions_t = pos[None] if pos.ndim == 0 else pos

    if rt.mode in ("decomposed", "decomposed_cpq"):
        q, k = _rope_qk(cfg, q, k, positions_t, positions_t, dims=r)
        wk_nope, wv, _ = _wk_wv_heads(cfg, p)
        out, cache = core_attn.decode_attend(
            rt, cache, q=q, k_t=k, v_t=v, x_t=x_t, k_rope_t=k[..., :r],
            q_nope=q[..., r:], q_rope=q[..., :r], w_k_nope=wk_nope, w_v=wv,
            scale=_scale(cfg))
    else:
        q, k = _rope_qk(cfg, q, k, positions_t, positions_t)
        out, cache = core_attn.decode_attend(
            rt, cache, q=q, k_t=k, v_t=v, x_t=None, k_rope_t=None,
            q_nope=None, q_rope=None, w_k_nope=None, w_v=None, scale=_scale(cfg))
    return _out(cfg, p, out), cache


def attn_prefill_chunk(cfg: ModelConfig, rt: AttentionRuntime, tier: int,
                       first: bool, p, x: jax.Array, positions: jax.Array,
                       slot, block_row, offset, valid, cache):
    """Chunked paged prefill: one prompt chunk's K/V (or X / CPQ codes) is
    written straight into slot ``slot``'s arena pages and its C queries
    attend the slot's pages [0, offset + valid) — the streaming admission
    path (no contiguous scratch cache). x: (1, C, D) normed block input at
    absolute ``positions``; ``tier``/``first`` are host-static."""
    from repro.serving import paged_cache as pgc

    decomposed = rt.mode in ("decomposed", "decomposed_cpq")
    r = decoupled_rope_dims(cfg)
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(cfg, p, x)
        q, k = _rope_qk(cfg, q, k, positions, positions,
                        dims=r if decomposed else None)

    with jax.named_scope("attn.kernel"):   # its cache writes: kv_write
        if decomposed:
            wk_nope, wv, _ = _wk_wv_heads(cfg, p)
            out, cache = pgc.chunk_attend_paged(
                rt, cache, tier=tier, first=first, slot=slot,
                block_row=block_row, offset=offset, valid=valid, q=q, k_c=k,
                v_c=v, x_c=x, k_rope_c=k[..., :r], q_nope=q[..., r:],
                q_rope=q[..., :r], w_k_nope=wk_nope, w_v=wv, scale=_scale(cfg))
        else:
            out, cache = pgc.chunk_attend_paged(
                rt, cache, tier=tier, first=first, slot=slot,
                block_row=block_row, offset=offset, valid=valid, q=q, k_c=k,
                v_c=v, x_c=None, k_rope_c=None, q_nope=None, q_rope=None,
                w_k_nope=None, w_v=None, scale=_scale(cfg))
    with jax.named_scope("attn.out"):
        return _out(cfg, p, out), cache


def init_paged_attn_cache(cfg: ModelConfig, rt: AttentionRuntime, serving,
                          tiered: bool = False):
    """Per-layer paged arena for the configured mode (serving/paged_cache.py).
    ``tiered`` adds the CPQ escalation arena next to the dense base arena."""
    from repro.serving import paged_cache as pgc

    kw = dict(kv=cfg.num_kv_heads, dh=cfg.head_dim)
    if tiered:
        assert rt.mode == "dense", "tier escalation starts from a dense base"
        return pgc.TieredPagedCache(
            dense=pgc.init_paged_dense(serving.num_pages, serving.page_size,
                                       dtype=cfg.param_dtype, **kw),
            cpq=pgc.init_paged_cpq(serving.escalated_pages, serving.page_size,
                                   serving.num_slots, cfg.num_kv_heads,
                                   cfg.head_dim, rt.cpq or CPQCfg()))
    if rt.mode == "dense":
        return pgc.init_paged_dense(serving.num_pages, serving.page_size,
                                    dtype=cfg.param_dtype, **kw)
    if rt.mode == "decomposed":
        return pgc.init_paged_x(serving.num_pages, serving.page_size, cfg.d_model,
                                cfg.num_kv_heads, decoupled_rope_dims(cfg),
                                cfg.param_dtype)
    if rt.mode == "cpq":
        return pgc.init_paged_cpq(serving.num_pages, serving.page_size,
                                  serving.num_slots, cfg.num_kv_heads,
                                  cfg.head_dim, rt.cpq)
    if rt.mode == "decomposed_cpq":
        return pgc.init_paged_cpq_x(serving.num_pages, serving.page_size,
                                    serving.num_slots, cfg.d_model,
                                    cfg.num_kv_heads, decoupled_rope_dims(cfg),
                                    rt.cpq, cfg.param_dtype)
    if rt.mode == "retrieval":
        return pgc.init_paged_retrieval(serving.num_pages, serving.page_size,
                                        serving.num_slots, cfg.num_kv_heads,
                                        cfg.head_dim, rt.retrieval, cfg.param_dtype)
    raise ValueError(rt.mode)


def attn_decode_rows(cfg: ModelConfig, rt: AttentionRuntime, p, x_t: jax.Array,
                     rows, cache):
    """One-token decode against a paged arena. x_t: (B, 1, D) normed block
    input; ``rows`` is a serving.paged_cache.RowState (per-row positions =
    rows.lengths)."""
    from repro.serving import paged_cache as pgc

    decomposed = rt.mode in ("decomposed", "decomposed_cpq")
    r = decoupled_rope_dims(cfg)
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(cfg, p, x_t)
        q, k = _rope_qk_rows(cfg, q, k, rows.lengths,
                             dims=r if decomposed else None)

    with jax.named_scope("attn.kernel"):   # its cache writes: kv_write
        if decomposed:
            wk_nope, wv, _ = _wk_wv_heads(cfg, p)
            out, cache = pgc.decode_attend_paged(
                rt, cache, rows, q=q, k_t=k, v_t=v, x_t=x_t,
                k_rope_t=k[..., :r], q_nope=q[..., r:], q_rope=q[..., :r],
                w_k_nope=wk_nope, w_v=wv, scale=_scale(cfg))
        else:
            out, cache = pgc.decode_attend_paged(
                rt, cache, rows, q=q, k_t=k, v_t=v, x_t=None, k_rope_t=None,
                q_nope=None, q_rope=None, w_k_nope=None, w_v=None,
                scale=_scale(cfg))
    with jax.named_scope("attn.out"):
        return _out(cfg, p, out), cache


# cross-attention serving: K/V are static per request (computed at prefill),
# decode just attends — no append, no CWC dependency (DESIGN.md §5).


def xattn_prefill(cfg: ModelConfig, p, x: jax.Array, patches: jax.Array):
    q, k, v = _project_qkv(cfg, p, x, xkv=patches)
    o = attention_auto(q, k, v, _scale(cfg), causal=False)
    cache = kvc.DenseKVCache(k, v, jnp.asarray(patches.shape[1], jnp.int32))
    return _out(cfg, p, o) * jnp.tanh(p["gate"]).astype(x.dtype), cache


def xattn_decode(cfg: ModelConfig, p, x_t: jax.Array, cache: kvc.DenseKVCache):
    q = (x_t @ p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
    B, T = x_t.shape[:2]
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm_vec(q, p["q_norm"])
    o = core_attn.dense_attention(q, cache.k, cache.v, _scale(cfg), causal=False,
                                  kv_length=cache.length)
    return _out(cfg, p, o) * jnp.tanh(p["gate"]).astype(x_t.dtype), cache
