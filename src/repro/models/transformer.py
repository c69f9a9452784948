"""Layer dispatch + block assembly.

A layer is (mixer, mlp) from the config's block_pattern. The decoder stack is
lowered as ``lax.scan`` over stacked same-position layers (HLO size — and
hence compile time and remat behaviour — is independent of depth).
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.common.param import stack_defs
from repro.configs.base import AttentionRuntime, ModelConfig
from repro.models import attention_layer as attn
from repro.models import mamba as mamba_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import apply_mlp, apply_norm, mlp_defs, norm_defs


# --------------------------------------------------------------------- defs


def layer_defs(cfg: ModelConfig, mixer: str, mlp: str):
    d: dict[str, Any] = {"norm1": norm_defs(cfg)}
    if mixer == "attn":
        d["mixer"] = attn.attn_defs(cfg)
    elif mixer == "xattn":
        d["mixer"] = attn.attn_defs(cfg, cross=True)
    elif mixer == "mla":
        d["mixer"] = mla_lib.mla_defs(cfg)
    elif mixer == "mamba":
        d["mixer"] = mamba_lib.mamba_defs(cfg)
    elif mixer == "mlstm":
        d["mixer"] = xlstm_lib.mlstm_defs(cfg)
    elif mixer == "slstm":
        d["mixer"] = xlstm_lib.slstm_defs(cfg)
    else:
        raise ValueError(mixer)
    if mlp == "dense":
        d["norm2"] = norm_defs(cfg)
        d["mlp"] = mlp_defs(cfg)
    elif mlp == "moe":
        d["norm2"] = norm_defs(cfg)
        d["mlp"] = moe_lib.moe_defs(cfg)
    return d


def stacked_block_defs(cfg: ModelConfig):
    """One stacked def-tree per position in the block pattern."""
    return [stack_defs(layer_defs(cfg, mixer, mlp), cfg.num_blocks, axis_name="layers")
            for mixer, mlp in cfg.block_pattern]


# -------------------------------------------------------------------- train


def _apply_mlp_part(cfg: ModelConfig, mlp: str, p, x, serve: bool = True):
    """The MLP sublayer. Serving runs the dropless expert layer (its own
    ``moe.*`` scopes); training (``serve=False``) the capacity dispatch with
    its load-balance loss."""
    if mlp == "none":
        return x, jnp.zeros((), jnp.float32)
    with jax.named_scope("norm"):
        h = apply_norm(cfg, p["norm2"], x)
    if mlp == "moe":
        if serve:
            return x + moe_lib.serve_moe(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)
        with jax.named_scope("mlp"):
            y, aux = moe_lib.apply_moe(cfg, p["mlp"], h)
        return x + y, aux
    with jax.named_scope("mlp"):
        return x + apply_mlp(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)


def layer_train(cfg: ModelConfig, kind: tuple[str, str], p, x: jax.Array,
                positions: jax.Array, patches: Optional[jax.Array]):
    mixer, mlp = kind
    h = apply_norm(cfg, p["norm1"], x)
    if mixer == "attn":
        x = x + attn.attn_train(cfg, p["mixer"], h, positions)
    elif mixer == "xattn":
        x = x + attn.xattn_train(cfg, p["mixer"], h, patches)
    elif mixer == "mla":
        x = x + mla_lib.mla_train(cfg, p["mixer"], h, positions)
    elif mixer == "mamba":
        y, _ = mamba_lib.mamba_forward(cfg, p["mixer"], h)
        x = x + y
    elif mixer == "mlstm":
        y, _ = xlstm_lib.mlstm_forward(cfg, p["mixer"], h)
        x = x + y
    elif mixer == "slstm":
        y, _ = xlstm_lib.slstm_forward(cfg, p["mixer"], h)
        x = x + y
    return _apply_mlp_part(cfg, mlp, p, x, serve=False)


# ------------------------------------------------------------------ serving


def layer_cache_init(cfg: ModelConfig, rt: AttentionRuntime, kind: tuple[str, str],
                     batch: int, n_max: int, n_patches: int):
    mixer, _ = kind
    if mixer == "attn":
        return attn.init_attn_cache(cfg, rt, batch, n_max)
    if mixer == "xattn":
        from repro.core import kv_cache as kvc
        return kvc.init_dense(batch, n_patches, cfg.num_kv_heads, cfg.head_dim,
                              cfg.param_dtype)
    if mixer == "mla":
        return mla_lib.init_mla_cache(cfg, rt, batch, n_max)
    if mixer == "mamba":
        return mamba_lib.init_mamba_state(cfg, batch)
    if mixer == "mlstm":
        return xlstm_lib.init_mlstm_state(cfg, batch)
    if mixer == "slstm":
        return xlstm_lib.init_slstm_state(cfg, batch)
    raise ValueError(mixer)


def layer_paged_cache_init(cfg: ModelConfig, rt: AttentionRuntime,
                           kind: tuple[str, str], serving, tiered: bool):
    """Paged arena for attention mixers; slot-indexed contiguous state for
    everything else (recurrent state is O(1)/request, xattn K/V is static
    per request — neither needs paging)."""
    mixer, _ = kind
    if mixer == "attn":
        return attn.init_paged_attn_cache(cfg, rt, serving, tiered)
    if mixer == "mla":
        return mla_lib.init_paged_mla_cache(cfg, rt, serving)
    return layer_cache_init(cfg, rt, kind, serving.num_slots,
                            serving.max_len, cfg.num_patch_tokens)


def layer_decode_rows(cfg: ModelConfig, rt: AttentionRuntime, kind: tuple[str, str],
                      p, x_t: jax.Array, rows, cache):
    """Continuous-batching decode: per-row positions/lengths via ``rows``
    (serving.paged_cache.RowState). Non-attention mixers are position-free and
    reuse their contiguous decode; retired slots' garbage state is overwritten
    at the next admission."""
    mixer, mlp = kind
    with jax.named_scope("norm"):
        h = apply_norm(cfg, p["norm1"], x_t)
    if mixer == "attn":
        y, cache = attn.attn_decode_rows(cfg, rt, p["mixer"], h, rows, cache)
    elif mixer == "xattn":
        y, cache = attn.xattn_decode(cfg, p["mixer"], h, cache)
    elif mixer == "mla":
        y, cache = mla_lib.mla_decode_rows(cfg, rt, p["mixer"], h, rows, cache)
    elif mixer == "mamba":
        y, cache = mamba_lib.mamba_decode(cfg, p["mixer"], h, cache)
    elif mixer == "mlstm":
        y, cache = xlstm_lib.mlstm_decode(cfg, p["mixer"], h, cache)
    elif mixer == "slstm":
        y, cache = xlstm_lib.slstm_decode(cfg, p["mixer"], h, cache)
    with (jax.named_scope("attn.out") if mixer in ("attn", "mla")
          else contextlib.nullcontext()):
        x_t = x_t + y
    x_t, _ = _apply_mlp_part(cfg, mlp, p, x_t)
    return x_t, cache


def layer_prefill_chunk(cfg: ModelConfig, rt: AttentionRuntime, tier: int,
                        first: bool, kind: tuple[str, str], p, x: jax.Array,
                        positions: jax.Array, slot, block_row, offset, valid,
                        cache):
    """Chunked paged prefill of one prompt chunk for one request slot: the
    chunk's cache payload is written straight into the slot's arena pages
    (serving/paged_cache.chunk_attend_paged). Recurrent and cross-attention
    mixers never reach here — the engine keeps their exact one-shot
    admission (state integration cannot be cut at page boundaries)."""
    mixer, mlp = kind
    with jax.named_scope("norm"):
        h = apply_norm(cfg, p["norm1"], x)
    if mixer == "attn":
        y, cache = attn.attn_prefill_chunk(cfg, rt, tier, first, p["mixer"], h,
                                           positions, slot, block_row, offset,
                                           valid, cache)
    elif mixer == "mla":
        y, cache = mla_lib.mla_prefill_chunk(cfg, rt, tier, first, p["mixer"],
                                             h, positions, slot, block_row,
                                             offset, valid, cache)
    else:
        raise ValueError(f"chunked prefill has no {mixer!r} path "
                         "(engine falls back to one-shot admission)")
    with (jax.named_scope("attn.out") if mixer in ("attn", "mla")
          else contextlib.nullcontext()):
        x = x + y
    x, _ = _apply_mlp_part(cfg, mlp, p, x)
    return x, cache


def layer_prefill(cfg: ModelConfig, rt: AttentionRuntime, kind: tuple[str, str], p,
                  x: jax.Array, positions: jax.Array, patches: Optional[jax.Array],
                  cache):
    mixer, mlp = kind
    h = apply_norm(cfg, p["norm1"], x)
    if mixer == "attn":
        y, cache = attn.attn_prefill(cfg, rt, p["mixer"], h, positions, cache)
    elif mixer == "xattn":
        y, cache = attn.xattn_prefill(cfg, p["mixer"], h, patches)
    elif mixer == "mla":
        y, cache = mla_lib.mla_prefill(cfg, rt, p["mixer"], h, positions, cache)
    elif mixer == "mamba":
        y, cache = mamba_lib.mamba_forward(cfg, p["mixer"], h)
    elif mixer == "mlstm":
        y, cache = xlstm_lib.mlstm_forward(cfg, p["mixer"], h)
    elif mixer == "slstm":
        y, cache = xlstm_lib.slstm_forward(cfg, p["mixer"], h)
    x = x + y
    x, _ = _apply_mlp_part(cfg, mlp, p, x)
    return x, cache


def layer_decode(cfg: ModelConfig, rt: AttentionRuntime, kind: tuple[str, str], p,
                 x_t: jax.Array, pos: jax.Array, cache):
    mixer, mlp = kind
    h = apply_norm(cfg, p["norm1"], x_t)
    if mixer == "attn":
        y, cache = attn.attn_decode(cfg, rt, p["mixer"], h, pos, cache)
    elif mixer == "xattn":
        y, cache = attn.xattn_decode(cfg, p["mixer"], h, cache)
    elif mixer == "mla":
        y, cache = mla_lib.mla_decode(cfg, rt, p["mixer"], h, pos, cache)
    elif mixer == "mamba":
        y, cache = mamba_lib.mamba_decode(cfg, p["mixer"], h, cache)
    elif mixer == "mlstm":
        y, cache = xlstm_lib.mlstm_decode(cfg, p["mixer"], h, cache)
    elif mixer == "slstm":
        y, cache = xlstm_lib.slstm_decode(cfg, p["mixer"], h, cache)
    x_t = x_t + y
    x_t, _ = _apply_mlp_part(cfg, mlp, p, x_t)
    return x_t, cache
