"""Public op: full T1 decode attention via the fused kernel.

Splits the work exactly as the paper does: the two tiny dense matmuls
(R = q W_K^T, out = P W_V) run as ordinary XLA ops; the O(N) cache sweep —
both cascaded MatMuls + online softmax — is the Pallas kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import kernels as K
from repro.kernels.decomposed_attn.kernel import (decomposed_decode_fwd,
                                                  paged_decomposed_decode_fwd,
                                                  paged_decomposed_prefill_fwd,
                                                  paged_latent_decode_fwd,
                                                  paged_latent_prefill_fwd)


@partial(jax.jit, static_argnames=("scale", "block_n", "interpret"))
def decomposed_decode_tpu(q_nope, q_rope, x_cache, k_rope, w_k_nope, w_v,
                          length, scale: float, block_n: int = 512,
                          interpret: bool | None = None):
    """q_nope: (B,1,H,Dn); q_rope: (B,1,H,Rr); x_cache: (B,N,Dm);
    k_rope: (B,N,1,Rr) shared across heads (MLA layout) or Rr == 0;
    w_k_nope: (Dm, KV, Dn); w_v: (Dm, KV, Dv). Returns (B, 1, H, Dv)."""
    B, _, H, Dn = q_nope.shape
    Dm = x_cache.shape[-1]
    KV, Dv = w_v.shape[1], w_v.shape[2]
    g = H // KV

    # R = q W_K^T  (first cascaded MatMul — tiny for decode)
    qg = q_nope[:, 0].reshape(B, KV, g, Dn)
    r = jnp.einsum("bkgd,mkd->bkgm", qg, w_k_nope).reshape(B, H, Dm)

    kr = k_rope[:, :, 0, :] if k_rope is not None and k_rope.shape[-1] > 0 \
        else jnp.zeros((B, x_cache.shape[1], 0), x_cache.dtype)
    qr = q_rope[:, 0] if q_rope is not None and q_rope.shape[-1] > 0 \
        else jnp.zeros((B, H, 0), x_cache.dtype)

    p = K.platform_call(decomposed_decode_fwd, r.astype(x_cache.dtype),
                        qr.astype(x_cache.dtype), x_cache, kr, length,
                        scale=scale, block_n=block_n, interpret=interpret)

    # out = P W_V  (second tiny dense MatMul)
    pg = p.reshape(B, KV, g, Dm)
    out = jnp.einsum("bkgm,mkd->bkgd", pg, w_v).reshape(B, 1, H, Dv)
    return out


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decomposed_prefill_tpu(q_nope, q_rope, x_pages, kr_pages,
                                 block_row, offset, valid, w_k_nope, w_v,
                                 scale: float, interpret: bool | None = None):
    """Chunked paged T1/MLA prefill for one slot: the admission chunk's C
    queries attend the slot's X (+roped key) pages [0, offset + valid)
    through its block-table row (the chunk's X rows already live in those
    pages). q_nope: (1, C, H, Dn); q_rope: (1, C, H, Rr) or None/Rr == 0;
    block_row: (max_blocks,) int32 (0 = null page); offset/valid: () int32;
    w_k_nope: (Dm, KV, Dn); w_v: (Dm, KV, Dv). -> (1, C, H, Dv); rows past
    ``valid`` are jit-padding garbage."""
    _, C, H, Dn = q_nope.shape
    Dm = x_pages.shape[-1]
    KV, Dv = w_v.shape[1], w_v.shape[2]
    g = H // KV

    # R = q W_K^T  (first cascaded MatMul — tiny for a chunk)
    qg = q_nope[0].reshape(C, KV, g, Dn)
    r = jnp.einsum("ckgd,mkd->ckgm", qg, w_k_nope).reshape(C, H, Dm)

    qr = q_rope[0] if q_rope is not None and q_rope.shape[-1] > 0 \
        else jnp.zeros((C, H, 0), x_pages.dtype)

    p = K.platform_call(
        paged_decomposed_prefill_fwd, r.astype(x_pages.dtype),
        qr.astype(x_pages.dtype), x_pages, kr_pages, block_row, offset, valid,
        scale=scale, interpret=interpret)

    # out = P W_V  (second tiny dense MatMul)
    pg = p.reshape(C, KV, g, Dm)
    return jnp.einsum("ckgm,mkd->ckgd", pg, w_v).reshape(1, C, H, Dv)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decomposed_decode_tpu(q_nope, q_rope, x_pages, kr_pages,
                                block_table, lengths, w_k_nope, w_v,
                                scale: float, interpret: bool | None = None):
    """Paged T1/MLA decode over a (P, page, Dm) X arena through its block
    table — no contiguous logical view. q_nope: (B, 1, H, Dn); q_rope:
    (B, 1, H, Rr) or None/Rr == 0; kr_pages: (P, page, KV_r, Rr) with
    KV_r == 1 (MLA shared rope) or per-kv-head; w_k_nope: (Dm, KV, Dn);
    w_v: (Dm, KV, Dv); block_table: (B, max_blocks) int32 (0 = null page);
    lengths: (B,) int32. Returns (B, 1, H, Dv)."""
    B, _, H, Dn = q_nope.shape
    Dm = x_pages.shape[-1]
    KV, Dv = w_v.shape[1], w_v.shape[2]
    g = H // KV

    # R = q W_K^T  (first cascaded MatMul — tiny for decode)
    qg = q_nope[:, 0].reshape(B, KV, g, Dn)
    r = jnp.einsum("bkgd,mkd->bkgm", qg, w_k_nope).reshape(B, H, Dm)

    qr = q_rope[:, 0] if q_rope is not None and q_rope.shape[-1] > 0 \
        else jnp.zeros((B, H, 0), x_pages.dtype)

    p = K.platform_call(
        paged_decomposed_decode_fwd, r.astype(x_pages.dtype),
        qr.astype(x_pages.dtype), x_pages, kr_pages, block_table, lengths,
        scale=scale, interpret=interpret)

    # out = P W_V  (second tiny dense MatMul)
    pg = p.reshape(B, KV, g, Dm)
    return jnp.einsum("bkgm,mkd->bkgd", pg, w_v).reshape(B, 1, H, Dv)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_decode_tpu(q_nope, q_rope, c_pages, rope_pages, block_table,
                            lengths, layer, w_uk, w_uv, scale: float,
                            interpret: bool | None = None):
    """Paged MLA decode (the absorbed form) over layer ``layer`` of a stacked
    latent arena (``PagedLatentCache``). q_nope: (B, 1, H, Dn); q_rope:
    (B, 1, H, R); w_uk: (H, Dc, Dn); w_uv: (H, Dc, Dv); block_table:
    (B, max_blocks) int32; lengths: (B,) int32; layer: () int32.
    Returns (B, 1, H, Dv)."""
    B, _, H, _ = q_nope.shape
    dt = c_pages.dtype
    r = jnp.einsum("bhd,hmd->bhm", q_nope[:, 0], w_uk)     # q_nope W_UK^T
    p = K.platform_call(paged_latent_decode_fwd, r.astype(dt),
                        q_rope[:, 0].astype(dt), c_pages, rope_pages,
                        block_table, lengths, layer, scale=scale,
                        interpret=interpret)
    return jnp.einsum("bhm,hmd->bhd", p, w_uv).reshape(B, 1, H, -1)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_latent_prefill_tpu(q_nope, q_rope, c_pages, rope_pages, block_row,
                             offset, valid, layer, w_uk, w_uv, scale: float,
                             interpret: bool | None = None):
    """Chunked paged MLA prefill for one slot over layer ``layer`` of a
    stacked latent arena. q_nope: (1, C, H, Dn); q_rope: (1, C, H, R);
    block_row: (max_blocks,) int32; offset/valid/layer: () int32.
    Returns (1, C, H, Dv); rows past ``valid`` are jit-padding garbage."""
    _, C, H, _ = q_nope.shape
    dt = c_pages.dtype
    r = jnp.einsum("chd,hmd->chm", q_nope[0], w_uk)
    p = K.platform_call(paged_latent_prefill_fwd, r.astype(dt),
                        q_rope[0].astype(dt), c_pages, rope_pages, block_row,
                        offset, valid, layer, scale=scale, interpret=interpret)
    return jnp.einsum("chm,hmd->chd", p, w_uv)[None]
