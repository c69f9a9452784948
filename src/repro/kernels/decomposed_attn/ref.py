"""Pure-jnp oracle for the decomposed-attention decode kernel: the P-stage of
core.decomposed_attention (shared-rope layout)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_decomposed_decode_ref(r, q_rope, x_pages, kr_pages, block_table,
                                lengths, scale):
    """Oracle for the paged T1/MLA kernel, straight from the paged layout:
    r: (B, H, Dm); q_rope: (B, H, Rr) (Rr may be 0); x_pages: (P, page, Dm);
    kr_pages: (P, page, KV_r, Rr) (KV_r == 1 shared / per-kv-head);
    block_table: (B, max_blocks) (0 = null page); lengths: (B,).
    -> P: (B, H, Dm); positions >= lengths[b] masked, empty rows zero."""
    B, H, Dm = r.shape
    page = x_pages.shape[1]
    nb = block_table.shape[1]
    x = jnp.take(x_pages, block_table, axis=0).reshape(B, nb * page, Dm)
    s = jnp.einsum("bhm,bnm->bhn", r.astype(jnp.float32),
                   x.astype(jnp.float32))
    if q_rope.shape[-1] > 0:
        kv_r, Rr = kr_pages.shape[2], kr_pages.shape[3]
        g_r = H // kv_r
        kr = jnp.take(kr_pages, block_table, axis=0).reshape(
            B, nb * page, kv_r, Rr)
        qg = q_rope.reshape(B, kv_r, g_r, Rr)
        s = s + jnp.einsum("bkgr,bnkr->bkgn", qg.astype(jnp.float32),
                           kr.astype(jnp.float32)).reshape(B, H, nb * page)
    s = s * scale
    pos = jnp.arange(nb * page, dtype=jnp.int32)
    live = pos[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(w, axis=-1, keepdims=True)
    p = jnp.einsum("bhn,bnm->bhm", w, x.astype(jnp.float32))
    p = p / jnp.maximum(l, 1e-30)
    return jnp.where((lengths > 0)[:, None, None], p,
                     0.0).astype(x_pages.dtype)


def paged_decomposed_prefill_ref(r, q_rope, x_pages, kr_pages, block_row,
                                 offset, valid, scale):
    """Oracle for the chunked paged T1/MLA prefill kernel, straight from the
    paged layout: r: (C, H, Dm) = q_nope W_K^T for queries at positions
    offset + i; q_rope: (C, H, Rr) (Rr may be 0); x_pages: (P, page, Dm);
    kr_pages: (P, page, KV_r, Rr); block_row: (max_blocks,). Keys are the
    slot's positions < offset + valid, causal. -> P: (C, H, Dm); rows past
    ``valid`` are padding."""
    C, H, Dm = r.shape
    N = block_row.shape[0] * x_pages.shape[1]
    x = jnp.take(x_pages, block_row, axis=0).reshape(N, Dm).astype(jnp.float32)
    s = jnp.einsum("chm,nm->chn", r.astype(jnp.float32), x)
    if q_rope.shape[-1] > 0:
        kv_r, Rr = kr_pages.shape[2], kr_pages.shape[3]
        kr = jnp.take(kr_pages, block_row, axis=0).reshape(N, kv_r, Rr)
        qg = q_rope.reshape(C, kv_r, H // kv_r, Rr)
        s = s + jnp.einsum("ckgr,nkr->ckgn", qg.astype(jnp.float32),
                           kr.astype(jnp.float32)).reshape(C, H, N)
    s = s * scale
    pos = jnp.arange(N, dtype=jnp.int32)
    qpos = offset + jnp.arange(C, dtype=jnp.int32)
    live = (pos[None, :] < offset + valid) & (pos[None, :] <= qpos[:, None])
    s = jnp.where(live[:, None, :], s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(w, axis=-1, keepdims=True)
    p = jnp.einsum("chn,nm->chm", w, x) / jnp.maximum(l, 1e-30)
    return p.astype(x_pages.dtype)


def decomposed_decode_ref(r, q_rope, x, k_rope, length, scale):
    """r: (B,H,Dm); q_rope: (B,H,Rr); x: (B,N,Dm); k_rope: (B,N,Rr);
    -> P: (B, H, Dm)."""
    s = jnp.einsum("bhm,bnm->bhn", r, x).astype(jnp.float32)
    if q_rope.shape[-1] > 0:
        s = s + jnp.einsum("bhr,bnr->bhn", q_rope, k_rope).astype(jnp.float32)
    s = s * scale
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    s = jnp.where((pos < length)[None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhn,bnm->bhm", w.astype(x.dtype), x)
