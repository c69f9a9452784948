"""Pure-jnp oracle for the flash attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def paged_flash_decode_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           block_table: jax.Array, lengths: jax.Array,
                           scale: float) -> jax.Array:
    """Oracle for the paged decode kernel, straight from the paged layout:
    q: (B, 1, H, Dh); k_pages/v_pages: (P, page, KV, Dh|Dv); block_table:
    (B, max_blocks) int32 (0 = null page); lengths: (B,). -> (B, 1, H, Dv).
    Positions >= lengths[b] (null pages, partial last page) are masked;
    lengths[b] == 0 rows return zeros."""
    B, _, H, Dh = q.shape
    page, KV = k_pages.shape[1], k_pages.shape[2]
    nb = block_table.shape[1]
    g = H // KV
    kl = jnp.take(k_pages, block_table, axis=0).reshape(B, nb * page, KV, Dh)
    vl = jnp.take(v_pages, block_table, axis=0).reshape(
        B, nb * page, KV, v_pages.shape[-1])
    qg = q[:, 0].reshape(B, KV, g, Dh)
    s = jnp.einsum("bkgd,bnkd->bkgn", qg.astype(jnp.float32),
                   kl.astype(jnp.float32)) * scale
    pos = jnp.arange(nb * page, dtype=jnp.int32)
    live = pos[None, :] < lengths[:, None]                      # (B, N)
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(w, axis=-1, keepdims=True)
    o = jnp.einsum("bkgn,bnkd->bkgd", w, vl.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)   # empty rows
    return o.reshape(B, 1, H, -1).astype(q.dtype)


def paged_flash_prefill_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                            block_row: jax.Array, offset: jax.Array,
                            valid: jax.Array, scale: float) -> jax.Array:
    """Oracle for the chunked paged prefill kernel, straight from the paged
    layout: q: (1, C, H, Dh) queries at positions offset + i attend the
    slot's positions < offset + valid causally through block_row
    (max_blocks,). -> (1, C, H, Dv); rows past ``valid`` are padding."""
    _, C, H, Dh = q.shape
    page, KV = k_pages.shape[1], k_pages.shape[2]
    N = block_row.shape[0] * page
    g = H // KV
    kl = jnp.take(k_pages, block_row, axis=0).reshape(N, KV, Dh)
    vl = jnp.take(v_pages, block_row, axis=0).reshape(N, KV, v_pages.shape[-1])
    qg = q[0].reshape(C, KV, g, Dh)
    s = jnp.einsum("ckgd,nkd->ckgn", qg.astype(jnp.float32),
                   kl.astype(jnp.float32)) * scale
    pos = jnp.arange(N, dtype=jnp.int32)
    qpos = offset + jnp.arange(C, dtype=jnp.int32)
    live = (pos[None, :] < offset + valid) & (pos[None, :] <= qpos[:, None])
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    l = jnp.sum(w, axis=-1, keepdims=True)
    o = jnp.einsum("ckgn,nkd->ckgd", w, vl.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)
    return o.reshape(1, C, H, -1).astype(q.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                        causal: bool = True) -> jax.Array:
    """q: (B, T, H, D); k/v: (B, S, KV, D) -> (B, T, H, Dv). Exact SDA."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.reshape(B, T, KV, g, D)
    s = jnp.einsum("btkgd,bskd->btkgs", qg, k).astype(jnp.float32) * scale
    if causal:
        pos_q = jnp.arange(T)[:, None]
        pos_k = jnp.arange(S)[None, :]
        s = jnp.where((pos_k <= pos_q)[None, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("btkgs,bskd->btkgd", w.astype(v.dtype), v)
    return o.reshape(B, T, H, v.shape[-1])
